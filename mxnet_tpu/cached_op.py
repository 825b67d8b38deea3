"""CachedOp: trace-once, compile-once graph execution (hybridize backend).

Role parity: reference ``src/imperative/cached_op.cc`` — Gluon's
``hybridize()`` traces ``hybrid_forward`` into an nnvm graph, then replays it
through a cached executor with static memory planning
(`cached_op.cc:1023 Forward`, `:861 StaticForward`, `:414 SetForwardGraph`);
when autograd is recording, the whole graph is recorded as ONE tape node
(`_CachedOp`, see `src/imperative/cached_op.cc:1077 DynamicBackward`).

TPU-native design: the graph IS an XLA program. We trace the Python callable
once per (shapes, dtypes, train-mode) signature with ``jax.jit`` — the
NDArray handles transparently carry tracers, so the whole eager op surface is
traceable with zero duplicated code. XLA then does what MXNet's passes did by
hand: memory planning (`src/nnvm/plan_memory.cc`), pointwise fusion
(`src/executor/pointwise_fusion_pass.cc`), op bulking, and static buffer
assignment (`static_alloc`/`static_shape` flags are accepted for API parity
and are effectively always-on under XLA).

Randomness: a fresh base PRNG key is an *argument* of the compiled program;
ops that need randomness split from it via ``random.push_trace_key`` — so
every execution of a cached graph sees new randomness while the trace stays
pure (the reference holds stateful cuDNN dropout descriptors in op state
instead).
"""
from __future__ import annotations

import re
import threading
import time
from collections import OrderedDict

import jax

from . import _tape
from . import aot as _aot
from . import config as _config
from . import pcache as _pcache
from . import random as _random
from .observability import attribution as _attr
from .observability import telemetry as _telemetry
from .observability import tracer as _trace

__all__ = ["CachedOp", "cache_stats", "reset_cache_stats"]


def _np_dtype(name):
    """dtype-string (as stored in cache signatures) -> numpy dtype,
    including the ml_dtypes extras ("bfloat16") jax registers."""
    import numpy as _np
    try:
        return _np.dtype(name)
    except TypeError:
        import ml_dtypes
        return _np.dtype(getattr(ml_dtypes, str(name)))


def _active_sharding(val):
    """The input's NamedSharding when it is committed onto a multi-device
    mesh — the part of program identity the (shape, dtype) cache
    signature can't see. jit specializes the compiled SPMD program on
    these, so AOT export must re-lower with the SAME shardings or it
    would serialize a different (single-device) program than the one
    dispatch actually ran. Uncommitted / single-device inputs record
    None and keep the exact pre-sharding behavior."""
    s = getattr(val, "sharding", None)
    mesh = getattr(s, "mesh", None)
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return None
    return s

# Process-wide executor-cache counters, aggregated across every CachedOp
# instance (the serving layer exports these through /metrics). A "miss" is
# an XLA compile; an "eviction" frees a compiled executable under the LRU
# bound (role of the reference's GetCachedOp registry bookkeeping).
_GLOBAL_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_STATS_LOCK = threading.Lock()


def cache_stats():
    """Process-wide executor-cache counters summed over all CachedOps:
    ``{"hits", "misses", "evictions"}``. ``misses`` == number of XLA
    compiles issued by CachedOp dispatch since the last reset."""
    with _STATS_LOCK:
        return dict(_GLOBAL_STATS)


def reset_cache_stats():
    """Zero the process-wide counters (per-instance counters are reset by
    dropping the instance)."""
    with _STATS_LOCK:
        for k in _GLOBAL_STATS:
            _GLOBAL_STATS[k] = 0


class CachedOp:
    """Compile-cached executor for a callable over NDArrays.

    ``fn`` takes NDArray positional args and returns an NDArray or a
    list/tuple of NDArrays. Calls dispatch to a jitted pure function,
    cache-keyed on input (shape, dtype) signature and train mode —
    the moral equivalent of `SetForwardGraph`'s shape-match check
    (reference `src/imperative/cached_op.cc:414`).
    """

    def __init__(self, fn, static_alloc=False, static_shape=False,
                 inline_limit=2, forward_bulk_size=None,
                 backward_bulk_size=None, name="CachedOp", capacity=None):
        self._fn = fn
        self._name = name
        # flags kept for API parity (cached_op.h:33-52); XLA makes them no-ops
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape,
                           inline_limit=inline_limit,
                           forward_bulk_size=forward_bulk_size,
                           backward_bulk_size=backward_bulk_size)
        # LRU-bounded executor cache: each entry holds a compiled XLA
        # executable, so unbounded shape churn (dynamic batch/seq sizes)
        # is a memory leak without a cap. capacity <= 0 disables the bound.
        if capacity is None:
            capacity = _config.get("MXNET_CACHED_OP_CAPACITY")
        self._capacity = int(capacity)
        self._cache = OrderedDict()
        # per-signature committed input shardings (mesh lanes only; see
        # _active_sharding) — what serialize() re-lowers against
        self._shardings = {}
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "aot_loads": 0}
        # the serving engine dispatches one CachedOp from many HTTP threads:
        # every _cache/_stats mutation happens under this lock. Compiles run
        # OUTSIDE it (an XLA compile can take seconds; serializing compiles
        # of different signatures would stall every other thread) — two
        # threads racing the same cold signature may both compile, and the
        # loser's executable is simply dropped on insert.
        self._dispatch_lock = threading.Lock()

    def cache_stats(self):
        """This instance's executor-cache counters plus occupancy:
        ``{"size", "capacity", "hits", "misses", "evictions",
        "aot_loads"}`` — ``aot_loads`` counts executables installed
        from serialized artifacts (zero XLA compiles)."""
        with self._dispatch_lock:
            out = dict(self._stats)
            out["size"] = len(self._cache)
        out["capacity"] = self._capacity
        return out

    def flops_per_call(self):
        """Analytic FLOPs of each resident executable, keyed by the full
        cache signature — input shapes/dtypes AND train mode, since the
        same shapes compile distinct train/eval executables (XLA cost
        model, computed at compile time): the per-executable number the
        MFU accounting multiplies by dispatch count. 0.0 entries mean
        the backend's cost model was unavailable (MFU is then
        underreported, never fabricated)."""
        with self._dispatch_lock:
            return {"%s|train=%s" % (sig[0], sig[1]): entry[4]
                    for sig, entry in self._cache.items()}

    def bytes_per_call(self):
        """Analytic bytes accessed per execution of each resident
        executable (XLA cost model, same keying as
        :meth:`flops_per_call`) — the denominator of the roofline
        arithmetic intensity. 0.0 = cost model unavailable (the
        executable classifies as ``unknown``, never a guess)."""
        with self._dispatch_lock:
            return {"%s|train=%s" % (sig[0], sig[1]): entry[6]
                    for sig, entry in self._cache.items()}

    def clear(self):
        """Drop every compiled executable (the LRU empties; counters
        keep their history). Unloading a served model must free its XLA
        programs — a retired fleet version holding ``len(buckets)``
        executables through this cache would be a device-memory leak."""
        with self._dispatch_lock:
            self._cache.clear()
            self._shardings.clear()

    def _signature(self, args):
        return (tuple((a.shape, str(a.dtype)) for a in args),
                _tape.is_training())

    def _make_pure(self, train):
        """The jit-able pure wrapper over ``self._fn`` at an explicit
        train mode (dispatch passes the current mode; serialize/
        deserialize pass the mode stored in the cache signature).
        Returns ``(pure, n_out_box, aux_handles_box)`` — the boxes fill
        on first trace."""
        from .ndarray.ndarray import NDArray
        fn = self._fn
        n_out_box = []
        aux_handles_box = []

        def pure(rng_key, *vals):
            nds = [NDArray(v) for v in vals]
            _random.push_trace_key(rng_key)
            prev_rec = _tape.set_recording(False)
            prev_train = _tape.set_training(train)
            sink = _tape.push_aux_sink()
            try:
                outs = fn(*nds)
            finally:
                _tape.pop_aux_sink()
                _tape.set_training(prev_train)
                _tape.set_recording(prev_rec)
                _random.pop_trace_key()
            multi = isinstance(outs, (list, tuple))
            outs_t = tuple(outs) if multi else (outs,)
            if not n_out_box:
                n_out_box.append((len(outs_t), multi))
                aux_handles_box.append([h for h, _ in sink])
            # aux writes (e.g. BatchNorm moving stats) ride as extra outputs
            return tuple(o._data for o in outs_t) + tuple(v for _, v in sink)

        # the program is jit_<op name> in the device trace and the HLO
        pure.__name__ = pure.__qualname__ = \
            re.sub(r"[^0-9A-Za-z_.]", "_", self._name) or "CachedOp"
        return pure, n_out_box, aux_handles_box

    def _compile(self, args):
        train = _tape.is_training()
        pure, n_out_box, aux_handles_box = self._make_pure(train)
        jitted = jax.jit(pure)
        # force trace now so n_out is known before first real dispatch;
        # with FLOPs accounting on, the forcing trace is lower() instead
        # of eval_shape() so the analytic FLOPs (XLA cost model, cached
        # on the cache entry — every dispatch then feeds the process
        # FlopsMeter at the cost of one float add, the source behind the
        # live mxtpu_mfu_percent / mxtpu_flops_total series) ride the
        # SAME trace rather than paying a second one
        specs = [jax.ShapeDtypeStruct(a.shape, a._data.dtype)
                 for a in args]
        # cost analysis is gated on MXNET_TELEMETRY_FLOPS alone: with it
        # off, attribution still measures dispatch wall but reports its
        # rows as `unknown` (no analytic numbers, no guessed ones)
        flops = 0.0
        nbytes = 0.0
        if int(_config.get("MXNET_TELEMETRY_FLOPS") or 0):
            try:
                lowered = jitted.lower(jax.random.PRNGKey(0), *specs)
            except Exception:  # fall back to the plain forcing trace
                jax.eval_shape(jitted, jax.random.PRNGKey(0), *specs)
            else:
                try:
                    cost = lowered.cost_analysis()
                    flops = float((cost or {}).get("flops", 0.0) or 0.0)
                    # "bytes accessed" (HBM traffic per execution) rides
                    # the same analysis: the roofline denominator
                    nbytes = float((cost or {}).get("bytes accessed",
                                                    0.0) or 0.0)
                except Exception:  # cost model unavailable on this backend
                    flops = 0.0
                    nbytes = 0.0
        else:
            jax.eval_shape(jitted, jax.random.PRNGKey(0), *specs)
        n_out, multi = n_out_box[0]
        return (jitted, n_out, multi, aux_handles_box[0], flops, False,
                nbytes)

    # ---- AOT export / load (cold-start: compile in CI, ship bytes) --------
    def _specs_for(self, sig, shardings=None):
        shapes, _ = sig
        if shardings is None:
            shardings = (None,) * len(shapes)
        return [jax.ShapeDtypeStruct(tuple(shape), _np_dtype(dtype),
                                     sharding=s)
                for (shape, dtype), s in zip(shapes, shardings)]

    def input_shardings(self, sig):
        """The committed input shardings signature ``sig`` was compiled
        against (None per arg on single-device lanes)."""
        with self._dispatch_lock:
            return self._shardings.get(sig)

    def record_shardings(self, sig, shardings):
        """Pre-seed ``sig``'s committed input shardings. Sharded engines
        call this after an AOT load (deserialized machine code carries
        no jax-level shardings), so a later re-export still lowers the
        same SPMD program instead of a single-device one."""
        with self._dispatch_lock:
            self._shardings[sig] = tuple(shardings)

    def signatures(self):
        """Cache signatures of the resident executables, LRU order."""
        with self._dispatch_lock:
            return list(self._cache)

    def lower(self, sig):
        """Re-lower resident signature ``sig`` through the jax AOT API —
        the program dispatch runs, against the committed input shardings
        it ran with — as a ``jax.stages.Lowered``: ``.compile()`` it to
        serialize the executable or to read its HLO. (The traced-dispatch
        path's own executable isn't directly extractable.)"""
        pure, _n_out_box, _aux_box = self._make_pure(sig[1])
        return jax.jit(pure).lower(
            jax.random.PRNGKey(0),
            *self._specs_for(sig, self.input_shardings(sig)))

    def serialize(self):
        """Capture every resident executable's *program* as
        PJRT-serialized bytes: a list of records for
        :func:`mxnet_tpu.aot.write_artifact`, keyed by the exact cache
        signature (shapes, dtypes, train mode) each was compiled under.

        Export re-lowers and compiles each signature through the jax AOT
        API (the traced-dispatch path's executable isn't directly
        extractable), so exporting costs one compile per signature —
        that is the point: the export runs ONCE in CI, and every serving
        restart after it compiles nothing. With the persistent compile
        cache enabled the re-compile here is itself a disk hit."""
        with self._dispatch_lock:
            sigs = [(sig, entry[4], entry[6])
                    for sig, entry in self._cache.items()]
        records = []
        for sig, flops, nbytes in sigs:
            blob, in_tree, out_tree, devices = \
                _aot.serialize_compiled(self.lower(sig).compile())
            records.append({"signature": sig, "train": sig[1],
                            "flops": flops, "bytes": nbytes,
                            "devices": devices, "blob": blob,
                            "in_tree": in_tree, "out_tree": out_tree})
        return records

    def deserialize(self, records):
        """Install serialized executables (``mxnet_tpu.aot`` records)
        into the cache WITHOUT compiling: each record's program loads as
        machine code, and an abstract ``eval_shape`` trace (pure Python,
        no XLA) recovers the output arity and aux-state handles the
        dispatch path needs. Returns the number of executables
        installed; raises :class:`~mxnet_tpu.aot.ArtifactError` on a
        corrupt record — fingerprint gating belongs to the caller
        (``InferenceEngine.load_artifacts``), which turns it into a
        warn-once fallback instead of a crash."""
        loaded = 0
        evicted = 0
        for rec in records:
            sig = rec["signature"]
            train = bool(sig[1])
            specs = self._specs_for(sig)
            pure, n_out_box, aux_handles_box = self._make_pure(train)
            jitted = jax.jit(pure)
            jax.eval_shape(jitted, jax.random.PRNGKey(0), *specs)
            n_out, multi = n_out_box[0]
            exe = _aot.deserialize_compiled(rec["blob"], rec["in_tree"],
                                            rec["out_tree"],
                                            rec["devices"])
            entry = (exe, n_out, multi, aux_handles_box[0],
                     float(rec.get("flops") or 0.0), True,
                     float(rec.get("bytes") or 0.0))
            with self._dispatch_lock:
                self._cache[sig] = entry
                self._cache.move_to_end(sig)
                self._stats["aot_loads"] = \
                    self._stats.get("aot_loads", 0) + 1
                if self._capacity > 0:
                    while len(self._cache) > self._capacity:
                        self._cache.popitem(last=False)
                        evicted += 1
                        self._stats["evictions"] += 1
            loaded += 1
        if evicted:
            with _STATS_LOCK:
                _GLOBAL_STATS["evictions"] += evicted
        if loaded:
            _pcache.note_aot_load(loaded)
        return loaded

    def __call__(self, *args, **kwargs):
        import jax as _jax
        from .ndarray.ndarray import NDArray

        args = [a if isinstance(a, NDArray) else NDArray(a) for a in args]
        # Inside an enclosing trace (a hybridized parent block), inline this
        # op's body into the parent program instead of nesting jit — matches
        # the reference where the whole net becomes ONE CachedOp graph, and
        # keeps aux-state writes flowing to the outermost sink.
        if any(isinstance(a._data, _jax.core.Tracer) for a in args):
            return self._fn(*args)
        sig = self._signature(args)
        recording = _tape.is_recording()
        with self._dispatch_lock:
            entry = self._cache.get(sig)
            if entry is not None and entry[5] and recording:
                # an AOT-loaded executable is machine code — it can't be
                # retraced for the autograd tape. Recording dispatch of
                # an AOT entry recompiles fresh (counted as the miss it
                # is) and replaces the entry; serving never records.
                entry = None
            elif entry is not None:
                self._cache.move_to_end(sig)
                self._stats["hits"] += 1
                if entry[4]:
                    self._stats["flops"] = \
                        self._stats.get("flops", 0.0) + entry[4]
        bucket = args[0].shape[0] if args and args[0].shape else None
        compiled_now = entry is None
        if entry is None:
            # compile outside the lock (see __init__). The span covers
            # the forcing trace and the lowering, labeled with the shape
            # bucket (leading dim of the first input) that triggered them;
            # the BACKEND compile is paid by the first dispatch below, and
            # the bridged ``jax.compile`` event (pcache.py) shows it where
            # it really is — together the "why was THIS request 2s?"
            # answer. (t_c0 stays a hand-kept pair: the flight recorder
            # takes the wall with the tracer off.)
            t_c0 = time.perf_counter()
            shards = tuple(_active_sharding(a._data) for a in args)
            if not any(s is not None for s in shards):
                shards = None
            with _trace.span("cachedop.compile", op=self._name,
                             bucket=bucket, signature=str(sig[0])):
                compiled = self._compile(args)
            _attr.flight_note("compile", op=self._name, bucket=bucket,
                              wall_ms=(time.perf_counter() - t_c0) * 1e3)
            evicted = 0
            with self._dispatch_lock:
                entry = self._cache.get(sig)
                if shards is not None:
                    self._shardings[sig] = shards
                if entry is None or (entry[5] and recording):
                    # we won (or were alone, or are replacing an AOT
                    # entry with a traceable one): publish our executable
                    self._cache[sig] = entry = compiled
                else:
                    # a racing thread published first — use theirs, drop
                    # ours; still a miss (an XLA compile really happened)
                    self._cache.move_to_end(sig)
                self._stats["misses"] += 1
                if entry[4]:
                    self._stats["flops"] = \
                        self._stats.get("flops", 0.0) + entry[4]
                if self._capacity > 0:
                    while len(self._cache) > self._capacity:
                        self._cache.popitem(last=False)
                        evicted += 1
                self._stats["evictions"] += evicted
            with _STATS_LOCK:
                _GLOBAL_STATS["misses"] += 1
                _GLOBAL_STATS["evictions"] += evicted
        else:
            with _STATS_LOCK:
                _GLOBAL_STATS["hits"] += 1
        # per-op flops already accounted inside the hit/miss critical
        # sections above — no second lock acquisition on the hot path
        jitted, n_out, multi, aux_handles, flops, aot, nbytes = entry
        if flops:
            _telemetry.add_flops(flops)

        key = _random.next_key()
        vals = [a._data for a in args]
        # dispatch wall pair for the roofline attribution: on a
        # synchronous backend this is execution time; under async
        # dispatch it can understate execution (enqueue-only), making
        # the derived achieved-FLOP/s an overstatement — see the
        # attribution.py module docstring for the reading guidance
        t_d0 = time.perf_counter()
        try:
            out_vals = jitted(key, *vals)
        except Exception as exc:  # noqa: BLE001 — AOT aval drift only
            if not aot:
                raise
            # a loaded executable refused these exact arguments (aval
            # drift the shape/dtype signature can't see, or a backend
            # that rejected the deserialized program at dispatch):
            # recompile fresh ONCE, replace the entry, and count the
            # fallback — a shipped artifact must degrade to a compile,
            # never to a serving error
            _pcache.note_aot_fallback(
                "%s: %s" % (type(exc).__name__, exc),
                where="CachedOp(%s)" % self._name)
            with _trace.span("cachedop.compile", op=self._name,
                             bucket=bucket, signature=str(sig[0])):
                entry = self._compile(args)
            with self._dispatch_lock:
                self._cache[sig] = entry
                self._cache.move_to_end(sig)
                self._stats["misses"] += 1
            with _STATS_LOCK:
                _GLOBAL_STATS["misses"] += 1
            jitted, n_out, multi, aux_handles, flops, aot, nbytes = entry
            compiled_now = True
            t_d0 = time.perf_counter()
            out_vals = jitted(key, *vals)
        # the FIRST dispatch after a miss pays the jit wrapper's retrace
        # + backend compile (the forcing trace in _compile lower()s but
        # never .compile()s) — its wall is compile, not dispatch, and
        # would rank compile cost in the roofline table; it registers
        # the executable (calls/FLOPs/AI) with wall_s=None, and only
        # warm dispatches contribute measured time
        _attr.record_dispatch(self._name,
                              "%s|train=%s" % (sig[0], sig[1]),
                              bucket, flops, nbytes,
                              None if compiled_now
                              else time.perf_counter() - t_d0)
        for h, v in zip(aux_handles, out_vals[n_out:]):
            h._data = v
        out_vals = out_vals[:n_out]

        node = None
        if _tape.is_recording():
            parents = [_tape.Const(key)]
            for a in args:
                n = a._ag_node
                if n is None:
                    parents.append(_tape.Const(a._data))
                else:
                    parents.append(n if isinstance(n, tuple) else (n, 0))
            node = _tape.OpNode(jitted, parents, n_out, {}, self._name)

        results = []
        for i, v in enumerate(out_vals):
            arr = NDArray(v, ctx=args[0]._ctx if args else None)
            if node is not None:
                arr._ag_node = (node, i)
            results.append(arr)
        return results if multi else results[0]
