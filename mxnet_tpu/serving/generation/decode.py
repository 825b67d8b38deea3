"""Compiled generation programs: bucket-laddered prefill + one decode step.

Exactly TWO program families exist, both dispatched through
:class:`~mxnet_tpu.cached_op.CachedOp` (so XLA compiles are counted,
LRU-bounded, and traced as ``cachedop.compile`` spans):

- **prefill** — fill one slot from a prompt in a single forward pass.
  Prompts are padded up to a *bucket ladder* rung (``MXNET_GEN_LADDER``),
  so compiles are bounded by ``len(ladder)`` regardless of prompt-length
  traffic; the pad tail is masked out of attention and never becomes
  readable cache (lengths gate the decode mask). The slot index is a
  *traced* scalar: one rung's program serves every slot.
- **decode** — ONE fused step for the whole slot batch, fixed signature
  ``(num_slots, 1)`` tokens + per-slot lengths/temperatures + the K/V
  arenas + an explicit PRNG key. Requests joining/leaving the running
  batch change only data, so membership churn triggers **zero** new XLA
  compiles (asserted by ``tests/test_generation.py`` via CachedOp stats).

Inside the decode program: embed, per-layer 1-token attention against the
arena with a keep-mask built from lengths, per-row
``dynamic_update_slice`` cache writes, and fused greedy/temperature/top-k
sampling (``ops/generation_ops.py``) under the explicit key.
"""
from __future__ import annotations

import bisect
import queue as _queue
import threading

import numpy as _np

from ... import config as _config
from ...cached_op import CachedOp
from ...gluon.parameter import swapped_in
from ...observability import tracer as _trace
from ..batcher import ServingError
from .kvcache import SlotKVCache
from .prefix_cache import PrefixCache

__all__ = ["DecodeEngine", "PromptTooLong", "DEFAULT_LADDER"]

DEFAULT_LADDER = (16, 32, 64, 128)


def _next_pow2(n, cap=None):
    """Smallest power of two >= n (optionally capped) — the shared width
    quantizer for prefix-slab inserts and arena-edge chunk tails, so the
    two program families :meth:`DecodeEngine.program_bound` charges with
    one log2 term cannot drift apart."""
    w = 1
    while w < n:
        w <<= 1
    return min(w, cap) if cap is not None else w


class PromptTooLong(ServingError):
    """Prompt exceeds the prefill ladder / leaves no room to generate."""


def _ladder_from_config(max_seq):
    raw = _config.get("MXNET_GEN_LADDER")
    rungs = tuple(int(r) for r in str(raw).split(",") if str(r).strip())
    return tuple(r for r in sorted(set(rungs)) if r <= max_seq) or (max_seq,)


class DecodeEngine:
    """Slot-batched autoregressive decoder over a :class:`SlotKVCache`.

    Parameters
    ----------
    model : TransformerLM-like
        Must expose ``prefill(tokens, lengths)`` and
        ``step(tokens, cache, lengths)`` plus the geometry properties
        (``num_layers``/``num_heads``/``head_dim``/``max_len``) — the
        incremental-decode contract of ``models/transformer.py``.
    cache : SlotKVCache, optional
        Built from the model geometry when omitted (``num_slots`` /
        ``max_seq`` then apply, defaulting to ``MXNET_GEN_SLOTS`` /
        ``MXNET_GEN_MAX_SEQ`` capped to the model's ``max_len``).
    ladder : sequence of int, optional
        Prefill bucket rungs (default ``MXNET_GEN_LADDER``); rungs above
        ``max_seq`` are dropped.
    top_k : int, optional
        Static top-k filter baked into the decode program
        (``MXNET_GEN_TOP_K``; 0 = off). Per-request *temperature* is a
        traced per-slot array — mixing greedy and sampled requests in one
        batch costs nothing.
    seed : int
        Base PRNG key for sampling; each step folds in a monotonically
        increasing counter, so a fixed seed replays a run exactly.
    """

    def __init__(self, model, cache=None, num_slots=None, max_seq=None,
                 ladder=None, top_k=None, seed=0, dtype="float32",
                 chunk=None, prefix_cache=None, name="generation"):
        import jax
        self._model = model
        self._params = list(model.collect_params().values())
        self._name = name
        if cache is None:
            num_slots = int(num_slots or _config.get("MXNET_GEN_SLOTS"))
            max_seq = int(max_seq or min(_config.get("MXNET_GEN_MAX_SEQ"),
                                         model.max_len))
            # the cache registers stats under its name, prefixed
            # "generation.kvcache." by the exporter — the engine name
            # alone keeps the rows readable (generation.kvcache.<name>.*)
            cache = SlotKVCache.for_model(model, num_slots, max_seq,
                                          dtype=dtype, name=name)
        self.cache = cache
        if ladder is None:
            ladder = _ladder_from_config(cache.max_seq)
        self._ladder = tuple(r for r in sorted(set(int(r) for r in ladder))
                             if 1 <= r <= cache.max_seq)
        if not self._ladder:
            raise ValueError("empty prefill ladder for max_seq=%d"
                             % cache.max_seq)
        self._top_k = int(_config.get("MXNET_GEN_TOP_K")
                          if top_k is None else top_k)
        self.chunk = int(_config.get("MXNET_GEN_PREFILL_CHUNK")
                         if chunk is None else chunk)
        if self.chunk:
            # chunk-program widths: ladder rungs below the chunk size plus
            # the chunk itself — compiles stay bounded by the ladder
            self._chunk_ladder = tuple(sorted(
                {r for r in self._ladder if r < self.chunk}
                | {min(self.chunk, cache.max_seq)}))
        else:
            # chunking off: the chunk program still serves prefix-hit
            # suffix fills, bucketed over the normal prefill ladder
            self._chunk_ladder = self._ladder
        if prefix_cache is None:
            self._owns_prefix = bool(_config.get("MXNET_GEN_PREFIX_CACHE"))
            self.prefix = PrefixCache(name=name) if self._owns_prefix \
                else None
        else:
            self._owns_prefix = False
            self.prefix = prefix_cache or None
        self._decode_op = CachedOp(self._decode_fn, name=name + ".decode")
        self._prefill_op = CachedOp(self._prefill_fn, name=name + ".prefill")
        self._chunk_op = CachedOp(self._chunk_fn, name=name + ".chunk")
        self._insert_op = CachedOp(self._insert_fn,
                                   name=name + ".prefix_insert")
        self._extract_op = CachedOp(self._extract_fn,
                                    name=name + ".prefix_extract")
        self._base_key = jax.random.PRNGKey(int(seed))
        self._fold = jax.jit(jax.random.fold_in)
        self._step_counter = 0
        self._key_lock = threading.Lock()
        self._publisher = None        # lazy prefix-publish daemon
        self._publish_q = None
        self._publish_lock = threading.Lock()

    # ---- configuration ----------------------------------------------------
    @property
    def ladder(self):
        return self._ladder

    @property
    def num_slots(self):
        return self.cache.num_slots

    @property
    def max_seq(self):
        return self.cache.max_seq

    def rung_for(self, n):
        """Smallest ladder rung >= n; :class:`PromptTooLong` when the
        prompt (plus one generated position) can't fit."""
        if n < 1:
            raise ServingError("empty prompt")
        if n > self._ladder[-1] or n >= self.cache.max_seq:
            raise PromptTooLong(
                "prompt of %d tokens exceeds the prefill ladder (max rung "
                "%d) or leaves no room to generate (max_seq %d)"
                % (n, self._ladder[-1], self.cache.max_seq))
        return self._ladder[bisect.bisect_left(self._ladder, n)]

    def validate_prompt(self, n):
        """Admission-time length check. With chunked prefill on, any
        prompt that leaves room to generate is admissible (chunks bucket
        to the chunk ladder, so a 4k prompt costs no new wide compile);
        without it the monolithic prefill ladder bounds the prompt."""
        if n < 1:
            raise ServingError("empty prompt")
        if self.chunk:
            if n >= self.cache.max_seq:
                raise PromptTooLong(
                    "prompt of %d tokens leaves no room to generate "
                    "(max_seq %d)" % (n, self.cache.max_seq))
            return
        self.rung_for(n)

    def _chunk_rung(self, m, pos):
        """Chunk-program width for an ``m``-token segment written at
        absolute position ``pos``: smallest chunk-ladder rung >= m whose
        write window stays inside the arena (``dynamic_update_slice``
        would otherwise *clamp the start* and overwrite committed
        positions). Arena-edge tails that no rung fits fall back to
        power-of-two widths (a bounded program family, counted in
        :meth:`program_bound`), then to the exact width — m always fits,
        since ``pos + m <= max_seq - 1``."""
        S = self.cache.max_seq
        for r in self._chunk_ladder:
            if r >= m and pos + r <= S:
                return r
        w = _next_pow2(m)
        if pos + w <= S:
            return w
        return m

    def _next_key(self):
        with self._key_lock:
            self._step_counter += 1
            c = self._step_counter
        return _np.asarray(self._fold(self._base_key, c))

    # ---- traced programs --------------------------------------------------
    def param_args(self):
        """The model's parameter handles: the trailing ARGUMENTS of every
        program that runs the model. Closed over instead, the weights
        would be baked into each executable as constants (see
        :func:`~mxnet_tpu.gluon.parameter.swapped_in`) — at 768 wide that
        is 0.5 GB per program, and compiling the ladder runs the host out
        of memory."""
        return [p.data() for p in self._params]

    def bound_params(self, pvals):
        """Context manager: the model reads ``pvals`` (the traced
        :meth:`param_args`) as its parameters."""
        return swapped_in(self._params, pvals)

    def _prefill_fn(self, tokens, length, slot, k_arena, v_arena, *pvals):
        from ... import ndarray as nd
        with self.bound_params(pvals):
            logits, cache = self._model.prefill(tokens, length)
        k_blk = nd.stack(*[k for k, _ in cache], axis=0)  # (L,1,rung,H,D)
        v_blk = nd.stack(*[v for _, v in cache], axis=0)
        k_arena = nd.arena_update(k_arena, k_blk, slot, axis=1)
        v_arena = nd.arena_update(v_arena, v_blk, slot, axis=1)
        return logits, k_arena, v_arena

    def _decode_fn(self, tokens, lengths, temps, key, k_arena, v_arena,
                   *pvals):
        from ... import ndarray as nd
        cache = [(k_arena[layer], v_arena[layer])
                 for layer in range(self.cache.num_layers)]
        with self.bound_params(pvals):
            logits, new_cache = self._model.step(tokens, cache, lengths)
        k_arena = nd.stack(*[k for k, _ in new_cache], axis=0)
        v_arena = nd.stack(*[v for _, v in new_cache], axis=0)
        toks = nd.generation_sample(logits, key, temps, k=self._top_k)
        return toks, k_arena, v_arena

    def _chunk_fn(self, tokens, start, slot, k_arena, v_arena, *pvals):
        """Chunk prefill for ONE slot: pull the slot's K/V rows out of
        the arena (traced slot index — one program per chunk width serves
        every slot), append the chunk via the model's ``prefill_chunk``,
        and write the rows back. Returns the chunk's per-position logits
        (the final chunk's last valid row feeds first-token sampling)."""
        from ... import ndarray as nd
        k_slot = nd.arena_slice(k_arena, slot, axis=1)   # (L, 1, S, H, D)
        v_slot = nd.arena_slice(v_arena, slot, axis=1)
        cache = [(k_slot[layer], v_slot[layer])
                 for layer in range(self.cache.num_layers)]
        with self.bound_params(pvals):
            logits, new_cache = self._model.prefill_chunk(tokens, cache,
                                                          start)
        k_blk = nd.stack(*[k for k, _ in new_cache], axis=0)
        v_blk = nd.stack(*[v for _, v in new_cache], axis=0)
        k_arena = nd.arena_update(k_arena, k_blk, slot, axis=1)
        v_arena = nd.arena_update(v_arena, v_blk, slot, axis=1)
        return logits, k_arena, v_arena

    def _insert_fn(self, k_slab, v_slab, slot, k_arena, v_arena):
        """Copy-on-admit: write a cached prefix slab ``(L, 1, W, H, D)``
        into ``slot`` — the one ``dynamic_update_slice`` the prefix cache
        was waiting on. Keyed by slab width (power-of-two padded), so
        compiles stay logarithmic in ``max_seq``."""
        from ... import ndarray as nd
        k_arena = nd.arena_update(k_arena, k_slab, slot, axis=1)
        v_arena = nd.arena_update(v_arena, v_slab, slot, axis=1)
        return k_arena, v_arena

    def _extract_fn(self, k_arena, v_arena, slot):
        """Pull one slot's full K/V rows for prefix-cache storage (ONE
        fixed signature; the host slices the valid prefix lengths)."""
        from ... import ndarray as nd
        return (nd.arena_slice(k_arena, slot, axis=1),
                nd.arena_slice(v_arena, slot, axis=1))

    # ---- host-side entry points -------------------------------------------
    def prefill(self, slot, prompt, temperature=0.0):
        """Fill ``slot`` from ``prompt`` (1-D int token ids) and sample the
        first generated token. Pads to a ladder rung, runs the compiled
        prefill, commits the arenas, records the slot length, and returns
        the sampled token (python int)."""
        from ... import ndarray as nd
        prompt = _np.asarray(prompt, dtype=_np.int32).reshape(-1)
        n = int(prompt.shape[0])
        rung = self.rung_for(n)
        padded = _np.zeros((1, rung), dtype=_np.int32)
        padded[0, :n] = prompt
        with _trace.span("generation.prefill", rung=rung, prompt_len=n,
                         slot=int(slot)):
            logits, k_arena, v_arena = self._prefill_op(
                nd.array(padded), nd.array(_np.array([n], _np.int32)),
                nd.array(_np.int32(slot)),
                self.cache.k_arena, self.cache.v_arena,
                *self.param_args())
            self.cache.commit(k_arena, v_arena)
            self.cache.set_length(slot, n)
            return self._sample_first(logits[0], temperature)

    def _sample_first(self, logits_row, temperature):
        """Sample the first generated token from one device-resident
        logits row (NDArray ``(V,)``) — the same fused sampler the
        decode program uses, so greedy/temperature semantics match
        exactly, and only the sampled token crosses to the host."""
        from ... import ndarray as nd
        temps = _np.asarray([temperature], dtype=_np.float32)
        tok = nd.generation_sample(
            logits_row.reshape((1, -1)),
            nd.array(self._next_key()), nd.array(temps), k=self._top_k)
        return int(tok.asnumpy()[0])

    def prefill_chunks(self, slot, prompt, start, temperature=0.0,
                       max_chunks=None, sample=True):
        """Advance the chunked prefill of ``prompt`` in ``slot`` from
        absolute position ``start`` by up to ``max_chunks`` chunk-program
        calls (``None`` = run to completion).

        Chunk boundaries are *absolute* multiples of ``self.chunk`` (when
        chunking is on), so the same prompt is always cut identically
        regardless of where a prefix-cache hit started it — the bitwise
        hit-equals-cold guarantee rides on that. With chunking off the
        whole remainder goes in one ladder-bucketed call (the prefix-hit
        suffix path).

        Returns ``(pos, tok)``: the new committed position, and the
        sampled first token once ``pos == len(prompt)`` (``None`` while
        prefill is still in flight, or when ``sample=False`` — the
        draft-sync path needs the KV only)."""
        from ... import ndarray as nd
        prompt = _np.asarray(prompt, dtype=_np.int32).reshape(-1)
        n = int(prompt.shape[0])
        pos = int(start)
        if not 0 <= pos < n:
            raise ServingError("chunk start %d outside prompt [0, %d)"
                               % (pos, n))
        steps = 0
        tok = None
        while pos < n and (max_chunks is None or steps < max_chunks):
            end = min(n, (pos // self.chunk + 1) * self.chunk) \
                if self.chunk else n
            m = end - pos
            rung = self._chunk_rung(m, pos)
            padded = _np.zeros((1, rung), dtype=_np.int32)
            padded[0, :m] = prompt[pos:end]
            with _trace.span("generation.prefill_chunk", rung=rung,
                             start=pos, tokens=m, slot=int(slot)):
                logits, k_arena, v_arena = self._chunk_op(
                    nd.array(padded),
                    nd.array(_np.array([pos], _np.int32)),
                    nd.array(_np.int32(slot)),
                    self.cache.k_arena, self.cache.v_arena,
                    *self.param_args())
                self.cache.commit(k_arena, v_arena)
                self.cache.set_length(slot, end)
            pos = end
            steps += 1
            if pos >= n and sample:
                # device-side row slice: the (rung, V) logits never
                # round-trip to the host, only the sampled token does
                tok = self._sample_first(logits[0][m - 1], temperature)
        return pos, tok

    # ---- prefix cache -----------------------------------------------------
    @staticmethod
    def _slab_rung(n, max_seq):
        """Power-of-two padded insert width: bounds the insert-program
        family to log2(max_seq) signatures."""
        return _next_pow2(n, cap=max_seq)

    def prefix_admit(self, slot, prompt):
        """Probe the prefix cache for the longest usable cached prefix of
        ``prompt`` and, on a hit, copy its K/V slab into ``slot`` and
        commit the slot length. Returns the number of prompt tokens
        skipped (0 on miss / cache disabled)."""
        if self.prefix is None:
            return 0
        hit = self.prefix.lookup(prompt)
        if hit is None:
            return 0
        entry, plen = hit
        from ... import ndarray as nd
        try:
            W = self._slab_rung(plen, self.cache.max_seq)
            shape = list(entry.k_slab.shape)
            shape[2] = W
            k_pad = _np.zeros(shape, dtype=entry.k_slab.dtype)
            v_pad = _np.zeros(shape, dtype=entry.v_slab.dtype)
            k_pad[:, :, :plen] = entry.k_slab
            v_pad[:, :, :plen] = entry.v_slab
            with _trace.span("generation.prefix_hit", tokens=plen,
                             slot=int(slot)):
                k_arena, v_arena = self._insert_op(
                    nd.array(k_pad), nd.array(v_pad),
                    nd.array(_np.int32(slot)),
                    self.cache.k_arena, self.cache.v_arena)
                self.cache.commit(k_arena, v_arena)
                self.cache.set_length(slot, plen)
        finally:
            self.prefix.release(entry)
        return plen

    def prefix_store(self, slot, prompt):
        """Publish ``slot``'s freshly prefilled prompt K/V into the
        prefix cache at every block-aligned prefix length not already
        stored (ONE hash-chain sweep, one extract program call + one
        device->host copy per prompt), amortized across every future
        admit that shares it. Synchronous — the scheduler uses
        :meth:`prefix_store_async` so the copy never blocks the
        iteration loop."""
        self._prefix_store_from(self.cache.k_arena, self.cache.v_arena,
                                slot, prompt)

    def _prefix_store_from(self, k_arena, v_arena, slot, prompt):
        if self.prefix is None:
            return
        prompt = _np.asarray(prompt, dtype=_np.int32).reshape(-1)
        points, chain = self.prefix.missing_store_points(prompt)
        if not points:
            return
        from ... import ndarray as nd
        k_slot, v_slot = self._extract_op(k_arena, v_arena,
                                          nd.array(_np.int32(slot)))
        k_np = k_slot.asnumpy()
        v_np = v_slot.asnumpy()
        for p in points:
            self.prefix.insert(prompt[:p], k_np[:, :, :p], v_np[:, :, :p],
                               chain=chain)

    def prefix_store_async(self, slot, prompt):
        """Queue a prefix publish onto the background publisher thread.
        The CURRENT arenas are captured by reference — they are
        immutable functional values, so the extract reads a consistent
        snapshot even after the scheduler commits newer arenas or reuses
        the slot. Best-effort: a full queue drops the publish (the next
        admit sharing the prompt re-offers it)."""
        if self.prefix is None:
            return
        with self._publish_lock:
            if self._publisher is None:
                self._publish_q = _queue.Queue(maxsize=8)
                self._publisher = threading.Thread(
                    target=self._publish_loop, daemon=True,
                    name=self._name + "-prefix-publish")
                self._publisher.start()
        try:
            self._publish_q.put_nowait(
                (self.cache.k_arena, self.cache.v_arena, int(slot),
                 _np.array(prompt, dtype=_np.int32).reshape(-1)))
        except _queue.Full:
            pass

    def _publish_loop(self):
        while True:
            item = self._publish_q.get()
            try:
                if item is None:
                    return
                k_arena, v_arena, slot, prompt = item
                self._prefix_store_from(k_arena, v_arena, slot, prompt)
            except Exception:  # noqa: BLE001 — publishing is best-effort
                pass
            finally:
                self._publish_q.task_done()

    def prefix_flush(self):
        """Block until every queued prefix publish has landed (tests and
        prefill-lane handoff barriers)."""
        if self._publisher is not None:
            self._publish_q.join()

    def decode_step(self, tokens, temperatures):
        """ONE fused decode iteration for every slot.

        ``tokens (num_slots,)`` int — each held slot's pending token
        (free slots: any valid id, conventionally 0); ``temperatures
        (num_slots,)`` float. Appends each token at its slot's current
        length and returns the sampled next tokens ``(num_slots,)``
        (numpy int32). The caller advances lengths for the slots it
        considers live and ignores the rest."""
        from ... import ndarray as nd
        tokens = _np.asarray(tokens, dtype=_np.int32).reshape(
            self.num_slots, 1)
        temps = _np.asarray(temperatures, dtype=_np.float32).reshape(
            self.num_slots)
        lengths = _np.minimum(self.cache.lengths, self.max_seq - 1)
        with _trace.span("generation.step", slots=int(self.cache.in_use)):
            toks, k_arena, v_arena = self._decode_op(
                nd.array(tokens), nd.array(lengths), nd.array(temps),
                nd.array(self._next_key()),
                self.cache.k_arena, self.cache.v_arena,
                *self.param_args())
            self.cache.commit(k_arena, v_arena)
            return toks.asnumpy().reshape(-1)

    # ---- stats ------------------------------------------------------------
    def lower_decode(self):
        """The fused decode step, once it has run, re-lowered as a
        ``jax.stages.Lowered`` (:meth:`CachedOp.lower`) — ``.compile()``
        it to read what the compiler made of the step."""
        return self._decode_op.lower(self._decode_op.signatures()[0])

    def compile_stats(self):
        """CachedOp cache stats for every program family — the
        membership-churn-compiles-nothing acceptance check reads
        ``decode["misses"]``; chunk/insert/extract are bounded by the
        chunk ladder and log2(max_seq) respectively."""
        return {"decode": self._decode_op.cache_stats(),
                "prefill": self._prefill_op.cache_stats(),
                "chunk": self._chunk_op.cache_stats(),
                "prefix_insert": self._insert_op.cache_stats(),
                "prefix_extract": self._extract_op.cache_stats()}

    def program_bound(self):
        """Upper bound on compiled programs this engine can hold — what
        the fleet compile-budget admission charges a generation lane."""
        log_widths = max(1, self.cache.max_seq.bit_length())
        n = len(self._ladder) + 1                 # prefill rungs + decode
        # chunk rungs + the pow2 arena-edge tail family (exact-width
        # fallbacks are a subset of positions the pow2 family misses:
        # rare, but budgeted by the same log term)
        n += len(self._chunk_ladder) + log_widths
        if self.prefix is not None:
            # insert widths are pow2-padded, plus the one extract program
            n += log_widths + 1
        return n

    def close(self):
        if self._publisher is not None:
            self._publish_q.put(None)
            self._publisher.join(timeout=10.0)
            self._publisher = None
        if self.prefix is not None and self._owns_prefix:
            self.prefix.close()
        self.cache.close()
