"""Device-fed training pipeline: overlap host->device staging with compute.

Role parity: the reference's threaded prefetcher (`src/io/iter_prefetcher.h`)
double-buffered *host* batches ahead of the executor; the GPU copy was then
hidden by the engine's dependency scheduler. On TPU the equivalent hole in
the pipeline is the host->device (H2D) transfer itself: `device_put` issued
at step time serializes staging with compute, and `step_many` pre-stages an
entire `(n_steps, batch, ...)` tensor into HBM — bounding span length and
delaying step 0 until the whole span has transferred.

:class:`DeviceFeed` is the TPU-native prefetcher: a depth-K ring of batches
*already dispatched* to sharded device buffers. A single background stager
thread pulls host batches from any source (Gluon ``DataLoader``, an
``io.DataIter``, or a plain iterator of numpy/NDArray batches) and issues
non-blocking ``jax.device_put`` onto ``batch_sharding(mesh, batch_axes)``;
JAX's async dispatch returns immediately, so transfer N+1..N+K are in
flight while the consumer computes on batch N. All JAX dispatch from the
feed happens on that one stager thread — the consumer only *holds* device
handles, it never issues a transfer that could have been issued earlier.

``ShardedTrainer.step_stream`` builds on this: chunked ``lax.scan`` spans
(the ``_step_many_fn`` program) where chunk N+1's batches stage while chunk
N computes, closing the gap between data-fed and in-graph throughput.

Telemetry rides the existing stats-provider hook (profiler aggregate table,
serving ``/metrics``): per-feed rows ``datafeed.<name>.batches``,
``.bytes_staged``, ``.stage_wait_ms``, ``.depth_occupancy``.

Env knobs: ``MXNET_DATAFEED_DEPTH`` (ring depth K), ``MXNET_DATAFEED_CHUNK``
(default ``step_stream`` span length).
"""
from __future__ import annotations

import queue
import threading
import time
import weakref
from collections import deque

import numpy as np
import jax

from ..ndarray.ndarray import NDArray
from ..observability import tracer as _trace
from ..resilience._stats import Registry, export_rows
from .mesh import batch_sharding

__all__ = ["DeviceFeed", "feed_stats"]

_END = object()          # stager ran the source dry


class _StageError:
    """The stager caught ``exc`` in the source; re-raised at the consumer
    (the prefetch thread must never wedge the handshake — satellite
    contract shared with io.PrefetchingIter)."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class _FeedHandle:
    """Weakref proxy a :class:`DeviceFeed` registers under: stats stay
    observable while the feed lives, and a feed dropped without close()
    stays collectable (its ring buffers must not be pinned by telemetry).
    Collection self-discards the handle so uniquely-named feeds (e.g.
    ``dataloader.N``) can't grow the registry without bound."""

    __slots__ = ("name", "_ref", "__weakref__")

    def __init__(self, feed):
        self.name = feed.name
        self_ref = weakref.ref(self)

        def on_collect(_, self_ref=self_ref):
            handle = self_ref()
            if handle is not None:
                _registry.discard(handle)

        self._ref = weakref.ref(feed, on_collect)

    def stats(self):
        feed = self._ref()
        return None if feed is None else feed.stats()


def _stage_put(value, sharding):
    """ALL DeviceFeed H2D staging funnels through here (tests monkeypatch
    it to count transfers and prove the staged-ahead contract). Non-blocking:
    ``jax.device_put`` enqueues the transfer and returns a future-like
    array immediately."""
    if sharding is None:
        return jax.device_put(value)
    return jax.device_put(value, sharding)


def _stager_main(feed_ref, source, gen):
    """Stager thread body. Deliberately holds NO strong reference to the
    feed while idle or blocked: an abandoned feed stays garbage-collectable
    (its staged buffers must not be pinned by its own worker), and a
    collected, closed, or re-armed feed (generation bump on reset/restart)
    retires this thread instead of letting a zombie pump stale batches
    into a fresh epoch's ring."""

    def live_feed():
        feed = feed_ref()
        if feed is None or feed._gen != gen or feed._stop.is_set():
            return None
        return feed

    def ring_put(item):
        while True:
            feed = live_feed()
            if feed is None:
                return False
            ring = feed._ring
            feed = None
            try:
                ring.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue

    try:
        it = iter(source)
        while True:
            if live_feed() is None:
                return
            try:
                item = next(it)
            except StopIteration:
                break
            feed = live_feed()
            if feed is None:
                return
            staged = feed._stage_item(item)
            feed = None
            if not ring_put(staged):
                return
        ring_put(_END)
    except BaseException as exc:  # noqa: BLE001 — relayed to the consumer
        ring_put(_StageError(exc))


class DeviceFeed:
    """Depth-K ring of batches already dispatched to (sharded) device
    buffers, kept full by one background stager thread.

    Parameters
    ----------
    source : iterable
        Any host batch source: a Gluon ``DataLoader``, an ``io.DataIter``
        (its ``DataBatch`` items are unpacked), or a plain iterable of
        batches. A batch is ``(data, label)`` / ``[data, label]`` — with
        ``data`` itself a tuple/list for multi-input models — or a
        ``DataBatch``.
    mesh : jax.sharding.Mesh, optional
        Target mesh; batches land on ``batch_sharding(mesh, batch_axes)``.
        ``None`` stages to the default device unsharded (the
        ``DataLoader(pin_memory=True)`` path).
    batch_axes : tuple of str
        Mesh axes the leading (batch) dim shards over.
    plan : ShardingPlan, optional
        Derive ``mesh`` and ``batch_axes`` from a
        :class:`~mxnet_tpu.parallel.planner.ShardingPlan` — batches are
        staged onto the plan's DATA axes (dp and ep jointly for MoE
        placements) instead of a hardcoded dp sharding. An explicit
        ``mesh`` still wins (the plan then only supplies the axes).
    depth : int, optional
        Ring depth K (default ``MXNET_DATAFEED_DEPTH``): how many batches
        may be in flight/resident ahead of consumption.
    output : {"arrays", "batch"}
        ``"arrays"`` (trainer path) yields ``(xs_tuple, y)`` of jax arrays;
        ``"batch"`` (pin_memory path) yields the source's own structure
        with every array leaf replaced by a device-backed ``NDArray``.
    timeout : float
        Seconds the consumer waits on an empty ring before declaring the
        stager wedged (mirrors ``DataLoader(timeout=)``).
    name : str
        Stats key: rows export as ``datafeed.<name>.*``.
    """

    def __init__(self, source, mesh=None, batch_axes=("dp",), depth=None,
                 output="arrays", timeout=120.0, name="default", plan=None):
        if output not in ("arrays", "batch"):
            raise ValueError("output must be 'arrays' or 'batch', got %r"
                             % (output,))
        if plan is not None:
            if mesh is None:
                mesh = plan.mesh()
            batch_axes = plan.data_axes
        if depth is None:
            from .. import config as _config
            depth = _config.get("MXNET_DATAFEED_DEPTH")
        if int(depth) < 1:
            raise ValueError("depth must be >= 1, got %r" % (depth,))
        self._source = source
        self._sharding = None if mesh is None \
            else batch_sharding(mesh, tuple(batch_axes))
        self.depth = int(depth)
        self._output = output
        self._timeout = float(timeout)
        self.name = name
        self._ring = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._gen = 0  # bumped on restart/reset: retires zombie stagers
        self._thread = None
        self._closed = False  # persistent: only reset() revives a closed feed
        self._exhausted = False
        self._holdover = deque()  # batches returned via _unget
        self._lock = threading.Lock()
        self._stats = {"batches": 0, "bytes_staged": 0, "stage_time_s": 0.0,
                       "stage_waits": 0, "stage_wait_s": 0.0, "flushed": 0}
        # the registry must not keep an abandoned feed (and its staged
        # device buffers) alive — register a weakref handle, not the feed
        self._reg_handle = _FeedHandle(self)
        _registry.add(self._reg_handle)

    # -- staging (runs ONLY on the stager thread) ---------------------------

    def _to_host(self, a):
        return a._data if isinstance(a, NDArray) else np.asarray(a)

    def _put_one(self, a):
        v = self._to_host(a)
        t0 = time.perf_counter()
        out = _stage_put(v, self._sharding)
        dt = time.perf_counter() - t0
        with self._lock:
            self._stats["bytes_staged"] += int(getattr(v, "nbytes", 0))
            self._stats["stage_time_s"] += dt
        return out

    def _split(self, item):
        """Normalize one source item to ``(xs_tuple, y)`` of host arrays."""
        if hasattr(item, "data") and hasattr(item, "label"):  # DataBatch
            xs = tuple(item.data)
            label = item.label or ()
            if len(label) != 1:
                raise ValueError(
                    "DeviceFeed: DataBatch must carry exactly one label "
                    "array, got %d" % len(label))
            return xs, label[0]
        if isinstance(item, (list, tuple)):
            if len(item) < 2:
                raise ValueError("DeviceFeed: batch must be (data, label), "
                                 "got %d element(s)" % len(item))
            head, y = item[0], item[-1]
            if len(item) == 2 and isinstance(head, (list, tuple)):
                return tuple(head), y     # ((x1, x2, ...), y)
            return tuple(item[:-1]), y    # (x1, ..., xn, y)
        raise TypeError("DeviceFeed: cannot split batch of type %s into "
                        "(data, label)" % type(item).__name__)

    def _stage_item(self, item):
        # recorded on the stager thread: datafeed.stage spans interleaving
        # with the consumer's trainer.chunk spans on another lane is the
        # visual proof that H2D staging overlaps compute
        with _trace.span("datafeed.stage", feed=self.name):
            if self._output == "batch":
                return self._stage_structure(item)
            xs, y = self._split(item)
            return (tuple(self._put_one(x) for x in xs), self._put_one(y))

    def _stage_structure(self, item):
        """pin_memory mode: same structure out, device-backed NDArray
        leaves in (lists/tuples/dicts recursed — a custom batchify's dict
        batch must not silently skip staging)."""
        if isinstance(item, tuple) and hasattr(item, "_fields"):
            # namedtuple: rebuild positionally (the 1-arg iterable
            # constructor below would miss its required fields)
            return type(item)(*(self._stage_structure(v) for v in item))
        if isinstance(item, (list, tuple)):
            return type(item)(self._stage_structure(v) for v in item)
        if isinstance(item, dict):
            return {k: self._stage_structure(v) for k, v in item.items()}
        if isinstance(item, (NDArray, np.ndarray)) or hasattr(item, "nbytes"):
            return NDArray(self._put_one(item))
        return item

    def _check_open(self):
        # fail fast on use-after-close (whatever the path — a silently
        # revived stager would run unregistered, or exit without a
        # sentinel and strand the consumer in a full-timeout wait)
        if self._closed:
            raise RuntimeError(
                "DeviceFeed(%s) is closed — build a new feed or call "
                "reset()" % self.name)

    def _ensure_started(self):
        self._check_open()
        if self._thread is None and not self._exhausted:
            self._thread = threading.Thread(
                target=_stager_main,
                args=(weakref.ref(self), self._source, self._gen),
                daemon=True, name="datafeed-stager-%s" % self.name)
            self._thread.start()

    # -- consumer surface ---------------------------------------------------

    def __iter__(self):
        self._check_open()
        if self._exhausted:
            # restart over a re-iterable source (DataLoader, list, DataIter
            # after its own reset); a spent generator just yields nothing
            self._restart()
        self._ensure_started()
        return self

    def __next__(self):
        if self._holdover:
            # a batch handed back by _unget (already counted when first
            # served) — re-serve it before touching the ring
            return self._holdover.popleft()
        self._ensure_started()
        if self._exhausted:
            raise StopIteration
        waited = None
        wait_t0 = None
        try:
            item = self._ring.get_nowait()
        except queue.Empty:
            # one pair on the tracer's clock serves the wait counter
            # (kept with the tracer off) and the span below
            wait_t0 = _trace.now()
            try:
                item = self._ring.get(timeout=self._timeout)
            except queue.Empty:
                raise RuntimeError(
                    "DeviceFeed(%s): stager produced nothing for %.0fs — "
                    "wedged source?" % (self.name, self._timeout))
            waited = _trace.now() - wait_t0
        if item is _END:
            self._finish_epoch()
            raise StopIteration
        if isinstance(item, _StageError):
            self._finish_epoch()
            raise item.exc
        with self._lock:
            self._stats["batches"] += 1
            if waited is not None:
                # the ring was dry and a real batch was waited on: the
                # consumer stalled on staging — the number the pipeline
                # exists to drive to zero after warmup. (A wait that only
                # received the end-of-epoch sentinel is not a stall.)
                self._stats["stage_waits"] += 1
                self._stats["stage_wait_s"] += waited
        if waited is not None and _trace.enabled():
            # the consumer-side stall the pipeline exists to eliminate;
            # on the trace it nests inside the consuming trainer.chunk
            _trace.complete("datafeed.consumer_wait", wait_t0,
                            wait_t0 + waited, parent=_trace.current(),
                            feed=self.name)
        return item

    next = __next__

    def _unget(self, item):
        """Hand a consumed batch back to the front of the feed.
        ``step_stream`` uses this to keep the chunk-boundary fault
        contract exact: a chaos fault fired after peeking the chunk's
        first batch must not lose that batch for the replay."""
        self._holdover.append(item)

    def _finish_epoch(self):
        self._exhausted = True
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _restart(self):
        self._drain()
        self._gen += 1  # a stager that outlived its join must not adopt us
        self._stop = threading.Event()
        self._exhausted = False

    def prefill(self, timeout=30.0):
        """Block until the ring is full or the source ran dry — warmup
        helper so the first consumed batch already has K-1 successors
        staged. Returns the number of resident batches."""
        self._ensure_started()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._ring.full() or (self._thread is not None
                                     and not self._thread.is_alive()):
                break
            if self._thread is None:
                break
            time.sleep(0.002)
        return self._ring.qsize()

    def flush(self):
        """Eviction path: stop the stager and DISCARD every staged-but-
        unconsumed batch (ring + holdover) so an emergency checkpoint is
        not held hostage by in-flight staging. Returns the number of
        batches released (counted into the ``flushed`` stat). Unlike
        :meth:`close` the feed is not dead — but the next iteration
        restarts the SOURCE per its own restart contract (a list or
        re-iterable source starts over from its top), which is right for
        the intended use (the process exits and the restarted run's
        replay re-reads from the beginning), NOT for continuing training
        in the same process mid-epoch — use :meth:`reset` and re-slice
        the source for an in-process drill."""
        self._check_open()
        # the load-bearing stop/join/drain/gen-bump ordering lives ONLY in
        # _shutdown/_restart — flush just counts what they release
        n = len(self._holdover)
        n += self._shutdown()
        self._restart()
        with self._lock:
            self._stats["flushed"] += n
        return n

    def reset(self):
        """``DataIter`` parity: stop staging, reset a resettable source,
        and restart from its top. The one sanctioned way to revive a
        closed feed — it re-registers the stats handle close() dropped."""
        self._shutdown()
        if hasattr(self._source, "reset"):
            self._source.reset()
        if self._closed:
            self._closed = False
            _registry.add(self._reg_handle)
        self._restart()

    def _drain(self):
        """Empty the ring; returns how many REAL batches (not the
        end-of-epoch sentinel or a relayed error) were discarded."""
        n = 0
        while True:
            try:
                item = self._ring.get_nowait()
            except queue.Empty:
                return n
            if item is not _END and not isinstance(item, _StageError):
                n += 1

    def _shutdown(self):
        self._stop.set()
        t = self._thread
        if t is not None:
            if t is not threading.current_thread():  # no self-join
                t.join(timeout=5.0)
            self._thread = None
        n = self._drain()
        self._holdover.clear()
        return n

    def close(self):
        """Stop the stager, release staged buffers, and drop the feed from
        the stats registry (a finished feed must not pin its buffers or
        keep exporting rows). Idempotent; only :meth:`reset` revives a
        closed feed."""
        self._closed = True
        self._shutdown()
        _registry.discard(self._reg_handle)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass

    # -- stats --------------------------------------------------------------

    def stats(self):
        """Host-side counters: ``{batches, bytes_staged, stage_time_s,
        stage_waits, stage_wait_s, flushed, depth, depth_occupancy}``."""
        with self._lock:
            out = dict(self._stats)
        out["depth"] = self.depth
        out["depth_occupancy"] = self._ring.qsize()
        return out


# ---------------------------------------------------------------------------
# registry + profiler rows (surface in /metrics via the provider hook)
# ---------------------------------------------------------------------------

_registry = Registry()


def feed_stats():
    """``{name: stats}`` over registered (live) :class:`DeviceFeed`s —
    collected feeds' handles resolve to None and are dropped."""
    return {name: st
            for name, st in _registry.map(lambda h: h.stats()).items()
            if st is not None}


def _profiler_rows():
    rows = {}
    for name, st in feed_stats().items():
        rows["datafeed.%s.batches" % name] = (st["batches"],
                                              st["stage_time_s"])
        rows["datafeed.%s.bytes_staged" % name] = (st["bytes_staged"], 0.0)
        rows["datafeed.%s.stage_wait_ms" % name] = (st["stage_waits"],
                                                    st["stage_wait_s"])
        rows["datafeed.%s.depth_occupancy" % name] = (st["depth_occupancy"],
                                                      0.0)
    return rows


export_rows(_profiler_rows)
