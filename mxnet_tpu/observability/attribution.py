"""Performance attribution plane: per-executable roofline accounting,
on-demand production profiling, and the crash/stall flight recorder.

The telemetry plane (PR 9) answers "what is the process doing" with ONE
process-wide FLOPs meter and ONE MFU gauge. This module answers the next
question — "*which compiled program* is the time going to, and is that
program compute-bound or HBM-bound" — in the spirit of the roofline
model (Williams, Waterman & Patterson, CACM 2009) and of always-on
production profiling (Google-Wide Profiling, Ren et al., IEEE Micro
2010):

- **Roofline accounting** — every CachedOp dispatch reports its
  executable's analytic FLOPs *and* bytes accessed (both from XLA's
  cost analysis, cached on the cache entry at compile time) plus a
  measured wall-clock pair around the dispatch. Aggregated per
  (op, signature) in :class:`RooflineRegistry`, each executable gets an
  arithmetic intensity (FLOP/byte), an achieved FLOP/s, a roofline
  ceiling (``min(peak, AI x bandwidth)``), and a
  ``compute_bound | hbm_bound | overhead_bound`` classification — the
  ranked target list ROADMAP item 1's kernel work needs. Surfaces:
  ``cachedop.roofline.*`` profiler rows, ``mxtpu_roofline_*``
  OpenMetrics families (``op=``/``bucket=`` labels), and
  ``tools/roofline_report.py``.
- **On-demand profiling** — :func:`capture_profile` records N seconds
  of live traffic (host-span trace + the flight-recorder ring + the
  attribution snapshot + a jax/XPlane device trace when the backend
  supports one) into a checksummed artifact directory. ``ModelServer``
  exposes it as admin-guarded ``POST /debug/profile?seconds=N`` and the
  gateway proxies it to a named replica — chip-side investigation never
  requires a redeploy.
- **Flight recorder** — :class:`FlightRecorder` keeps the last K
  step/request/dispatch/compile/guard-skip timing records in a bounded
  drop-oldest ring, always on (``MXNET_FLIGHT_RECORDER``), and dumps
  them as JSON on SIGUSR2, on ``AnomalyFault``/``CollectiveTimeout``,
  and on a watchdog stall — every post-mortem gets a timeline even when
  no trace session was running.

Timing caveat (documented, not hidden): the dispatch wall pair measures
*host dispatch* time. On synchronous backends (the CPU oracle) that is
execution time. On TPU, jax dispatch is asynchronous: the pair measures
enqueue cost unless the dispatch blocks on its inputs, so the wall can
UNDERSTATE execution time and the derived achieved-FLOP/s then
OVERSTATES real throughput (it may exceed the roofline ceiling, and
``overhead_bound`` fires less often than it should). The serving path's
per-batch host sync (``asnumpy`` on the reply) keeps steady-state
serving numbers execution-dominated; for pure async dispatch chains
treat achieved as an upper bound and rely on AI + the analytic ceiling.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import deque

from . import telemetry as _telemetry
from .telemetry import _cfg

__all__ = ["RooflineRegistry", "roofline", "record_dispatch",
           "attribution_enabled", "peak_bytes_per_s", "ridge_point",
           "classify", "snapshot", "reset", "roofline_gauge",
           "FlightRecorder", "flight", "flight_enabled", "flight_note",
           "flight_dump", "install_flight_signal_handler",
           "capture_profile", "CaptureBusy", "configure"]


# ---------------------------------------------------------------------------
# roofline parameters
# ---------------------------------------------------------------------------

# Ridge point used when neither peak FLOP/s nor HBM bandwidth is known
# (the CPU oracle): v5e-like, 197 TFLOP/s / 819 GB/s ~= 240 FLOP/byte.
# Classifications on the oracle then approximate what the chip would
# say about the same programs, which is the point of an oracle.
DEFAULT_RIDGE_FLOP_PER_BYTE = 240.0

COMPUTE_BOUND = "compute_bound"
HBM_BOUND = "hbm_bound"
OVERHEAD_BOUND = "overhead_bound"
UNKNOWN = "unknown"


def peak_bytes_per_s():
    """Aggregate peak HBM bytes/s across this process's accelerator
    devices (``MXNET_PROF_HBM_GBPS`` override, else the device-kind
    table of ``telemetry``), or ``None`` when unknown — the ridge then falls back to
    ``MXNET_PROF_RIDGE`` / the built-in default instead of fabricating
    a bandwidth."""
    override = float(_cfg("MXNET_PROF_HBM_GBPS") or 0.0) * 1e9
    devices = _telemetry._accel_devices()
    if not devices:
        return None
    if override > 0:
        return override * len(devices)
    return sum(_telemetry.device_peaks(d)[1] or 0.0
               for d in devices) or None


def _ridge_from(peak, bw):
    """Ridge from already-probed peak/bandwidth (readers that just
    computed both must not pay a second device probe for the ridge)."""
    if peak and bw:
        return peak / bw
    override = float(_cfg("MXNET_PROF_RIDGE") or 0.0)
    return override if override > 0 else DEFAULT_RIDGE_FLOP_PER_BYTE


def ridge_point():
    """The arithmetic-intensity ridge (FLOP/byte) separating HBM-bound
    from compute-bound: ``peak FLOP/s / peak bytes/s`` when both are
    known, else ``MXNET_PROF_RIDGE``, else the built-in default."""
    return _ridge_from(_telemetry.peak_flops(), peak_bytes_per_s())


def classify(flops_per_call, bytes_per_call, wall_s_per_call,
             peak=None, bw=None, ridge=None, overhead_fraction=None):
    """Roofline classification of one executable.

    Returns ``(bound, ai, achieved_flops_s, ceiling_flops_s)``:

    - ``ai`` — arithmetic intensity, FLOP per byte accessed;
    - ``achieved`` — analytic FLOPs / measured wall per call (can
      overstate under async dispatch, see the module caveat);
    - ``ceiling`` — ``min(peak, ai x bandwidth)`` when peak+bandwidth
      are known, else None;
    - ``bound`` — ``overhead_bound`` when achieved is under
      ``MXNET_PROF_OVERHEAD_FRACTION`` of the ceiling (the hardware is
      not the limiter); otherwise ``compute_bound``/``hbm_bound`` by
      AI against the ridge; ``unknown`` only when the cost model gave
      no FLOPs/bytes at all (absence of data, never a guess).
    """
    if flops_per_call <= 0 or bytes_per_call <= 0:
        return UNKNOWN, 0.0, 0.0, None
    ai = flops_per_call / bytes_per_call
    achieved = (flops_per_call / wall_s_per_call
                if wall_s_per_call > 0 else 0.0)
    if peak is None or bw is None:
        peak = _telemetry.peak_flops() if peak is None else peak
        bw = peak_bytes_per_s() if bw is None else bw
    ridge = ridge_point() if ridge is None else ridge
    ceiling = min(peak, ai * bw) if (peak and bw) else None
    if overhead_fraction is None:
        overhead_fraction = float(
            _cfg("MXNET_PROF_OVERHEAD_FRACTION") or 0.0)
    if ceiling and achieved < overhead_fraction * ceiling:
        return OVERHEAD_BOUND, ai, achieved, ceiling
    bound = COMPUTE_BOUND if ai >= ridge else HBM_BOUND
    return bound, ai, achieved, ceiling


# ---------------------------------------------------------------------------
# the roofline registry
# ---------------------------------------------------------------------------

class RooflineRegistry:
    """Per-(op, signature) dispatch accounting.

    The hot path (:meth:`record`, one per CachedOp dispatch) is one lock
    acquisition and four float adds — same cost class as the existing
    ``FlopsMeter.add``. Derivations (AI, achieved, ceiling, bound) run
    at read time in :meth:`snapshot`, never per dispatch.
    """

    def __init__(self):
        self._lock = threading.Lock()
        # (op, sig) -> [calls, warm_wall_s, flops_per_call,
        #               bytes_per_call, bucket, timed_calls]
        self._rows = {}

    def record(self, op, signature, bucket, flops, bytes_accessed,
               wall_s):
        """``wall_s=None`` registers a dispatch without timing it — the
        cold (just-compiled) dispatch, whose wall includes the jit
        retrace + backend compile and would poison per-call walls. The
        executable still appears in every surface (calls, FLOPs, AI);
        only warm dispatches contribute wall time."""
        key = (op, signature)
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._rows[key] = [0, 0.0, flops, bytes_accessed,
                                         bucket, 0]
            row[0] += 1
            # flops/bytes are per-executable constants; keep the
            # latest (an AOT->recompile fallback can refresh them)
            row[2] = flops
            row[3] = bytes_accessed
            if wall_s is not None:
                row[1] += wall_s
                row[5] += 1

    def reset(self):
        with self._lock:
            self._rows.clear()

    def snapshot(self):
        """Per-executable roofline records, sorted by total wall time
        (descending — the ranked target list). Each record::

            {op, signature, bucket, calls, total_s, flops_per_call,
             bytes_per_call, ai, achieved_flops_s, ceiling_flops_s,
             bound, pct_of_total}

        ``pct_of_total`` is the share of all attributed dispatch time —
        the "% of step budget" column in ``tools/roofline_report.py``.
        """
        with self._lock:
            rows = {k: list(v) for k, v in self._rows.items()}
        peak = _telemetry.peak_flops()
        bw = peak_bytes_per_s()
        ridge = _ridge_from(peak, bw)
        frac = float(_cfg("MXNET_PROF_OVERHEAD_FRACTION") or 0.0)
        total_s = sum(v[1] for v in rows.values()) or 0.0
        out = []
        for (op, sig), (calls, wall_s, flops, nbytes, bucket,
                        timed) in rows.items():
            per_call = wall_s / timed if timed else 0.0
            # an executable with no warm dispatch yet has no honest
            # achieved number: classify on AI alone (overhead_bound
            # needs a measured wall to accuse)
            bound, ai, achieved, ceiling = classify(
                flops, nbytes, per_call, peak=peak, bw=bw, ridge=ridge,
                overhead_fraction=frac if timed else 0.0)
            out.append({
                "op": op, "signature": sig, "bucket": bucket,
                "calls": calls, "timed_calls": timed,
                "total_s": wall_s,
                "flops_per_call": flops, "bytes_per_call": nbytes,
                "ai": ai, "achieved_flops_s": achieved,
                "ceiling_flops_s": ceiling, "bound": bound,
                "pct_of_total": (wall_s / total_s * 100.0
                                 if total_s > 0 else 0.0),
            })
        out.sort(key=lambda r: (-r["total_s"], r["op"],
                                str(r["signature"])))
        return out

    def by_op_bucket(self):
        """Snapshot aggregated per (op, bucket) — the bounded-cardinality
        view the Prometheus exposition emits (a signature label would
        explode a scrape under shape churn; per-signature detail stays
        on :meth:`snapshot` / the report tool). FLOPs/bytes per call are
        call-weighted means; the classification is recomputed on the
        aggregate."""
        with self._lock:
            rows = {k: list(v) for k, v in self._rows.items()}
        peak = _telemetry.peak_flops()
        bw = peak_bytes_per_s()
        ridge = _ridge_from(peak, bw)
        frac = float(_cfg("MXNET_PROF_OVERHEAD_FRACTION") or 0.0)
        agg = {}
        for (op, _sig), (calls, wall_s, flops, nbytes, bucket,
                         timed) in rows.items():
            key = (op, bucket)
            ent = agg.setdefault(key, [0, 0.0, 0.0, 0.0, 0])
            ent[0] += calls
            ent[1] += wall_s
            ent[2] += flops * calls
            ent[3] += nbytes * calls
            ent[4] += timed
        out = {}
        for (op, bucket), (calls, wall_s, flops_sum, bytes_sum,
                           timed) in agg.items():
            flops_pc = flops_sum / calls if calls else 0.0
            bytes_pc = bytes_sum / calls if calls else 0.0
            per_call = wall_s / timed if timed else 0.0
            bound, ai, achieved, ceiling = classify(
                flops_pc, bytes_pc, per_call, peak=peak, bw=bw,
                ridge=ridge,
                overhead_fraction=frac if timed else 0.0)
            out[(op, bucket)] = {
                "calls": calls, "timed_calls": timed,
                "total_s": wall_s,
                "flops_per_call": flops_pc, "bytes_per_call": bytes_pc,
                "ai": ai, "achieved_flops_s": achieved,
                "ceiling_flops_s": ceiling, "bound": bound,
            }
        return out


roofline = RooflineRegistry()

# cached enabled flags: the dispatch hot path must not re-parse env vars
# per call. configure() refreshes (tests monkeypatch env then call it).
_enabled = True
_flight_enabled = True


def configure():
    """Re-read the ``MXNET_PROF_ATTRIBUTION`` / ``MXNET_FLIGHT_RECORDER``
    knobs (import-time default; call after changing the env). Also
    re-bounds the flight ring to ``MXNET_FLIGHT_RECORDS``."""
    global _enabled, _flight_enabled
    _enabled = bool(int(_cfg("MXNET_PROF_ATTRIBUTION") or 0))
    _flight_enabled = bool(int(_cfg("MXNET_FLIGHT_RECORDER") or 0))
    cap = int(_cfg("MXNET_FLIGHT_RECORDS") or 0)
    if cap > 0:
        flight.set_capacity(cap)
    return _enabled


def attribution_enabled():
    return _enabled


def record_dispatch(op, signature, bucket, flops, bytes_accessed,
                    wall_s):
    """CachedOp dispatch hook (no-op while attribution is disabled).
    ``wall_s=None`` marks a cold (compile-paying) dispatch: registered
    but untimed in the registry, flagged ``cold`` in the flight ring."""
    if _enabled:
        roofline.record(op, signature, bucket, flops, bytes_accessed,
                        wall_s)
    if _flight_enabled:
        if wall_s is None:
            flight.note("dispatch", op=op, bucket=bucket, cold=True)
        else:
            flight.note("dispatch", op=op, bucket=bucket,
                        wall_ms=wall_s * 1e3)


def snapshot():
    return roofline.snapshot()


def reset():
    roofline.reset()


def roofline_gauge():
    """JSON gauge (the ``/metrics`` ``"roofline"`` section): the ranked
    per-executable table plus the parameters it was derived under."""
    return {"rows": snapshot(),
            "peak_flops": _telemetry.peak_flops(),
            "peak_bytes_s": peak_bytes_per_s(),
            "ridge_flop_per_byte": ridge_point()}


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Bounded always-on ring of the last K timing records.

    A record is ``{"seq", "t_mono", "t_wall", "kind", ...fields}`` —
    ``t_mono`` on the monotonic clock (matches trace timestamps),
    ``t_wall`` epoch seconds (matches log lines). :meth:`note` is a lock
    + deque append; the ring drops the oldest record when full, so a
    week of uptime costs the same memory as a minute.

    Dumps are JSON documents (``{"reason", "dumped_at", "pid",
    "records": [...]}``) written atomically (tmp+rename) into
    ``MXNET_FLIGHT_DIR`` — triggered by SIGUSR2, by the instrumented
    fault paths (AnomalyFault, CollectiveTimeout, watchdog stall), or
    explicitly. Both clocks are injectable for fake-clock tests.
    """

    def __init__(self, capacity=None, clock=time.monotonic,
                 wall_clock=time.time):
        if capacity is None:
            capacity = int(_cfg("MXNET_FLIGHT_RECORDS") or 256)
        self._lock = threading.Lock()
        self._buf = deque(maxlen=max(1, int(capacity)))
        self._clock = clock
        self._wall = wall_clock
        self._seq = 0
        self._dumps = 0

    def set_capacity(self, capacity):
        capacity = max(1, int(capacity))
        with self._lock:
            if capacity != self._buf.maxlen:
                self._buf = deque(self._buf, maxlen=capacity)

    @property
    def capacity(self):
        return self._buf.maxlen

    def note(self, kind, **fields):
        rec = {"kind": kind, "t_mono": self._clock(),
               "t_wall": self._wall()}
        rec.update(fields)
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self._buf.append(rec)

    def records(self):
        """Snapshot, oldest first."""
        with self._lock:
            return list(self._buf)

    def __len__(self):
        return len(self._buf)

    def dump_count(self):
        with self._lock:
            return self._dumps

    def stats(self):
        with self._lock:
            return {"records": len(self._buf),
                    "capacity": self._buf.maxlen,
                    "total_recorded": self._seq, "dumps": self._dumps}

    def dump(self, reason, path=None, directory=None):
        """Write the ring as one JSON document; returns the path.

        ``path=None`` derives ``<directory or MXNET_FLIGHT_DIR>/
        flight_<reason>_<pid>_<seq>.json``. The write is atomic
        (tmp+rename) so a dump racing a crash never publishes a
        truncated file; a dump that cannot be written (read-only fs in
        a dying process) returns None rather than masking the fault
        that triggered it."""
        with self._lock:
            records = list(self._buf)
            self._dumps += 1
            n_dump = self._dumps
        doc = {"reason": reason, "dumped_at": self._wall(),
               "dumped_at_mono": self._clock(), "pid": os.getpid(),
               "capacity": self._buf.maxlen, "records": records}
        if path is None:
            directory = directory or _cfg("MXNET_FLIGHT_DIR") \
                or "/tmp/mxnet_tpu_flight"
            safe = "".join(c if c.isalnum() or c in "-_" else "_"
                           for c in str(reason))
            path = os.path.join(directory, "flight_%s_%d_%d.json"
                                % (safe, os.getpid(), n_dump))
        try:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, default=str)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            return None
        return path


flight = FlightRecorder()


def flight_enabled():
    return _flight_enabled


def flight_note(kind, **fields):
    """Record one flight record (no-op while the recorder is disabled) —
    the call every instrumented site uses, so disabling is one flag."""
    if _flight_enabled:
        flight.note(kind, **fields)


def flight_dump(reason, path=None):
    """Dump the ring if the recorder is enabled; returns the path (or
    None: disabled, or the write failed)."""
    if not _flight_enabled:
        return None
    return flight.dump(reason, path=path)


_signal_installed = False


def install_flight_signal_handler(signum=None):
    """Install the SIGUSR2 dump handler (main thread only — signal
    dispositions are process-global). Safe to call from any thread or
    repeatedly: a non-main caller returns False instead of raising.
    ``kill -USR2 <pid>`` then writes a flight dump with zero service
    interruption.

    The handler only SPAWNS the dump onto a daemon thread: Python runs
    signal handlers on the main thread between bytecodes, so a signal
    landing while the main thread is inside ``flight.note()``'s
    critical section would deadlock an inline ``dump()`` on the same
    non-reentrant lock."""
    global _signal_installed
    import signal as _signal
    if signum is None:
        signum = getattr(_signal, "SIGUSR2", None)
        if signum is None:   # platform without SIGUSR2
            return False

    def _on_signal(_signum, _frame):
        threading.Thread(target=flight_dump, args=("sigusr2",),
                         name="flight-dump", daemon=True).start()

    try:
        _signal.signal(signum, _on_signal)
    except ValueError:       # not the main thread
        return False
    _signal_installed = True
    return True


# ---------------------------------------------------------------------------
# on-demand profile capture
# ---------------------------------------------------------------------------

class CaptureBusy(RuntimeError):
    """A profile capture is already running (one at a time — two
    concurrent XPlane sessions would clobber each other)."""


_capture_lock = threading.Lock()


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 16), b""):
            h.update(chunk)
    return h.hexdigest()


def capture_profile(seconds, out_dir=None, sleep=time.sleep):
    """Capture ``seconds`` of live traffic into a checksummed artifact
    directory; returns the manifest dict (also written as
    ``manifest.json``).

    What lands in the directory:

    - ``host_trace.json`` — Chrome-trace of every host span recorded
      during the window (tracing is force-enabled for the window and
      restored after; an already-running session keeps its state);
    - ``flight.json`` — the flight-recorder ring at capture end;
    - ``attribution.json`` — the roofline snapshot
      (:func:`roofline_gauge`), i.e. ``tools/roofline_report.py`` input;
    - a jax/XPlane device trace (``plugins/profile/...``) when the
      backend supports one — best-effort, its absence is recorded in
      the manifest, never an error;
    - ``manifest.json`` — capture parameters + per-file SHA-256, so a
      partially-copied artifact dir is detectable before anyone stares
      at a truncated trace.

    ``seconds`` is clamped to ``MXNET_PROF_CAPTURE_MAX_S``. Raises
    :class:`CaptureBusy` when a capture is already in flight. The
    caller's thread blocks for the window (the server runs this on the
    request's own handler thread; every other thread keeps serving).
    """
    from . import export as _export
    from . import tracer as _tracer
    max_s = float(_cfg("MXNET_PROF_CAPTURE_MAX_S") or 60.0)
    seconds = max(0.0, min(float(seconds), max_s))
    if not _capture_lock.acquire(blocking=False):
        raise CaptureBusy("a profile capture is already running")
    try:
        if out_dir is None:
            base = _cfg("MXNET_PROF_DIR") or "/tmp/mxnet_tpu_profiles"
            out_dir = os.path.join(
                base, "capture_%d_%d" % (os.getpid(),
                                         int(time.time() * 1e3)))
        os.makedirs(out_dir, exist_ok=True)
        was_enabled = _tracer.tracer.enabled()
        # pre-window events are excluded by TIMESTAMP, not ring index: on
        # a busy server the bounded ring evicts oldest records during the
        # window, so len()-based slicing would return nothing exactly
        # when the capture matters most. A span belongs to the window
        # when it was still running at capture start (end >= t_mark).
        t_mark = _tracer.now()
        _tracer.tracer.enable()
        xplane = False
        xplane_error = None
        try:
            import jax
            jax.profiler.start_trace(out_dir)
            xplane = True
        except Exception as exc:  # no XPlane backend / session collision
            xplane_error = "%s: %s" % (type(exc).__name__, exc)
        t0 = time.monotonic()
        try:
            if seconds > 0:
                sleep(seconds)
        finally:
            if xplane:
                import jax
                try:
                    jax.profiler.stop_trace()
                except Exception as exc:
                    xplane = False
                    xplane_error = "stop: %s: %s" \
                        % (type(exc).__name__, exc)
            if not was_enabled:
                _tracer.tracer.disable()
        window_s = time.monotonic() - t0
        events = [ev for ev in _tracer.tracer.events()
                  if ev[2] + (ev[3] or 0.0) >= t_mark]
        _export.dump_chrome_trace(
            os.path.join(out_dir, "host_trace.json"), events)
        flight.dump("profile_capture",
                    path=os.path.join(out_dir, "flight.json"))
        with open(os.path.join(out_dir, "attribution.json"), "w") as f:
            json.dump(roofline_gauge(), f, indent=2, default=str)
        files = []
        for dirpath, _dirs, names in os.walk(out_dir):
            for name in sorted(names):
                if name == "manifest.json":
                    continue
                fp = os.path.join(dirpath, name)
                files.append({
                    "name": os.path.relpath(fp, out_dir),
                    "bytes": os.path.getsize(fp),
                    "sha256": _sha256(fp)})
        manifest = {"dir": out_dir, "seconds_requested": seconds,
                    "seconds_captured": window_s,
                    "host_span_events": len(events),
                    "xplane": xplane, "xplane_error": xplane_error,
                    "pid": os.getpid(), "files": files}
        with open(os.path.join(out_dir, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
        flight_note("profile_capture", dir=out_dir, seconds=window_s)
        return manifest
    finally:
        _capture_lock.release()


# ---------------------------------------------------------------------------
# profiler integration + init
# ---------------------------------------------------------------------------

def _roofline_rows():
    """Aggregate-table rows: ``cachedop.roofline.<op>|b<bucket>`` =
    (dispatch count, total dispatch seconds) — the attribution table in
    ``profiler.dumps()`` without a scrape — plus the flight ring's
    occupancy."""
    rows = {}
    for (op, bucket), ent in roofline.by_op_bucket().items():
        rows["cachedop.roofline.%s|b%s" % (op, bucket)] = \
            (ent["calls"], ent["total_s"])
    st = flight.stats()
    if st["total_recorded"]:
        rows["flight.records"] = (st["total_recorded"], 0.0)
    return rows


def _bind_profiler():
    from .. import profiler as _profiler
    _profiler.register_stats_provider(_roofline_rows,
                                      reset_fn=roofline.reset)


configure()
_bind_profiler()
# NOTE: the SIGUSR2 handler is NOT installed at import — a library that
# clobbers a process-global signal disposition as an import side effect
# breaks hosts that own SIGUSR2 themselves (gunicorn, supervisors).
# ModelServer installs it for serving processes; training scripts and
# embedders opt in with install_flight_signal_handler().
