"""Production telemetry plane: device memory, FLOPs/MFU, tail sampling.

Three accounting layers the serving/training stack was missing, all
exposed through the Prometheus exposition (:mod:`.export_prom`) and the
existing ``/metrics`` JSON:

- **Device-memory accounting** — per-device HBM bytes-in-use / limit /
  peak gauges from ``jax.Device.memory_stats()``, with a process-tracked
  peak (PJRT's own peak resets with the allocator) and a **headroom**
  gauge. :func:`memory_health` degrades ``/healthz`` BEFORE the
  allocator OOMs: a host at 97% HBM should drain, not take the request
  that kills it. Probe failures are counted
  (``telemetry.memory_probe_errors``) and warned once — reporting zero
  capacity as fact is how the ROADMAP's hand-computed MFU plateau
  happened.
- **FLOPs / MFU accounting** — every CachedOp executable carries an
  analytic FLOPs count from XLA's cost model, cached at compile time
  (``lowered.cost_analysis()``); each dispatch adds it to the process
  :class:`FlopsMeter`. :func:`mfu_percent` divides the windowed FLOP/s
  rate by the devices' peak (``MXNET_TELEMETRY_PEAK_FLOPS`` or the
  built-in per-device-kind table) — the live version of the "17.4% MFU"
  number PERF.md computed by hand.
- **Tail-based trace sampling** — :class:`TailSampler` attaches to the
  tracer and decides, at span completion, which traces are worth
  keeping: 100% of error/deadline/anomaly spans (anything carrying a
  truthy ``error`` attribute, plus spans over ``MXNET_TRACE_SLOW_MS``),
  and a budgeted random fraction of the rest
  (``MXNET_TRACE_SAMPLE`` × ``MXNET_TRACE_SAMPLE_BUDGET``/s). Kept
  trace ids become the exemplars on the Prometheus phase histograms, so
  a bad p99 bucket links straight to a retrievable trace.

:func:`serve_metrics` runs the standalone worker endpoint
(``GET /metrics.prom`` + ``/healthz``) for processes that are not
``ModelServer``s — training workers under ``tools/launch.py
--supervise`` expose themselves with one call, and
``tools/telemetry_agg.py`` merges the fleet.
"""
from __future__ import annotations

import random as _random_mod
import threading
import time
import warnings
from collections import OrderedDict, deque

__all__ = ["FlopsMeter", "flops_meter", "add_flops", "flops_total",
           "flops_rate", "mfu_percent", "peak_flops",
           "device_memory", "memory_headroom", "memory_health",
           "note_memory_probe_error", "memory_probe_errors",
           "TailSampler", "install_tail_sampler", "serve_metrics",
           "telemetry_gauge", "worker_health"]


def _cfg(name):
    from .. import config as _config
    return _config.get(name)


# ---------------------------------------------------------------------------
# FLOPs / MFU accounting
# ---------------------------------------------------------------------------

class FlopsMeter:
    """Monotonic FLOPs ledger with a windowed rate.

    The hot path (:meth:`add`, one per CachedOp dispatch) is a lock and
    an integer add. The rate is sampled lazily at read time
    (:meth:`rate`): each read appends ``(t, total)`` to a bounded sample
    ring and measures against the oldest sample still inside the window
    — scrape-driven, so an idle process costs nothing.
    """

    def __init__(self, window_s=None, clock=time.monotonic):
        self._lock = threading.Lock()
        self._total = 0.0
        self._window_s = float(window_s if window_s is not None
                               else _cfg("MXNET_TELEMETRY_WINDOW_S"))
        self._clock = clock
        self._samples = deque(maxlen=512)  # (t, cumulative_flops)

    def add(self, flops):
        if flops:
            with self._lock:
                self._total += flops

    def total(self):
        with self._lock:
            return self._total

    def rate(self):
        """FLOP/s over (up to) the trailing window. 0.0 until two
        samples exist inside the window — the first scrape (and the
        first scrape after an idle gap longer than the window) primes
        it."""
        now = self._clock()
        with self._lock:
            if self._samples and now - self._samples[-1][0] > self._window_s:
                # idle gap longer than the window: the stale anchors say
                # nothing about the current window, and averaging across
                # the gap would dilute a fresh burst into near-zero MFU
                self._samples.clear()
            self._samples.append((now, self._total))
            while (len(self._samples) > 1
                   and now - self._samples[1][0] >= self._window_s):
                self._samples.popleft()
            t0, f0 = self._samples[0]
            if now - t0 <= 0:
                return 0.0
            return (self._total - f0) / (now - t0)

    def reset(self):
        with self._lock:
            self._total = 0.0
            self._samples.clear()


flops_meter = FlopsMeter()


def add_flops(flops):
    """CachedOp dispatch hook: account one executable execution."""
    flops_meter.add(flops)


def flops_total():
    return flops_meter.total()


def flops_rate():
    return flops_meter.rate()


# The program's one table of published peaks per jax device, by
# ``device_kind`` substring — first match wins, most specific first:
# dense-matmul throughput (FLOP/s, bf16) and HBM bandwidth (bytes/s).
# v2/v3 expose each CORE as a jax device, so their entries are per-core.
# Override with MXNET_TELEMETRY_PEAK_FLOPS / MXNET_PROF_HBM_GBPS when the
# table is wrong for your topology.
_PEAKS_BY_KIND = (
    ("v6", 918e12, 1640e9),        # Trillium
    ("v5 lite", 197e12, 819e9),    # v5e
    ("v5e", 197e12, 819e9),
    ("v5", 459e12, 2765e9),        # v5p
    ("v4", 275e12, 1228e9),
    ("v3", 61.5e12, 450e9),        # per core (123 TFLOP/s, 900 GB/s a chip)
    ("v2", 23e12, 350e9),          # per core (46 TFLOP/s, 700 GB/s a chip)
)


def _accel_devices():
    import jax
    try:
        devs = jax.local_devices()
    except RuntimeError:
        return []
    accel = [d for d in devs if d.platform != "cpu"]
    return accel or devs


def device_peaks(device):
    """Published ``(FLOP/s in bf16, HBM bytes/s)`` of one jax device by
    its ``device_kind``, or ``(None, None)`` for a kind that is not in the
    table — never a default."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    return next(((f, b) for sub, f, b in _PEAKS_BY_KIND if sub in kind),
                (None, None))


def peak_flops():
    """Aggregate peak FLOP/s across this process's devices, or ``None``
    when unknown (CPU-only and no ``MXNET_TELEMETRY_PEAK_FLOPS``
    override) — MFU is then unreported rather than fabricated."""
    override = float(_cfg("MXNET_TELEMETRY_PEAK_FLOPS") or 0.0)
    devices = _accel_devices()
    if not devices:
        return None
    if override > 0:
        return override * len(devices)
    return sum(device_peaks(d)[0] or 0.0 for d in devices) or None


def mfu_percent():
    """Model FLOPs Utilization over the trailing window: analytic
    FLOP/s executed via CachedOp ÷ device peak, as a percentage.
    ``None`` when the peak is unknown."""
    peak = peak_flops()
    if not peak:
        return None
    return flops_rate() / peak * 100.0


# ---------------------------------------------------------------------------
# Device-memory accounting
# ---------------------------------------------------------------------------

_mem_lock = threading.Lock()
_mem_peak = {}            # device index -> max bytes_in_use observed
_probe_errors = 0
_probe_warned = False


def note_memory_probe_error(exc=None, where="telemetry"):
    """Count a failed device-memory probe (and warn once). Shared with
    ``context.gpu_memory_info`` so every probe path feeds the same
    ``telemetry.memory_probe_errors`` counter instead of silently
    reporting zero capacity."""
    global _probe_errors, _probe_warned
    with _mem_lock:
        _probe_errors += 1
        first = not _probe_warned
        _probe_warned = True
    if first:
        warnings.warn(
            "device memory probe failed in %s (%s: %s) — memory gauges "
            "are unavailable, NOT zero; failures are counted in "
            "telemetry.memory_probe_errors (warning once)"
            % (where, type(exc).__name__ if exc is not None else "n/a",
               exc),
            RuntimeWarning, stacklevel=3)


def memory_probe_errors():
    with _mem_lock:
        return _probe_errors


def device_memory():
    """Per-device HBM accounting: ``[{device, platform, kind,
    bytes_in_use, bytes_limit, peak_bytes_in_use, bytes_reserved,
    peak_bytes_reserved, available}]``.
    Devices whose runtime exposes no allocator stats (CPU backend)
    report ``available: False`` — absence of data, not zero usage.
    The peak is the max in-use THIS process has observed across probes
    (monotone per process lifetime), seeded from PJRT's own
    ``peak_bytes_in_use`` when present. ``bytes_reserved`` is what the
    runtime holds apart for compiled programs' temporaries, which
    ``bytes_in_use`` (live arrays) leaves out: a training step can fill
    the chip there. 0 where the backend does not report it."""
    out = []
    for i, d in enumerate(_accel_devices()):
        rec = {"device": i, "platform": getattr(d, "platform", "?"),
               "kind": getattr(d, "device_kind", "") or "",
               "available": False, "bytes_in_use": 0, "bytes_limit": 0,
               "peak_bytes_in_use": 0, "bytes_reserved": 0,
               "peak_bytes_reserved": 0}
        try:
            stats = d.memory_stats()
        except Exception as exc:  # noqa: BLE001 — counted, not swallowed
            note_memory_probe_error(exc, where="device_memory")
            out.append(rec)
            continue
        if not stats:
            out.append(rec)
            continue
        in_use = int(stats.get("bytes_in_use", 0))
        limit = int(stats.get("bytes_limit", 0))
        peak = int(stats.get("peak_bytes_in_use", 0))
        with _mem_lock:
            prev = _mem_peak.get(i, 0)
            peak = max(peak, prev, in_use)
            _mem_peak[i] = peak
        reserved = int(stats.get("bytes_reserved", 0))
        rec.update(available=True, bytes_in_use=in_use,
                   bytes_limit=limit, peak_bytes_in_use=peak,
                   bytes_reserved=reserved,
                   peak_bytes_reserved=max(
                       reserved, int(stats.get("peak_bytes_reserved", 0))))
        out.append(rec)
    return out


def memory_headroom(mems=None):
    """Worst-case free-HBM fraction across devices with a known limit
    (``min (limit - in_use - reserved) / limit``), or ``None`` when no
    device reports a limit."""
    mems = device_memory() if mems is None else mems
    fracs = [(m["bytes_limit"] - m["bytes_in_use"]
              - m.get("bytes_reserved", 0)) / m["bytes_limit"]
             for m in mems if m["available"] and m["bytes_limit"] > 0]
    return min(fracs) if fracs else None


def memory_health():
    """Telemetry contribution to ``/healthz``: degraded when any
    device's free-HBM fraction is below ``MXNET_TELEMETRY_HEADROOM_MIN``
    — the drain signal fires BEFORE the OOM, while the LB can still
    route around this host."""
    threshold = float(_cfg("MXNET_TELEMETRY_HEADROOM_MIN") or 0.0)
    if threshold <= 0:
        return {"status": "ok"}
    headroom = memory_headroom()
    if headroom is not None and headroom < threshold:
        return {"status": "degraded", "reason": "memory_headroom",
                "headroom": headroom, "threshold": threshold}
    return {"status": "ok", "headroom": headroom}


# ---------------------------------------------------------------------------
# Tail-based trace sampling
# ---------------------------------------------------------------------------

class TailSampler:
    """Tail sampling for the span tracer: decide at completion time.

    Keep rules, in order:

    1. **error/deadline/anomaly** — any span carrying a truthy ``error``
      attribute (the server marks 5xx and 504 replies on the
      ``serving.http`` span; instrumented failure paths set it
      directly): its whole trace is kept, always, no budget.
    2. **slow** — spans at or over ``slow_ms`` (``MXNET_TRACE_SLOW_MS``,
      0 disables): latency anomalies are kept like errors.
    3. **random** — root spans draw a coin (``fraction``) under a token
      bucket of ``budget_per_s`` keeps/second, so a traffic spike can't
      turn "1% of traces" into an unbounded kept set.

    A span observed after its trace was already kept returns True
    immediately — child spans of a kept trace all count as kept, which
    is what makes the histogram exemplars land on retrievable traces.
    The kept set is a bounded LRU of trace ids; :meth:`kept_events`
    filters a tracer event snapshot down to the kept traces for export.
    """

    def __init__(self, fraction=None, budget_per_s=None, slow_ms=None,
                 capacity=4096, seed=0, clock=time.monotonic):
        self.fraction = float(fraction if fraction is not None
                              else _cfg("MXNET_TRACE_SAMPLE"))
        self.budget_per_s = float(
            budget_per_s if budget_per_s is not None
            else _cfg("MXNET_TRACE_SAMPLE_BUDGET"))
        self.slow_ms = float(slow_ms if slow_ms is not None
                             else _cfg("MXNET_TRACE_SLOW_MS"))
        self._capacity = max(1, int(capacity))
        self._clock = clock
        self._lock = threading.Lock()
        self._kept = OrderedDict()  # trace_id -> reason
        self._rng = _random_mod.Random(seed)
        self._tokens = self.budget_per_s
        self._last_refill = clock()
        self._c = {"spans": 0, "roots": 0, "kept_error": 0,
                   "kept_slow": 0, "kept_random": 0, "budget_denied": 0}

    def _keep(self, trace_id, reason):
        self._kept[trace_id] = reason
        self._kept.move_to_end(trace_id)
        while len(self._kept) > self._capacity:
            self._kept.popitem(last=False)
        self._c["kept_" + reason] += 1

    def _take_token(self, now):
        if self.budget_per_s <= 0:
            return True  # no budget configured: fraction alone governs
        elapsed = now - self._last_refill
        self._last_refill = now
        self._tokens = min(self.budget_per_s,
                           self._tokens + elapsed * self.budget_per_s)
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        return False

    # ---- the tracer hook --------------------------------------------------
    def observe(self, name, dur_s, trace_id, is_root, attrs):
        """Called by the tracer for every completed span; returns True
        when the span's trace is (now) kept."""
        with self._lock:
            self._c["spans"] += 1
            if is_root:
                self._c["roots"] += 1
            if trace_id in self._kept:
                self._kept.move_to_end(trace_id)
                return True
            if attrs and attrs.get("error"):
                self._keep(trace_id, "error")
                return True
            if self.slow_ms > 0 and dur_s * 1e3 >= self.slow_ms:
                self._keep(trace_id, "slow")
                return True
            if is_root and self.fraction > 0 \
                    and self._rng.random() < self.fraction:
                if self._take_token(self._clock()):
                    self._keep(trace_id, "random")
                    return True
                self._c["budget_denied"] += 1
            return False

    # ---- reading ----------------------------------------------------------
    def is_kept(self, trace_id):
        with self._lock:
            return trace_id in self._kept

    def kept_trace_ids(self):
        """``{trace_id: reason}`` snapshot (ids are the tracer's ints)."""
        with self._lock:
            return dict(self._kept)

    def kept_events(self, events):
        """Filter a ``tracer.events()`` snapshot down to kept traces."""
        with self._lock:
            kept = set(self._kept)
        return [ev for ev in events if ev[8] in kept]

    def stats(self):
        with self._lock:
            out = dict(self._c)
            out["kept"] = len(self._kept)
        out.update(fraction=self.fraction, budget_per_s=self.budget_per_s,
                   slow_ms=self.slow_ms)
        return out

    def reset(self):
        with self._lock:
            self._kept.clear()
            for k in self._c:
                self._c[k] = 0
            self._tokens = self.budget_per_s
            self._last_refill = self._clock()


def install_tail_sampler(**kwargs):
    """Build a :class:`TailSampler` from the env knobs (overridable via
    kwargs) and attach it to the process tracer; returns it."""
    from . import tracer as _trace
    sampler = TailSampler(**kwargs)
    _trace.set_sampler(sampler)
    return sampler


# ---------------------------------------------------------------------------
# process gauge + standalone metrics endpoint
# ---------------------------------------------------------------------------

def telemetry_gauge():
    """JSON gauge for the ``/metrics`` ``"telemetry"`` section: memory,
    FLOPs/MFU, probe errors."""
    mems = device_memory()
    return {"devices": mems,
            "memory_headroom": memory_headroom(mems),
            "memory_probe_errors": memory_probe_errors(),
            "flops_total": flops_total(),
            "flops_rate": flops_rate(),
            "peak_flops": peak_flops(),
            "mfu_percent": mfu_percent()}


def worker_health():
    """The standalone worker ``/healthz`` payload: the same degradation
    sources ``ModelServer.health()`` consults, minus the serving-only
    breaker — memory headroom, training guardrails, elastic membership/
    preemption. A training worker with an unserved eviction notice must
    read degraded on ITS endpoint too, not only on a model server's."""
    m = memory_health()
    if m["status"] != "ok":
        return {"status": "degraded", "memory": m}
    try:
        from ..resilience import guardrails as _guardrails
        g = _guardrails.health()
    except Exception:
        g = {"status": "ok"}
    if g["status"] != "ok":
        return {"status": "degraded", "guardrails": g}
    try:
        from ..resilience import elastic as _elastic
        e = _elastic.health()
    except Exception:
        e = {"status": "ok"}
    if e["status"] != "ok":
        return {"status": "degraded", "elastic": e}
    return {"status": "ok"}


class _MetricsServer:
    """Minimal stdlib endpoint for non-ModelServer processes (training
    workers): ``GET /metrics.prom`` (OpenMetrics text) and ``/healthz``
    (memory/guardrails/elastic-aware via :func:`worker_health`)."""

    def __init__(self, host="127.0.0.1", port=0):
        import json as _json
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        from . import export_prom as _prom

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _send(self, code, body, ctype):
                data = body.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):  # noqa: N802
                if self.path.split("?", 1)[0] == "/metrics.prom":
                    self._send(200, _prom.render_process(),
                               _prom.CONTENT_TYPE)
                elif self.path == "/healthz":
                    h = worker_health()
                    self._send(200 if h["status"] == "ok" else 503,
                               _json.dumps(h), "application/json")
                else:
                    self._send(404, _json.dumps({"error": "unknown path"}),
                               "application/json")

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="telemetry-metrics")
        self._thread.start()

    @property
    def address(self):
        return self._httpd.server_address[:2]

    @property
    def port(self):
        return self.address[1]

    @property
    def url(self):
        return "http://%s:%d" % self.address

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(5.0)


def serve_metrics(port=None, host=None):
    """Start the standalone worker metrics endpoint. ``port=None`` reads
    ``MXTPU_METRICS_PORT`` (set per rank by ``tools/launch.py
    --supervise``); a missing/empty env means "no endpoint" and returns
    None, so library code can call this unconditionally. ``host=None``
    reads ``MXTPU_METRICS_HOST`` (the supervisor sets ``0.0.0.0`` for
    ssh-launched workers — a loopback-only bind would refuse the
    supervisor's cross-host scrape) and defaults to loopback."""
    import os
    if port is None:
        raw = os.environ.get("MXTPU_METRICS_PORT", "")
        if not raw.strip():
            return None
        port = int(raw)
    if host is None:
        host = os.environ.get("MXTPU_METRICS_HOST", "").strip() \
            or "127.0.0.1"
    return _MetricsServer(host=host, port=port)


# ---- profiler integration ---------------------------------------------------

def _telemetry_rows():
    """Aggregate-table rows: the probe-error counter (satellite
    contract: ``telemetry.memory_probe_errors``) and executed-FLOPs
    ledger, visible in ``profiler.dumps()`` without a scrape."""
    return {"telemetry.memory_probe_errors": (memory_probe_errors(), 0.0),
            "telemetry.flops_total": (int(flops_total()), 0.0)}


def _bind_profiler():
    from .. import profiler as _profiler
    _profiler.register_stats_provider(_telemetry_rows)


_bind_profiler()
