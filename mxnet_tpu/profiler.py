"""Profiler (reference ``python/mxnet/profiler.py`` over ``src/profiler/``).

Parity surface: set_config :33, set_state, dumps :151, pause/resume, scoped
Task/Frame/Marker objects :314-396. TPU-native: two collection layers —

- **host spans**: ``mxnet_tpu.observability.tracer`` records nested,
  thread-aware spans (serving request chains, train-step chunks, staging,
  compiles); :func:`dump` writes them as Chrome Trace Event JSON to the
  ``filename`` from :func:`set_config` (default ``<dir>/profile.json``) —
  loadable in Perfetto/chrome://tracing, restoring the reference's
  ``MXDumpProfile`` output on CPU-only runs.
- **device trace**: ``set_state("run")`` also starts a jax.profiler
  XPlane trace into the same directory (viewable in TensorBoard/Perfetto)
  when the backend supports it.

Plus the host-side aggregate timing table kept by this module (role of
`src/profiler/aggregate_stats.cc`), fed both by the scoped objects below
and by registered stats providers (serving metrics, caches, resilience
counters, trace-phase histograms).

Session semantics (reference contract): ``pause()`` suspends collection
WITHOUT discarding anything — host spans buffered so far survive, and
``resume()`` continues the same logical session; only ``set_state("run")``
from a stopped state begins a fresh session (clearing the host buffer).
The jax device trace cannot be suspended mid-session (XPlane finalizes on
stop), so device events keep collecting across a host-side pause.
"""
from __future__ import annotations

import os
import time
import warnings
from collections import defaultdict

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dumps", "pause", "resume",
           "get_aggregate_stats", "register_stats_provider",
           "unregister_stats_provider", "provider_error_counts",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

_state = {"running": False, "paused": False, "jax_running": False,
          "dir": "/tmp/mxnet_tpu_profile", "filename": None,
          "aggregate": defaultdict(lambda: [0, 0.0])}

# External subsystems (e.g. mxnet_tpu.serving metrics, the CachedOp
# executor cache) contribute rows to the aggregate table by registering a
# zero-arg provider returning ``{name: (calls, total_seconds)}`` — the
# host-side analogue of the reference's per-device aggregate merge in
# `src/profiler/aggregate_stats.cc`.
_stats_providers = []
_provider_resets = {}   # provider fn -> zero-arg reset callable
_provider_errors = {}   # provider name -> failure count
_provider_warned = set()


def _provider_name(fn):
    return getattr(fn, "__qualname__", None) \
        or getattr(fn, "__name__", None) or repr(fn)


def register_stats_provider(fn, reset_fn=None):
    """Register a zero-arg callable returning ``{name: (calls, total_s)}``;
    its rows appear in :func:`get_aggregate_stats` and :func:`dumps`.
    ``reset_fn``, when given, is invoked by ``dumps(reset=True)`` so the
    provider's rows reset with the table; providers registered without one
    own their counters and keep them across resets (documented behavior —
    see :func:`dumps`)."""
    if fn not in _stats_providers:
        _stats_providers.append(fn)
    if reset_fn is not None:
        _provider_resets[fn] = reset_fn
    return fn


def unregister_stats_provider(fn):
    if fn in _stats_providers:
        _stats_providers.remove(fn)
    _provider_resets.pop(fn, None)


def provider_error_counts():
    """``{provider_name: failures}`` observed by
    :func:`get_aggregate_stats` — a broken exporter is diagnosable, not
    silent."""
    return dict(_provider_errors)


def get_aggregate_stats():
    """The host-side aggregate table as a dict:
    ``{name: {"calls": int, "total_ms": float}}`` — the programmatic
    counterpart of the :func:`dumps` string, merged with every registered
    stats provider. A provider failing never breaks the table: its error
    is counted in the ``profiler.provider_errors`` row and warned once per
    provider."""
    out = {}
    for name, (calls, total) in _state["aggregate"].items():
        out[name] = {"calls": int(calls), "total_ms": total * 1e3}
    for fn in list(_stats_providers):
        try:
            rows = fn() or {}
        except Exception as exc:  # noqa: BLE001 — diagnosable, not fatal
            pname = _provider_name(fn)
            _provider_errors[pname] = _provider_errors.get(pname, 0) + 1
            if pname not in _provider_warned:
                _provider_warned.add(pname)
                warnings.warn(
                    "profiler stats provider %r failed: %s: %s — its rows "
                    "are skipped; failures are counted in the "
                    "profiler.provider_errors row (warning once per "
                    "provider)" % (pname, type(exc).__name__, exc),
                    RuntimeWarning, stacklevel=2)
            continue
        for name, (calls, total) in rows.items():
            out[name] = {"calls": int(calls), "total_ms": total * 1e3}
    if _provider_errors:
        out["profiler.provider_errors"] = {
            "calls": sum(_provider_errors.values()), "total_ms": 0.0}
    return out

# MXNET_PROFILER_AUTOSTART=1 (reference env_var.md): begin profiling at
# import and flush the trace at interpreter exit
from . import config as _config  # noqa: E402
from .observability import export as _trace_export  # noqa: E402
from .observability import tracer as _trace  # noqa: E402

_autostart_pending = bool(int(_config.get("MXNET_PROFILER_AUTOSTART")))


def set_config(**kwargs):
    """reference profiler.py:33 — accepts the reference's kwargs
    (profile_symbolic, profile_imperative, profile_memory, profile_api,
    filename, aggregate_stats...). ``filename`` is where :func:`dump`
    writes the Chrome Trace JSON (reference behavior); the jax device
    trace lands in its directory."""
    filename = kwargs.get("filename")
    if filename:
        path = os.path.abspath(filename)
        _state["filename"] = path
        _state["dir"] = os.path.dirname(path) or "."
    _state["config"] = kwargs


profiler_set_config = set_config


def set_state(state="stop", profile_process="worker"):
    """'run' starts a session: host-span tracing on (fresh buffer) + a
    jax.profiler trace when the backend supports one; 'stop' ends it.
    'run' while paused is a :func:`resume`."""
    if state == "run":
        if _state["running"]:
            if _state["paused"]:
                resume()
            return
        # fallible work FIRST: a failed makedirs must not leave a phantom
        # "running" session (with the buffer cleared and tracer enabled)
        # that turns the user's corrected retry into a no-op
        os.makedirs(_state["dir"], exist_ok=True)
        _state["running"] = True
        _state["paused"] = False
        _trace.tracer.clear()
        _trace.tracer.reset_phase_stats()
        # the env knob resizes the ring only when actually set — it must
        # not trample a capacity the user configured programmatically
        cap = (_config.get("MXNET_TRACE_BUFFER")
               if os.environ.get("MXNET_TRACE_BUFFER") else None)
        _trace.tracer.enable(capacity=cap if cap and cap > 0 else None)
        try:
            import jax
            jax.profiler.start_trace(_state["dir"])
            _state["jax_running"] = True
        except Exception as exc:  # no XPlane backend / trace already live
            _state["jax_running"] = False
            warnings.warn(
                "profiler: jax.profiler.start_trace failed (%s: %s) — the "
                "session continues with host spans only, no device trace"
                % (type(exc).__name__, exc), RuntimeWarning, stacklevel=2)
    elif state == "stop":
        if not _state["running"]:
            return
        if _state["jax_running"]:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:  # a failed finalize must not wedge the
                pass           # session in a phantom "running" state
            finally:
                _state["jax_running"] = False
        _state["running"] = False
        _state["paused"] = False
        # buffered host spans stay readable for dump(); recording stops —
        # unless the env knob pins always-on tracing, which must survive
        # any pause()/stop() sequence (pause may have disabled the tracer,
        # so actively re-enable rather than merely skipping the disable)
        if int(_config.get("MXNET_TRACE_ENABLE") or 0):
            _trace.tracer.enable()
        else:
            _trace.tracer.disable()


profiler_set_state = set_state


def pause(profile_process="worker"):
    """Suspend host-span collection WITHOUT discarding the session:
    everything recorded so far stays buffered and :func:`resume` continues
    the same logical session (the reference contract — previously this
    finalized and effectively destroyed the in-flight trace). The jax
    device trace keeps collecting across the pause: XPlane sessions cannot
    be suspended without finalizing."""
    if _state["running"] and not _state["paused"]:
        _state["paused"] = True
        _trace.tracer.disable()


def resume(profile_process="worker"):
    """Continue the session :func:`pause` suspended; from a stopped state
    this behaves like ``set_state("run")`` (reference behavior)."""
    if _state["running"]:
        if _state["paused"]:
            _state["paused"] = False
            _trace.tracer.enable()
    else:
        set_state("run")


def dump(finished=True, profile_process="worker"):
    """Write the buffered host spans as Chrome Trace Event JSON to the
    ``filename`` from :func:`set_config` (default ``<dir>/profile.json``)
    — the file chrome://tracing / Perfetto loads (reference
    ``MXDumpProfile``). With ``finished`` (default) the session also stops,
    finalizing the jax device trace into the same directory; pass
    ``finished=False`` for a mid-run snapshot. Returns the JSON path."""
    path = _state["filename"] or os.path.join(_state["dir"], "profile.json")
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    _trace_export.dump_chrome_trace(path, _trace.tracer.events())
    if finished and _state["running"]:
        set_state("stop")
    return path


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Aggregate stats table (role of aggregate_stats.cc Dump) — includes
    rows contributed by registered stats providers (serving, caches).
    ``reset=True`` clears this module's rows AND calls the ``reset_fn`` of
    every provider registered with one; providers without a reset hook own
    their counters and their rows persist across the reset (by contract,
    not by accident — see :func:`register_stats_provider`)."""
    lines = ["Profile Statistics:",
             "%-40s %10s %14s" % ("Name", "Calls", "Total ms")]
    stats = get_aggregate_stats()
    for name in sorted(stats, key=lambda n: -stats[n]["total_ms"]):
        lines.append("%-40s %10d %14.3f"
                     % (name, stats[name]["calls"], stats[name]["total_ms"]))
    if reset:
        _state["aggregate"].clear()
        # error accounting resets with the table — a fixed/unregistered
        # provider must not report stale failures forever (and may warn
        # again if it breaks anew)
        _provider_errors.clear()
        _provider_warned.clear()
        for reset_fn in list(_provider_resets.values()):
            try:
                reset_fn()
            except Exception:  # a broken reset hook must not break dumps
                pass
    return "\n".join(lines)


class Domain:
    """reference profiler.py Domain."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Scoped:
    """User-scoped span: lands in the aggregate table AND, while tracing
    is enabled, in the exported timeline as a span of its own (wired into
    the trace ring, whose bridge also writes it into a running jax
    profile)."""

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._t0 = None
        self._span = None

    def start(self):
        self._t0 = time.time()
        self._span = _trace.span(self.name,
                                 domain=getattr(self.domain, "name", None),
                                 kind=type(self).__name__)
        self._span.__enter__()

    def stop(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self._t0 is not None:
            entry = _state["aggregate"][self.name]
            entry[0] += 1
            entry[1] += time.time() - self._t0
            self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Task(_Scoped):
    """reference profiler.py:314."""


class Frame(_Scoped):
    """reference profiler.py:342."""


class Event(_Scoped):
    """reference profiler.py:370."""


class Counter:
    """reference profiler.py Counter — samples land in the trace buffer as
    counter events (a Perfetto counter track) while tracing is enabled."""

    def __init__(self, domain, name, value=None):
        self.name = name
        self.value = value or 0

    def _sample(self):
        _trace.counter(self.name, value=self.value)

    def set_value(self, value):
        self.value = value
        self._sample()

    def increment(self, delta=1):
        self.value += delta
        self._sample()

    def decrement(self, delta=1):
        self.value -= delta
        self._sample()

    def __iadd__(self, v):
        self.value += v
        self._sample()
        return self

    def __isub__(self, v):
        self.value -= v
        self._sample()
        return self


class Marker:
    """Instant marker (reference profiler.py:396) — recorded in the
    aggregate table and as an instant event on the timeline."""

    def __init__(self, domain, name):
        self.name = name
        self._domain = domain

    def mark(self, scope="process"):
        entry = _state["aggregate"]["marker:" + self.name]
        entry[0] += 1
        _trace.instant(self.name,
                       domain=getattr(self._domain, "name", None),
                       scope=scope)


def _trace_phase_rows():
    """Trace-derived per-phase rows for the aggregate table (and thus the
    serving ``/metrics`` stats surface): ``trace.<span name>`` = (span
    count, total seconds), plus the ring's overflow counter — a trace
    that silently lost its oldest spans must say so next to the spans
    it kept."""
    rows = {"trace." + name: (st["count"], st["total_ms"] / 1e3)
            for name, st in _trace.tracer.phase_stats().items()}
    dropped = _trace.tracer.dropped_spans()
    if dropped:
        rows["trace.dropped_spans"] = (dropped, 0.0)
    return rows


register_stats_provider(_trace_phase_rows,
                        reset_fn=_trace.tracer.reset_phase_stats)


if _autostart_pending:
    import atexit
    set_state("run")
    # flush at exit means the FULL flush: dump() writes the host-span
    # Chrome trace JSON and then stops the session (finalizing the jax
    # trace) — a bare stop would discard every buffered span
    atexit.register(dump)
