"""Persistent XLA compilation cache: recompiles become disk reads.

Layer (1) of the cold-start work (ROADMAP item 4): JAX ships a
content-addressed persistent compilation cache — every compiled module is
keyed by a hash of its HLO + compile options + backend and written under
a directory, so a process restart that compiles a previously seen
program reads machine code off disk instead of running XLA for seconds.
This module decides where it lives and makes its effectiveness
*observable*:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself puts the cache
  there and this module sets no directory (whoever placed the cache from
  outside — a deployment, a benchmark driver — owns its location);
- where it is not, the cache is ``<checkout>/.jax_cache``: one fixed,
  git-ignored path. The path is part of every entry's key, so a
  directory built from a temp name, a pid or a time would never hit.

Tunables (``config.py``):

- ``MXNET_COMPILE_CACHE_MIN_COMPILE_SECS`` — only persist compiles at
  least this slow (0 = everything; jax's default 1.0 would skip exactly
  the small serving-ladder rungs restarts stall on)
- ``MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES``  — size floor per entry
- ``MXNET_COMPILE_CACHE_TTL_DAYS``  — age out entries at init (0 = keep)

:func:`init` is called once at import (from ``mxnet_tpu.context``) and is
idempotent; it also registers a ``jax.monitoring`` listener so disk hits
and misses are counted process-wide and exported as
``cachedop.pcache.*`` profiler rows and ``mxtpu_pcache_*`` Prometheus
families, and a duration listener that bridges jax's own trace / lower /
backend-compile / cache-load durations, for every program whoever
compiled it, into the counters ``trace_s`` / ``lower_s`` / ``compile_s`` /
``load_s``, the per-program table :func:`programs` and, where the tracer
is on, ``jax.trace`` / ``jax.lower`` / ``jax.compile`` / ``pcache.load``
events on its time line. The AOT fallback counters (layer 2, ``cached_op.py`` /
``serving/engine.py``) live here too so every cold-start surface reads
from one ledger.
"""
from __future__ import annotations

import os
import threading
import time
import warnings

from .observability import tracer as _trace

__all__ = ["init", "init_from_env", "default_dir", "enabled", "cache_dir",
           "stats", "programs",
           "reset_stats", "note_aot_load", "note_aot_fallback",
           "sweep_ttl"]

_lock = threading.Lock()
_state = {"initialized": False, "enabled": False, "dir": None,
          "listener_registered": False, "rows_registered": False}
_counters = {
    "disk_hits": 0,        # persistent-cache reads that replaced a compile
    "disk_misses": 0,      # lookups that fell through to a real XLA run
    "requests": 0,         # compile requests that consulted the cache
    "ttl_evictions": 0,    # entries aged out by the TTL sweep at init
    "aot_loads": 0,        # executables installed from AOT artifacts
    "aot_fallbacks": 0,    # AOT loads refused (fingerprint/corrupt) ->
                           # normal compile path taken instead
    # jax's own durations since process start, in seconds; a phase that ran
    # inside another (a jit traced inside another's trace) counts once
    "trace_s": 0.0,        # tracing functions to jaxprs
    "lower_s": 0.0,        # jaxpr -> MLIR module
    "compile_s": 0.0,      # backend compile, cache loads included
    "load_s": 0.0,         # of compile_s: reading the persistent cache
}
_fallback_warned = False

_EVENT_MAP = {
    "/jax/compilation_cache/cache_hits": "disk_hits",
    "/jax/compilation_cache/cache_misses": "disk_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
}


# jax's phases of making a program: event -> (column of _PROGRAM_KEYS,
# span name). jax announces a phase's start (``record_scalar``) and, at
# its end, its duration, both with ``fun_name=``
_PROGRAM_KEYS = ("trace_s", "lower_s", "compile_s", "load_s")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": (0, "jax.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (1, "jax.lower"),
    _COMPILE_EVENT: (2, "jax.compile"),
}
# a persistent-cache hit, reported without a name inside the
# backend-compile interval of the program it loaded
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# a jitted jnp function traced inside another's trace reports a trace of
# its own, tens of thousands a step program: all go to ``programs()``, the
# time line takes the nested ones from this length on
_NESTED_SPAN_FLOOR_S = 1e-3
_programs = {}              # name -> [seconds x 4, events x 4]
_open = threading.local()   # .phases: [[event, name, nested, load_s]]


def _cfg(name):
    from . import config as _config
    return _config.get(name)


def _on_jax_event(event, **kwargs):
    key = _EVENT_MAP.get(event)
    if key is not None:
        with _lock:
            _counters[key] += 1


def _program_name(fun_name):
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]       # lowering and compiling say jit(f)
    return name


def _on_jax_phase_start(event, value, fun_name=None, **kwargs):
    if event in _PHASES:
        phases = getattr(_open, "phases", None)
        if phases is None:
            phases = _open.phases = []
        phases.append([event, _program_name(fun_name), bool(phases), 0.0])


def _on_jax_duration(event, duration, fun_name=None, **kwargs):
    """A phase ended ``duration`` seconds after it began: count it and,
    where the tracer is on, put it on the time line under whatever span
    is open. A phase that began inside another (a jit traced inside
    another's trace) is ``nested``: it adds to :func:`programs` alone, so
    the flat counters, like the spans' self times, are the union."""
    phases = getattr(_open, "phases", None)
    if event == _LOAD_EVENT:
        if phases and phases[-1][0] == _COMPILE_EVENT:
            # the open compile's: it counts these seconds when it ends
            phases[-1][3] += duration
            _to_time_line("pcache.load", duration, phases[-1][1], True)
        return
    if event not in _PHASES:
        return
    i, span = _PHASES[event]
    name, nested, loaded = _program_name(fun_name), False, 0.0
    if phases and phases[-1][0] == event:
        _, name, nested, loaded = phases.pop()
    with _lock:
        if not nested:
            _counters[_PROGRAM_KEYS[i]] += duration
            _counters["load_s"] += loaded
        row = _programs.get(name)
        if row is None:
            row = _programs[name] = [0.0] * 4 + [0] * 4
        row[i] += duration
        row[4 + i] += 1
        if loaded:
            row[3] += loaded
            row[7] += 1
    _to_time_line(span, duration, name, nested)


def _to_time_line(span, duration, name, nested):
    if _trace.tracer._enabled and (
            not nested or duration >= _NESTED_SPAN_FLOOR_S):
        t1 = _trace.now()   # the listener runs as the phase ends
        _trace.complete(span, t1 - duration, t1, parent=_trace.current(),
                        nested=nested, fun=name)


def _register_listener():
    if _state["listener_registered"]:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_scalar_listener(_on_jax_phase_start)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _state["listener_registered"] = True


def _register_rows():
    if _state["rows_registered"]:
        return
    try:
        from . import profiler as _profiler
        _profiler.register_stats_provider(_rows)
        _state["rows_registered"] = True
    except Exception:  # noqa: BLE001 — profiler unavailable at early import
        pass


def sweep_ttl(directory, ttl_days):
    """Unlink persistent-cache entries older than ``ttl_days`` (by the
    newest of the entry's ``-cache``/``-atime`` file mtimes, so a
    recently *used* entry survives even when it was written long ago).
    Returns the eviction count. Best-effort: a cache dir shared with a
    concurrently starting process may race unlinks."""
    if ttl_days <= 0:
        return 0
    cutoff = time.time() - ttl_days * 86400.0
    try:
        names = os.listdir(directory)
    except OSError:
        return 0
    newest = {}
    for n in names:
        for suffix in ("-cache", "-atime"):
            if n.endswith(suffix):
                stem = n[:-len(suffix)]
                try:
                    mtime = os.path.getmtime(os.path.join(directory, n))
                except OSError:
                    continue
                newest[stem] = max(newest.get(stem, 0.0), mtime)
    evicted = 0
    for stem, mtime in newest.items():
        if mtime >= cutoff:
            continue
        removed = False
        for suffix in ("-cache", "-atime"):
            try:
                os.unlink(os.path.join(directory, stem + suffix))
                removed = True
            except OSError:
                pass
        if removed:
            evicted += 1
    if evicted:
        with _lock:
            _counters["ttl_evictions"] += evicted
    return evicted


def default_dir():
    """``<checkout>/.jax_cache`` — the fixed in-tree cache location used
    when nothing outside placed the cache."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def init(cache_dir=None, min_entry_bytes=None, min_compile_secs=None,
         ttl_days=None, force=False):
    """Place jax's persistent compilation cache and hook the hit/miss
    telemetry. The directory is, in order: ``cache_dir`` when given (a
    test's own ``tmp_path``, which also switches the cache on; ``""``
    leaves it without a directory, i.e. off);
    ``JAX_COMPILATION_CACHE_DIR`` when set — jax reads that itself and
    no directory is set here; else :func:`default_dir`. jax's own
    on/off flag (``JAX_ENABLE_COMPILATION_CACHE``, on by default) is
    left as the process was started. Nothing is created on disk here
    (jax makes the directory on first write). Idempotent unless
    ``force``. Returns the active cache directory or ``None``."""
    if _state["initialized"] and not force:
        return _state["dir"] if _state["enabled"] else None
    _state["initialized"] = True
    _register_listener()
    _register_rows()
    import jax
    placed_outside = cache_dir is None and \
        bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    if placed_outside:
        directory = jax.config.jax_compilation_cache_dir
    else:
        directory = default_dir() if cache_dir is None else cache_dir
        directory = directory and os.path.abspath(
            os.path.expanduser(str(directory)))
        jax.config.update("jax_compilation_cache_dir", directory or None)
        if cache_dir:
            jax.config.update("jax_enable_compilation_cache", True)
        # jax binds its cache object to the directory on first use
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    _state["enabled"] = bool(directory) and \
        bool(jax.config.jax_enable_compilation_cache)
    _state["dir"] = directory or None
    if not _state["enabled"]:
        return None
    ttl = float(ttl_days if ttl_days is not None
                else _cfg("MXNET_COMPILE_CACHE_TTL_DAYS"))
    sweep_ttl(directory, ttl)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_compile_secs if min_compile_secs is not None
              else _cfg("MXNET_COMPILE_CACHE_MIN_COMPILE_SECS")))
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes",
        int(min_entry_bytes if min_entry_bytes is not None
            else _cfg("MXNET_COMPILE_CACHE_MIN_ENTRY_BYTES")))
    return directory


def init_from_env():
    """Import-time entry point (``mxnet_tpu.context``): never raises — a
    cache that cannot be set up must not take the whole import down, it
    just warns and leaves compiles uncached."""
    try:
        return init()
    except Exception as exc:  # noqa: BLE001 — import path must survive
        warnings.warn(
            "persistent compile cache init failed (%s: %s) — compiles "
            "will not be cached across restarts"
            % (type(exc).__name__, exc), RuntimeWarning, stacklevel=2)
        _state["enabled"] = False
        return None


def enabled():
    return _state["enabled"]


def cache_dir():
    return _state["dir"] if _state["enabled"] else None


# ---------------------------------------------------------------------------
# AOT ledger (layer 2 counts here so one place owns cold-start telemetry)
# ---------------------------------------------------------------------------

def note_aot_load(n=1):
    """Count ``n`` executables installed from an AOT artifact."""
    with _lock:
        _counters["aot_loads"] += int(n)


def note_aot_fallback(reason, where="aot", warn=True):
    """Count one refused AOT load (fingerprint mismatch, corrupt blob,
    ladder drift) that fell back to a normal compile. Warns ONCE per
    process — a fleet restart across N lanes must not emit N screens of
    the same diagnosis — but every occurrence lands in the
    ``cachedop.pcache.fallback`` row."""
    global _fallback_warned
    with _lock:
        _counters["aot_fallbacks"] += 1
        first = not _fallback_warned
        _fallback_warned = True
    if warn and first:
        warnings.warn(
            "AOT executable artifact not loadable in %s (%s) — falling "
            "back to fresh XLA compiles; re-export artifacts on this "
            "topology/jax version (warning once; every fallback is "
            "counted in cachedop.pcache.fallback)" % (where, reason),
            RuntimeWarning, stacklevel=3)


def stats():
    """Snapshot: ``{"enabled", "dir", "disk_hits", "disk_misses",
    "requests", "ttl_evictions", "aot_loads", "aot_fallbacks", "trace_s",
    "lower_s", "compile_s", "load_s"}``: a flat dict of numbers beside
    the two of the state."""
    with _lock:
        out = dict(_counters)
    out["enabled"] = _state["enabled"]
    out["dir"] = _state["dir"]
    return out


def programs():
    """``{program: {"trace_s", "lower_s", "compile_s", "load_s",
    "count"}}`` by jax's own name of the function (``step``, not
    ``jit(step)``), since process start: the seconds each spent being
    traced, lowered, compiled (loads included) and loaded, nested traces
    included, and ``count``, the most events one of its phases reported
    (a trace that jax answers from its own cache reports an event of 0 s
    too, so a step whose program is asked for twice reads 2)."""
    with _lock:
        rows = {name: list(row) for name, row in _programs.items()}
    return {name: dict(zip(_PROGRAM_KEYS, row[:4]), count=max(row[4:]))
            for name, row in rows.items()}


def reset_stats():
    """Zero the counters and the per-program table (tests); the
    enabled/dir state is untouched."""
    global _fallback_warned
    with _lock:
        for k, v in _counters.items():
            _counters[k] = type(v)()
        _programs.clear()
        _fallback_warned = False


def _rows():
    """Profiler aggregate-table rows: the cold-start ledger visible in
    ``profiler.dumps()`` and ``/metrics`` without a Prometheus scrape."""
    with _lock:
        c = dict(_counters)
    return {
        "cachedop.pcache.hits": (c["disk_hits"], 0.0),
        "cachedop.pcache.misses": (c["disk_misses"], 0.0),
        "cachedop.pcache.requests": (c["requests"], 0.0),
        "cachedop.pcache.ttl_evictions": (c["ttl_evictions"], 0.0),
        "cachedop.pcache.fallback": (c["aot_fallbacks"], 0.0),
        "cachedop.aot.loads": (c["aot_loads"], 0.0),
    }
