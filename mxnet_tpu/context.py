"""Device contexts.

Parity surface: reference ``python/mxnet/context.py`` (Context class,
``mx.cpu()`` / ``mx.gpu()``). TPU-native additions: ``mx.tpu()`` is the
accelerator context; ``mx.gpu()`` aliases to the default accelerator so
reference scripts run unmodified. A Context maps to a concrete
``jax.Device``; ``with ctx:`` scopes default placement the way the
reference's thread-local ``Context._default_ctx`` does
(reference `python/mxnet/context.py:88`).
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "gpu_memory_info"]

_thread_local = threading.local()


class Context:
    """A device context (cpu / tpu). ``device_id`` indexes jax.devices()."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self):
        return self.devstr2type[self.device_type]

    def _accelerators(self):
        try:
            accel = [d for d in jax.local_devices() if d.platform != "cpu"]
        except RuntimeError:
            accel = []
        return accel

    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device. Device ids index this
        process's ADDRESSABLE devices (reference semantics: gpu(0) on each
        worker is that worker's own device) — under jax.distributed the
        global list contains peers' devices, which cannot back an eager
        array here."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            pool = [d for d in jax.local_devices(backend="cpu")] \
                if _has_cpu() else jax.local_devices()
        else:
            # a process started on the CPU backend (JAX_PLATFORMS=cpu: the
            # test suite, reference scripts that say mx.gpu()) has no
            # accelerator; its accelerator contexts index the CPU devices
            pool = self._accelerators() or jax.local_devices()
        if not 0 <= self.device_id < len(pool):
            # never clamp: tpu(3) on a one-chip host silently landing on
            # chip 0 would put a whole multi-chip job on the first device
            raise ValueError("%s: this process has %d such device(s)"
                             % (self, len(pool)))
        return pool[self.device_id]

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(_thread_local, "stack"):
            _thread_local.stack = []
        _thread_local.stack.append(self)
        return self

    def __exit__(self, *args):
        _thread_local.stack.pop()

    def empty_cache(self):
        """Parity with mx.Context.empty_cache — XLA manages pools; no-op."""


def _has_cpu():
    try:
        jax.devices("cpu")
        return True
    except RuntimeError:
        return False


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias: reference scripts that say mx.gpu(i) get the accelerator."""
    return Context("gpu", device_id)


def num_tpus():
    """Count of THIS process's accelerator devices — the ids mx.tpu(i)
    can address (local semantics, consistent with Context.jax_device)."""
    try:
        return len([d for d in jax.local_devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


def num_gpus():
    return num_tpus()


def gpu_memory_info(device_id=0):
    """(free, total) device bytes — reference ``mx.context
    .gpu_memory_info`` parity. A failed probe is COUNTED
    (``telemetry.memory_probe_errors``) and warned once instead of
    silently reported as ``(0, 0)``: zero capacity is a statement of
    fact callers size buffers against, not an acceptable error value."""
    d = Context("tpu", device_id).jax_device
    try:
        stats = d.memory_stats() or {}
        total = stats.get("bytes_limit", 0)
        used = stats.get("bytes_in_use", 0)
        return (total - used, total)
    except Exception as exc:
        from .observability import telemetry as _telemetry
        _telemetry.note_memory_probe_error(exc, where="gpu_memory_info")
        return (0, 0)


def current_context() -> Context:
    stack = getattr(_thread_local, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu", 0) if num_tpus() == 0 else Context("tpu", 0)


# Persistent XLA compile cache (ROADMAP item 4): initialized ONCE at
# import — this module is the first device-touching import every
# ``import mxnet_tpu`` performs, so the cache directory is configured
# before any program can compile: a restarted process re-reads
# previously compiled programs off disk instead of paying XLA again.
# The cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed
# ``<checkout>/.jax_cache``. Never raises (see pcache.py).
from . import pcache as _pcache  # noqa: E402  (import-time init by design)

_pcache.init_from_env()
