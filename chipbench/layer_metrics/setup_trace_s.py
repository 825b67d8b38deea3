"""Seconds jax spent tracing functions to jaxprs from process start to the
window's start, every top-level trace whoever compiled it (a jit traced
inside another's trace counts once): the program's ``pcache.stats()
["trace_s"]``, bridged from ``jax.monitoring``. (A traced run asks for the
step's program once more for its text; jax's in-memory caches answer, so
the step is traced, lowered and loaded once there too.)"""
from chipbench.host_timeline import setup_counter


def read(obs):
    return setup_counter(obs, "trace_s")
