"""Seconds jax spent lowering jaxprs to MLIR modules from process start to
the window's start: the program's ``pcache.stats()["lower_s"]``."""
from chipbench.host_timeline import setup_counter


def read(obs):
    return setup_counter(obs, "lower_s")
