"""Seconds spent reading executables (Mosaic kernels included) back from
the persistent compile cache, from process start to the window's start: the
program's ``pcache.stats()["load_s"]``."""
from chipbench.host_timeline import setup_counter


def read(obs):
    return setup_counter(obs, "load_s")
