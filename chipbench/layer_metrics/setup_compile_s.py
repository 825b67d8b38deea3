"""Seconds of backend compilation the persistent cache did not spare, from
process start to the window's start: ``compile_s`` less ``load_s`` of the
program's ``pcache.stats()``. Near 0 on a warm machine, where
``pcache_disk_misses`` reads 0."""
from chipbench.host_timeline import setup_counter


def read(obs):
    compiled = setup_counter(obs, "compile_s")
    loaded = setup_counter(obs, "load_s")
    return None if compiled is None or loaded is None else compiled - loaded
