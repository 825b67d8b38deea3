"""The window's seconds lost to stalls: the sum of (interval - median) over
the step-to-step intervals longer than 1.5 medians; 0.0 where none is."""
from chipbench.host_timeline import stall


def read(obs):
    return stall(obs, "stall_s")
