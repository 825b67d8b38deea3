"""Longest interval between the starts of consecutive ``trainer.step`` spans
over the median one, over the WHOLE window (intervals that hold the
profiler's start or stop left out): 1.0 is a run without a stall."""
from chipbench.host_timeline import stall


def read(obs):
    return stall(obs, "max_over_p50")
