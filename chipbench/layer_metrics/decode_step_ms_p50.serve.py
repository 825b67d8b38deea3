"""Median duration of the program's ``generation.step`` spans (one fused
decode step, ending in the readback of the sampled tokens) in the window."""
from chipbench.readers import span_ms_p50


def read(obs):
    return span_ms_p50(obs, "generation.step")
