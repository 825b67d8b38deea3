"""Median duration of the program's ``generation.prefill`` spans (one
admission: prefill at a rung, arena write, first token) in the window."""
from chipbench.readers import span_ms_p50


def read(obs):
    return span_ms_p50(obs, "generation.prefill")
