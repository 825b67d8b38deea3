"""Median host time of ``ShardedTrainer.step`` until it returns, from the
program's ``trainer.step`` spans inside the window."""
from chipbench.readers import span_ms_p50


def read(obs):
    return span_ms_p50(obs, "trainer.step")
