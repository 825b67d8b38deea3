"""Seconds inside ``ShardedTrainer.__init__`` (functionalize, shard, the
per-parameter copy, cast and placement, the optimizer's states), from the
program's ``trainer.build`` spans: inclusive, set-up only."""
from chipbench.host_timeline import phase


def read(obs):
    built = phase(obs, "trainer.build")
    return None if built is None else built["total_ms"] / 1e3
