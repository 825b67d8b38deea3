"""Share of ``setup_s`` that passed under at least one program span
(``trainer.build``, ``block.initialize``, ``trainer.step``, ``ndarray.wait``,
the bridged ``jax.*`` phases, ...): the sum of every name's self time less
the window's own spans. What is left is imports, the device's start, the
benchmark's seeding of weights and batches and its host copies for the
check."""
from chipbench.host_timeline import setup_named_pct


def read(obs):
    return setup_named_pct(obs)
