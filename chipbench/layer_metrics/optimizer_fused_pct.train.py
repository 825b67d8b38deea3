"""Share of the device's busy time in instructions that are the ``optimizer``
scope's or hold one of its operations in their body: the update loop of
``parallel/trainer.py::_one_step`` wherever the compiler put it. The Adam
update of a matrix is fused into its weight-gradient matmul, and that fusion
carries the matmul's name, so ``optimizer_scope_pct.train`` reads the
update's lower bound (what stands alone) and this its upper (the matmuls it
rides in, whole)."""
from chipbench import scopes


def read(obs):
    return scopes.held_pct(obs, scopes.OPTIMIZER)
