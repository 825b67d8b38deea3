"""Share of the device's busy time spent under the program's ``optimizer``
scope (the update loop of ``parallel/trainer.py::_one_step``). A fusion
counts by the one name the compiler gave it: a weight-gradient matmul fused
with its update carries the matmul's, so this is the update that stands
alone (``optimizer_fused_pct.train`` is the other bound)."""
from chipbench import scopes


def read(obs):
    return scopes.share_pct(obs, scopes.OPTIMIZER)
