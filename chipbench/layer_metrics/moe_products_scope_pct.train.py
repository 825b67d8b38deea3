"""Share of the device's busy time under the program's ``moe_products``
scope, inside ``moe_experts``: the held experts' grouped products (``gmm``
forward, recomputed and for the rows' gradient, ``tgmm`` for the weights'
gradients). ``None`` where the program writes no such scope."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("moe_products",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
