"""Share of the device's busy time under the expert layer's three scopes:
``moe_router`` (scores and choice), ``moe_experts`` (sort, grouped
products, combine) and ``moe_shared`` (the shared experts), forward and
backward."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(
        obs, ("moe_router", "moe_experts", "moe_shared"))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
