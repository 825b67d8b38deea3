"""Share of the device's busy time under the program's ``moe_combine``
scope, inside ``moe_experts``: the gather of the sorted buffer's rows into
token order, the sums over each token's adjacent rows (forward the result,
backward the tokens' gradient) and the weights' gradient, a row sum taken in
the sorted buffer. ``None`` where the program writes no such scope."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("moe_combine",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
