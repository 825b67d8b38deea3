"""Share of the device's busy time under the program's ``moe_sort`` scope,
inside ``moe_experts``: the sort of the assignments by held expert, the rows
of each expert and the maps between the sorted and the token order, all
outside the buffer-size switch. ``None`` where the program writes no such
scope."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("moe_sort",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
