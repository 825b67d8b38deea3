"""Share of the device's busy time under the program's ``ssm_scan`` scope:
the chunked scan with its log-decay sums, layout copies and the D skip, forward, recomputed and backward (inside ``ssm``)."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("ssm_scan",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
