"""Share of the device's busy time under the program's ``ssm`` scope:
everything a state-space mixer does between its two projections (the convolution, the softplus, the scan, the gating and the gated norm), forward, recomputed and backward."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("ssm",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
