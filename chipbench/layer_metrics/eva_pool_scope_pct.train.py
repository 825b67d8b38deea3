"""Share of the device's busy time under the program's ``eva_pool`` scope:
the learned pooling of each chunk's keys and values into its summary, and
the pooling's gradient (XLA, inside the ``attention`` scope)."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("eva_pool",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
