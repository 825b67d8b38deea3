"""Share of the device's busy time under the program's ``loss_head`` scope:
the chunked cross-entropy head's loop over chunks, the loss and the head's
gradient (its products over the vocabulary and the passes over the logits)."""
from chipbench import scope_time


def read(obs):
    if obs["kind"] != "train":
        return None
    under = scope_time.seconds_under(obs, ("loss_head",))
    if under is None or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * under / obs["trace"]["busy_s"]
