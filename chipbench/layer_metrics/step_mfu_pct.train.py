"""The whole training step's share of the chips' peak: operations the
forward and backward passes need (``flops.bert_train_flops_per_step``) for
the steps of the window, over window x chips x peak FLOP/s."""
from chipbench import flops


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None:
        return None
    per_step = flops.bert_train_flops_per_step(
        obs["cfg"], obs["batch"], obs["seq"], obs["picked"])
    peak = obs["chips"] * obs["peaks"]["flops_per_s"]
    return 100.0 * per_step * obs["steps"] / (obs["window_s"] * peak)
