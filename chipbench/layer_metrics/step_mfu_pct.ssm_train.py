"""The whole training step's share of the chips' peak for the hybrid
state-space / attention decoder: the operations forward and backward need
(``flops_ssm.train_flops_per_step``: the scan's and the attention's live
pairs only, nothing recomputed) times the steps of the window, over window x
chips x peak FLOP/s."""
from chipbench import flops_ssm


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None \
            or "mamba_d_state" not in obs["cfg"]:
        return None
    done = obs["steps"] * flops_ssm.train_flops_per_step(
        obs["cfg"], obs["batch"], obs["seq"])
    peak = obs["chips"] * obs["peaks"]["flops_per_s"]
    return 100.0 * done / (obs["window_s"] * peak)
