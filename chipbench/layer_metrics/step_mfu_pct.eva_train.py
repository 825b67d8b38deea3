"""The whole training step's share of the chips' peak for the decoder with
EVA attention: the operations forward and backward need
(``flops_eva.train_flops_per_step``: the live pairs of the attention only,
nothing recomputed) times the steps of the window, over window x chips x
peak FLOP/s."""
from chipbench import flops_eva


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None \
            or "window_size" not in obs["cfg"]:
        return None
    done = obs["steps"] * flops_eva.train_flops_per_step(
        obs["cfg"], obs["batch"], obs["seq"])
    peak = obs["chips"] * obs["peaks"]["flops_per_s"]
    return 100.0 * done / (obs["window_s"] * peak)
