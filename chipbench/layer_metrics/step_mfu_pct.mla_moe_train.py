"""The whole training step's share of the chips' peak for the
latent-attention decoder with held experts: the operations forward and
backward need (``flops_mla_moe.train_flops_per_step``, with the rows the
held experts REALLY computed in each step of the window, from the program's
counter) over window x chips x peak FLOP/s."""
from chipbench import flops_mla_moe, scope_time


def read(obs):
    by_step = scope_time.routed_rows_by_step(obs)
    if by_step is None or obs["peaks"] is None:
        return None
    done = sum(flops_mla_moe.train_flops_per_step(
        obs["cfg"], obs["batch"], obs["seq"], sum(map(sum, rows)))
        for rows in by_step)
    peak = obs["chips"] * obs["peaks"]["flops_per_s"]
    return 100.0 * done / (obs["window_s"] * peak)
