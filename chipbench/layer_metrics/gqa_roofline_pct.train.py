"""Share of its roofline the grouped-query attention reaches in training,
whatever implements it: the least time the chip could take for the attention
of the steps traced (forward and backward of every attention layer on the
causal pairs, ``flops_ssm.grouped_*_cost``: the larger of operations over
peak FLOP/s and bytes over peak bytes/s) over ALL device time under the
program's ``attention`` scope. Heads of 64 on a 128-wide unit, masked tiles,
the scores recomputed in each backward kernel and the transposes around the
kernels show as a low share."""
from chipbench import flops, flops_ssm, scope_time


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None \
            or "mamba_d_state" not in obs["cfg"]:
        return None
    under = scope_time.seconds_under(obs, ("attention",))
    if not under:
        return None
    cfg, rows = obs["cfg"], obs["batch"] // obs["chips"]
    least = sum(flops.roofline_seconds(*cost(rows, obs["seq"], cfg),
                                       obs["peaks"])
                for cost in (flops_ssm.grouped_forward_cost,
                             flops_ssm.grouped_backward_cost))
    steps = scope_time.steps_traced(obs, ("attention",))
    return 100.0 * least * flops_ssm.layer_kinds(cfg)[1] * steps / under
