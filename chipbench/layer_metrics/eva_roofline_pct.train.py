"""Share of its roofline the EVA attention reaches in training, whatever
implements it: the least time the chip could take for the pooling and the
aggregation of the steps traced (forward and backward of every layer on the
pairs the equations name, ``flops_eva.eva_*_cost``: the larger of operations
over peak FLOP/s and bytes over peak bytes/s) over ALL device time under the
program's ``attention`` scope. Dead tiles stepped through, a recomputed
forward, masks and copies show as a low share."""
from chipbench import flops, flops_eva, scope_time


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None \
            or "window_size" not in obs["cfg"]:
        return None
    under = scope_time.seconds_under(obs, ("attention",))
    if not under:
        return None
    cfg, rows = obs["cfg"], obs["batch"] // obs["chips"]
    least = sum(flops.roofline_seconds(*cost(rows, obs["seq"], cfg),
                                       obs["peaks"])
                for cost in (flops_eva.eva_forward_cost,
                             flops_eva.eva_backward_cost))
    steps = scope_time.steps_traced(obs, ("attention",))
    return 100.0 * least * cfg["num_hidden_layers"] * steps / under
