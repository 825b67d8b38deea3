"""Share of its roofline the attention reaches in training, whatever
implements it: the least time the chip could take for the attention of the
steps traced (forward and backward of every layer, ``flops.flash_*_cost``,
the larger of operations over peak FLOP/s and bytes over peak bytes/s, as
``flash_roofline_pct.train`` reckons it) over ALL device time under the
program's ``attention`` scope. The steps are counted from the trace."""
from chipbench import flops, scopes


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None:
        return None
    by_layer = scopes.seconds_by_layer(obs)
    if by_layer is None or not by_layer[scopes.ATTENTION]:
        return None
    cfg, peaks = obs["cfg"], obs["peaks"]
    rows = obs["batch"] // obs["chips"]
    least = sum(flops.roofline_seconds(*cost(rows, obs["seq"], cfg, 2, False),
                                       peaks)
                for cost in (flops.flash_forward_cost,
                             flops.flash_backward_cost))
    steps = scopes.steps_traced(obs, scopes.ATTENTION)
    return (100.0 * least * cfg["num_hidden_layers"] * steps
            / by_layer[scopes.ATTENTION])
