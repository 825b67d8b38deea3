"""Share of its roofline the chunked state-space scan reaches in training,
whatever implements it: the least time the chip could take for the scans of
the steps traced (forward and backward of every state-space layer,
``flops_ssm.scan_*_cost``: the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over ALL device time under the program's
``ssm_scan`` scope. A recomputed forward, heads of 64 padded to the unit's
128, the masked half of a chunk's tile, the chunk-boundary states and the
layouts' copies show as a low share."""
from chipbench import flops, flops_ssm, scope_time


def read(obs):
    if obs["kind"] != "train" or obs["peaks"] is None \
            or "mamba_d_state" not in obs["cfg"]:
        return None
    under = scope_time.seconds_under(obs, ("ssm_scan",))
    if not under:
        return None
    cfg, rows = obs["cfg"], obs["batch"] // obs["chips"]
    least = sum(flops.roofline_seconds(*cost(rows, obs["seq"], cfg),
                                       obs["peaks"])
                for cost in (flops_ssm.scan_forward_cost,
                             flops_ssm.scan_backward_cost))
    steps = scope_time.steps_traced(obs, ("ssm_scan",))
    return 100.0 * least * flops_ssm.layer_kinds(cfg)[0] * steps / under
