"""Share of their roofline the held experts reach: the least time the chip
could take for the grouped products of the rows REALLY routed in the steps
traced (the program's counter, step by step; ``flops_mla_moe.grouped_cost``,
forward and backward) over all device time under the program's
``moe_experts`` scope (sort, gathers, grouped products, combine, and their
backward) in those steps. Whatever implements it: padding to a worst case
shows as a low share."""
from chipbench import flops, flops_mla_moe, scope_time

SCOPE = ("moe_experts",)


def read(obs):
    if obs["peaks"] is None:
        return None
    under = scope_time.seconds_under(obs, SCOPE)
    rows = scope_time.routed_rows_traced(obs, SCOPE) if under else None
    if not rows:
        return None
    least = sum(flops.roofline_seconds(
        *flops_mla_moe.grouped_cost(layer / obs["chips"], obs["cfg"]),
        obs["peaks"]) for layer in rows)
    return 100.0 * least * scope_time.steps_traced(obs, SCOPE) / under
