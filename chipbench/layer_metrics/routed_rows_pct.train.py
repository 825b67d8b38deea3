"""Rows the held experts computed in the last step over the rows a uniform
router sends them (experts a token x held / router width, a token, an
expert layer): about 100, and 100 again when routing is skewed between the
held experts; above 100 where the router favours the experts held here. A
dropless layer computes every row it is sent: this counts them."""
from chipbench import flops_mla_moe, scope_time


def read(obs):
    rows = scope_time.routed_rows(obs)
    if rows is None:
        return None
    expected = (flops_mla_moe.expected_rows_per_token(obs["cfg"])
                * obs["batch"] * obs["seq"] * len(rows))
    return 100.0 * sum(map(sum, rows)) / expected
