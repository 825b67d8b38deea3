"""Operations and bytes of a latent-attention decoder with held experts,
from shapes alone (the companion of ``flops.py`` for ``configs/mla_moe``).
A matmul of (m, k) by (k, n) is 2*m*k*n operations; a backward pass is two
more of the same size; recomputed operations are not counted; a causal
attention counts the half of the score matrix at or below the diagonal.
"""


def _widths(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def attention_weights(cfg):
    """Matmul weights of one layer's attention: the query projection, the
    joint down-projection (latent + rotary key), the up-projection of the
    latent and the output projection."""
    h, heads, dn, dr, dv, rank = _widths(cfg)
    return (h * heads * (dn + dr) + h * (rank + dr)
            + rank * heads * (dn + dv) + heads * dv * h)


def expert_weights(cfg):
    """Matmul weights of ONE routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expected_rows_per_token(cfg):
    """Assignments a token sends to the experts held here under a uniform
    router: experts a token x held / router width (6 x 8 / 64 = 0.75)."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_width"])


def latent_forward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of one layer's causal latent attention forward over
    ``rows`` sequences: scores over nope + rope, values over v, on the
    UNPADDED widths; q (both parts), the up-projected latent and the one
    rotary key a position read, the output written, once."""
    h, heads, dn, dr, dv, rank = _widths(cfg)
    flops = rows * heads * seq * seq * ((dn + dr) + dv)     # 2 * half
    columns = heads * (dn + dr) + heads * (dn + dv) + dr + heads * dv
    return float(flops), float(rows * seq * columns * itemsize)


def latent_backward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of the backward: dV and dP over v, dQ and dK over
    nope + rope (twice the forward); q, kv, k_rope, o and do read, dq, dkv
    and dk_rope written, once."""
    h, heads, dn, dr, dv, rank = _widths(cfg)
    flops = 2.0 * rows * heads * seq * seq * ((dn + dr) + dv)
    columns = 2 * (heads * (dn + dr) + heads * (dn + dv) + dr) \
        + 2 * heads * dv
    return flops, float(rows * seq * columns * itemsize)


def grouped_cost(routed_rows, cfg, itemsize=2):
    """(flops, bytes) of the held experts' grouped products, forward and
    backward, for ``routed_rows`` assignments in one layer: three matmuls a
    row forward and six backward; the rows and their products read and
    written once a pass and the held experts' weights read twice and their
    gradient written once."""
    h, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 3.0 * routed_rows * 2 * expert_weights(cfg)
    row_bytes = routed_rows * (2 * h + 3 * fe) * itemsize
    weight_bytes = cfg["n_routed_experts"] * expert_weights(cfg) * itemsize
    return flops, 3.0 * row_bytes + 3.0 * weight_bytes


def train_flops_per_step(cfg, batch, seq, routed_rows):
    """Forward + backward (3x the forward) of one training step on
    ``batch`` sequences, with ``routed_rows`` the assignments the held
    experts really computed, summed over the expert layers: projections,
    attention, the dense layers' feed-forward, router, shared experts, the
    routed rows, and the head over the ``seq - 1`` targets of a row."""
    h = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    tokens = batch * seq
    forward = 2.0 * tokens * layers * attention_weights(cfg)
    forward += layers * latent_forward_cost(batch, seq, cfg)[0]
    forward += 2.0 * tokens * dense * 3 * h * cfg["intermediate_size"]
    shared = cfg["n_shared_experts"] * expert_weights(cfg)
    forward += 2.0 * tokens * (layers - dense) * (
        shared + h * cfg["router_width"])
    forward += 2.0 * routed_rows * expert_weights(cfg)
    forward += 2.0 * batch * (seq - 1) * h * cfg["vocab_size"]
    return 3.0 * forward
