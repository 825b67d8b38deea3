"""Operations and bytes of a hybrid decoder of Mamba-2 state-space layers
and grouped-query attention layers with a tied head, from shapes alone (the
companion of ``flops.py`` for ``configs/hybrid_ssm``). A matmul of (m, k) by
(k, n) is 2*m*k*n operations; a backward pass is two more of the same size;
recomputed operations are not counted. The scan counts the pairs the
equations name inside a chunk (position j at or before position i: the
shared ``C B^T`` once, the heads' ``(L o C B^T) X`` each) and the two state
products (a chunk's end state from ``B^T (decay o dt x)``, and ``C h_prev``);
the attention counts a query's pairs with the positions at or before it.
What a kernel pads (64-wide heads on a 128-wide unit), masks or reads twice
(the chunk-boundary states) is not counted: it shows as a low share.
"""


def _scan_widths(cfg):
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_chunk_size"])


def layer_kinds(cfg):
    """``(mamba layers, attention layers)`` of the configuration as run."""
    kinds = list(cfg["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def scan_forward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of one layer's chunked scan forward over ``rows``
    sequences of ``seq``. x read and y written once (heads x head width
    columns each), B and C read once, the step and the log-decay read once
    in float32."""
    heads, p, n, chunk = _scan_widths(cfg)
    chunk = min(chunk, seq)
    pairs = (seq // chunk) * chunk * (chunk + 1) // 2   # (i, j <= i), a row
    flops = 2.0 * pairs * n                             # C B^T, shared
    flops += heads * (2.0 * pairs * p + 2 * 2.0 * seq * p * n)
    columns = (2 * heads * p + 2 * n) * itemsize + 2 * heads * 4
    return float(rows * flops), float(rows * seq * columns)


def scan_backward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of the scan's backward: two products for each of the
    forward's; x, dy, B, C, the step and the log-decay read, dx, dB, dC and
    the step's and the log-decay's gradients written, once."""
    heads, p, n, _ = _scan_widths(cfg)
    flops = 2.0 * scan_forward_cost(rows, seq, cfg)[0]
    columns = (3 * heads * p + 4 * n) * itemsize + 4 * heads * 4
    return flops, float(rows * seq * columns)


def _attention_widths(cfg):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return heads, kv, cfg["hidden_size"] // heads


def grouped_forward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of one layer's causal grouped-query attention forward:
    scores and values over the pairs (query, position at or before it) of
    every query head; q read and the output written once, k and v (the
    key-value heads only) read once."""
    heads, kv, d = _attention_widths(cfg)
    pairs = seq * (seq + 1) // 2
    columns = (2 * heads + 2 * kv) * d * itemsize
    return float(rows * heads * pairs * 4 * d), float(rows * seq * columns)


def grouped_backward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of the attention's backward: dV, dP, dQ and dK over
    the same pairs (twice the forward; the recomputed scores are not
    counted); q, k, v, o and do read, dq, dk and dv written, once."""
    heads, kv, d = _attention_widths(cfg)
    flops = 2.0 * grouped_forward_cost(rows, seq, cfg)[0]
    columns = (4 * heads + 4 * kv) * d * itemsize
    return flops, float(rows * seq * columns)


def layer_weights(cfg, kind):
    """Matmul weights of one layer of ``kind``: the mixer's projections and
    the gated feed-forward's three (the convolution, the norms and the
    per-head vectors do no matmul work)."""
    h = cfg["hidden_size"]
    ffn = 3 * h * cfg["shared_intermediate_size"]
    if kind == "mamba":
        heads, p, n, _ = _scan_widths(cfg)
        inner = heads * p
        return h * (2 * inner + 2 * n + heads) + inner * h + ffn
    heads, kv, d = _attention_widths(cfg)
    return h * (heads + 2 * kv) * d + heads * d * h + ffn


def train_flops_per_step(cfg, batch, seq):
    """Forward + backward (3x the forward) of one training step on
    ``batch`` sequences: projections and feed-forward of every layer, the
    scans, the attention, and the tied head over the ``seq - 1`` targets a
    row (the lookup does no arithmetic)."""
    mamba, attention = layer_kinds(cfg)
    forward = 2.0 * batch * seq * (mamba * layer_weights(cfg, "mamba")
                                   + attention * layer_weights(cfg,
                                                               "attention"))
    forward += mamba * scan_forward_cost(batch, seq, cfg)[0]
    forward += attention * grouped_forward_cost(batch, seq, cfg)[0]
    forward += 2.0 * batch * (seq - 1) * cfg["hidden_size"] \
        * cfg["vocab_size"]
    return 3.0 * forward
