"""Operations and bytes of a decoder with EVA attention and a multi-byte
prediction head, from shapes alone (the companion of ``flops.py`` for
``configs/eva_lm``). A matmul of (m, k) by (k, n) is 2*m*k*n operations; a
backward pass is two more of the same size; recomputed operations are not
counted. The attention counts the pairs the equations name and no others:
a query's own window up to itself, and the chunk summaries of the windows
before its own.
"""


def _widths(cfg):
    heads = cfg["num_attention_heads"]
    return cfg["hidden_size"], heads, cfg["hidden_size"] // heads


def live_pairs(seq, cfg):
    """``(positions, summaries)``: (query, key) pairs of one head over one
    row of ``seq``, of each kind. At 32,768 with windows of 2,048 and chunks
    of 16: 33,570,816 + 31,457,280, or 1,024.5 + 960 a query."""
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    windows = seq // window
    positions = windows * window * (window + 1) // 2
    summaries = window * (window // chunk) * windows * (windows - 1) // 2
    return positions, summaries


def eva_forward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of one layer's EVA attention forward over ``rows``
    sequences. Aggregation: scores and values over the live pairs. Pooling:
    two dot products and two weighted sums a position a head. q, k and v
    read and the output written once; the summaries written and read once."""
    h, heads, d = _widths(cfg)
    flops = rows * heads * (sum(live_pairs(seq, cfg)) * 4 * d + seq * 8 * d)
    columns = 4 * h + 4 * h / cfg["chunk_size"]
    return float(flops), float(rows * seq * columns * itemsize)


def eva_backward_cost(rows, seq, cfg, itemsize=2):
    """(flops, bytes) of the backward: dV, dP, dQ and dK over the live pairs
    and the pooling's share (twice the forward); q, k, v, o and do read, dq,
    dk and dv written, the summaries and their gradients read and written,
    once."""
    h = cfg["hidden_size"]
    flops = 2.0 * eva_forward_cost(rows, seq, cfg)[0]
    columns = 8 * h + 8 * h / cfg["chunk_size"]
    return flops, float(rows * seq * columns * itemsize)


def layer_weights(cfg):
    """Matmul weights of one layer: four attention projections and the
    gated feed-forward's three."""
    h = cfg["hidden_size"]
    return 4 * h * h + 3 * h * cfg["intermediate_size"]


def train_flops_per_step(cfg, batch, seq):
    """Forward + backward (3x the forward) of one training step on
    ``batch`` sequences: projections and feed-forward, the attention, and
    the head over the targets each prediction head has (``seq - 1 - j`` a
    row for head j)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    forward = 2.0 * batch * seq * layers * layer_weights(cfg)
    forward += layers * eva_forward_cost(batch, seq, cfg)[0]
    targets = sum(seq - 1 - j for j in range(cfg["num_pred_heads"]))
    forward += 2.0 * batch * targets * h * cfg["vocab_size"]
    return 3.0 * forward
