"""Operations and bytes the algorithms need, from shapes alone.

Every utilization the benchmark prints divides one of these by a peak of
``peaks.py``. A matmul of (m, k) by (k, n) is 2*m*k*n operations; the
backward pass of a matmul is two more of the same size; recomputed
operations are not counted.
"""


def block_params(cfg):
    """Matmul weights of ONE transformer block: QKV, output projection and
    the two feed-forward matrices (biases and norms do no matmul work)."""
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * h * h + 2 * h * ff


def attention_forward_flops(rows, q_len, kv_len, cfg, causal=False):
    """QK^T and PV for ``rows`` sequences in one layer: 4*q*kv*hidden each
    (heads * head_dim == hidden), halved under a causal mask."""
    flops = 4.0 * rows * q_len * kv_len * cfg["hidden_size"]
    return flops / 2 if causal else flops


def bert_train_flops_per_step(cfg, batch, seq, picked):
    """Forward + backward of one BERT pretraining step (3x the forward):
    the blocks over every position (padding counted, as the step computes
    it), the MLM transform and decoder over the ``picked`` rows of each
    sequence, the pooler and the NSP head once a sequence."""
    h, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    tokens = batch * seq
    forward = 2.0 * tokens * layers * block_params(cfg)
    forward += layers * attention_forward_flops(batch, seq, seq, cfg)
    forward += 2.0 * batch * picked * (h * h + h * v)
    forward += 2.0 * batch * (h * h + 2 * h)
    return 3.0 * forward


def lm_prefill_flops(cfg, prompt_len):
    """Forward operations to prefill one prompt of a decoder-only LM: every
    position through the blocks, attending to itself and all earlier ones,
    and the vocabulary head on the last position only."""
    h, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    flops = 2.0 * prompt_len * layers * block_params(cfg)
    flops += 4.0 * layers * h * prompt_len * (prompt_len + 1) / 2.0
    return flops + 2.0 * h * v


def lm_decode_flops(cfg, cached):
    """Forward operations of one decoded token that attends to ``cached``
    earlier positions and itself, with the vocabulary head."""
    h, v, layers = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    return (2.0 * layers * block_params(cfg) + 4.0 * layers * h * (cached + 1)
            + 2.0 * h * v)


def flash_forward_cost(rows, seq, cfg, itemsize, causal):
    """(flops, bytes) of one fused attention forward over ``rows`` sequences
    of ``seq``: q, k, v read and the output written once."""
    flops = attention_forward_flops(rows, seq, seq, cfg, causal)
    return flops, 4.0 * rows * seq * cfg["hidden_size"] * itemsize


def flash_backward_cost(rows, seq, cfg, itemsize, causal):
    """(flops, bytes) of the attention backward: four matmuls of the
    forward's size (dV, dP, dQ, dK; the recomputed scores are not counted);
    q, k, v, o, do read and dq, dk, dv written once."""
    flops = 2.0 * attention_forward_flops(rows, seq, seq, cfg, causal)
    return flops, 8.0 * rows * seq * cfg["hidden_size"] * itemsize


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peaks["flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
