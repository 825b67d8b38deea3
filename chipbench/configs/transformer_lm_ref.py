"""Plain reference of the decoder-only LM: one full forward pass over a
prompt with its served tokens, in straightforward ``jax.numpy``, float32 at
``highest`` matmul precision, no cache, no kernels, no batching. Imports
nothing of the program; weights come from ``chipbench.weights``.

Follows GPT-2 (Radford et al. 2019): learned positions, pre-norm blocks,
final LayerNorm. Departures, all what ``mxnet_tpu.models.transformer`` ships
and the configuration file lists under ``assumed``: exact (erf) GELU, an
output head untied from the embedding, no bias on the QKV and output
projections.
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp


def param_spec(cfg):
    """``{name: (shape, kind)}``, names as the program has them without the
    model's own prefix."""
    h, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    spec = {"embed_weight": ((v, h), "normal"),
            "pos_weight": ((cfg["n_positions"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = "blocks_transformerencoderlayer%d_" % i
        spec[p + "layernorm0_gamma"] = ((h,), "ones")
        spec[p + "layernorm0_beta"] = ((h,), "zeros")
        spec[p + "multiheadattention0_qkv_weight"] = ((3 * h, h), "normal")
        spec[p + "multiheadattention0_proj_weight"] = ((h, h), "normal")
        spec[p + "layernorm1_gamma"] = ((h,), "ones")
        spec[p + "layernorm1_beta"] = ((h,), "zeros")
        spec[p + "ffn_up_weight"] = ((ff, h), "normal")
        spec[p + "ffn_up_bias"] = ((ff,), "zeros")
        spec[p + "ffn_down_weight"] = ((h, ff), "normal")
        spec[p + "ffn_down_bias"] = ((h,), "zeros")
    spec["layernorm0_gamma"] = ((h,), "ones")
    spec["layernorm0_beta"] = ((h,), "zeros")
    spec["head_weight"] = ((v, h), "normal")
    return spec


def _layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(x.dtype)))


def _operands(x, operands):
    """Round a matmul operand to the type the configuration's matmuls take
    their operands in (``None``: leave it)."""
    if operands is None:
        return x
    low = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[operands]
    return x.astype(low).astype(x.dtype)


def hidden_states(params, tokens, cfg, dtype, operands):
    """Final-norm hidden states ``(T, hidden)`` of one sequence ``(T,)``,
    every array and operation in ``dtype``; matmul operands rounded to
    ``operands`` first, the products accumulated exactly (``highest``)."""
    h, heads, eps = cfg["hidden_size"], cfg["num_attention_heads"], cfg["layer_norm_eps"]
    p = {k: v.astype(dtype) for k, v in params.items()}
    t = tokens.shape[0]

    def mm(a, b):
        return jnp.matmul(_operands(a, operands), _operands(b, operands),
                          precision="highest")
    x = p["embed_weight"][tokens] + p["pos_weight"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["num_hidden_layers"]):
        n = "blocks_transformerencoderlayer%d_" % i
        y = _layer_norm(x, p[n + "layernorm0_gamma"], p[n + "layernorm0_beta"], eps)
        qkv = mm(y, p[n + "multiheadattention0_qkv_weight"].T)
        qkv = qkv.reshape(t, 3, heads, h // heads)
        q, k, v = (_operands(qkv[:, j], operands) for j in range(3))
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision="highest")
        scores = scores / np.sqrt(h // heads).astype(dtype)
        scores = jnp.where(causal[None], scores, jnp.asarray(-1e30, dtype))
        probs = _operands(jax.nn.softmax(scores, axis=-1), operands)
        att = jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")
        x = x + mm(att.reshape(t, h), p[n + "multiheadattention0_proj_weight"].T)
        y = _layer_norm(x, p[n + "layernorm1_gamma"], p[n + "layernorm1_beta"], eps)
        up = _gelu(mm(y, p[n + "ffn_up_weight"].T) + p[n + "ffn_up_bias"])
        x = x + mm(up, p[n + "ffn_down_weight"].T) + p[n + "ffn_down_bias"]
    return _layer_norm(x, p["layernorm0_gamma"], p["layernorm0_beta"], eps)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _served_rows(params, tokens, first_row, cfg_items, dtype, rows, operands):
    """Logit rows ``(rows, vocab)`` in float32 that produced the served
    tokens: rows ``first_row .. first_row + rows - 1`` of the sequence."""
    cfg = dict(cfg_items)
    x = hidden_states(params, tokens, cfg, jnp.dtype(dtype), operands)
    picked = jax.lax.dynamic_slice_in_dim(x, first_row, rows, axis=0)
    logits = jnp.matmul(
        _operands(picked, operands),
        _operands(params["head_weight"].astype(x.dtype), operands).T,
        precision="highest")
    return logits.astype(jnp.float32)


def served_logits(params, cfg, prompt, served, dtype="float32",
                  operands="stated"):
    """Run the reference once over ``prompt`` with its ``served`` tokens and
    return the ``(len(served), vocab)`` logit rows the tokens were drawn
    from. Sequences are padded to ``n_positions`` (one compile; a causal
    model's rows do not see the pad tail). ``dtype`` other than float32 is
    the control: the whole forward in that type. ``operands``: the type
    matmul operands are rounded to; ``"stated"`` takes the configuration's
    ``matmul_operand_dtype``."""
    if operands == "stated":
        operands = cfg.get("matmul_operand_dtype")
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    width = cfg["n_positions"]
    rows = cfg["serving"]["max_new_tokens"]
    seq = np.zeros((width,), np.int32)
    fed = list(prompt) + list(served[:-1])
    seq[:len(fed)] = fed
    first = min(len(prompt) - 1, width - rows)
    logits = _served_rows(params, jnp.asarray(seq), first, cfg_items, dtype,
                          rows, operands)
    lo = len(prompt) - 1 - first
    return logits[lo:lo + len(served)]
