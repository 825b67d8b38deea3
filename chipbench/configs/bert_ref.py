"""Plain reference of BERT pretraining: forward, loss, gradients and Adam
in straightforward ``jax.numpy``, float32 at ``highest`` matmul precision,
no kernels. It imports nothing of the program and takes nothing the program
has made: weights come from ``chipbench.weights`` and batches from
:func:`make_batches`, both from the seed.

Follows Devlin et al. 2018 (post-norm encoder, exact GELU, MLM head on the
masked positions, NSP on the pooled [CLS]). Departures, all of them what
``mxnet_tpu.models.bert`` ships and the configuration file lists: no bias
on the QKV and output projections, LayerNorm eps 1e-5, an untied MLM
decoder, dropout 0.

The configuration states bfloat16 parameters and Adam state. The reference
holds them in that type too (rounding once per step, as the storage does)
and computes everything between in float32: a float32-stored reference
would move leaves (a LayerNorm gain of 1.0 under a 1e-4 step) that the
stated storage type cannot move.
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.weights import make_weights

ROW_BLOCK = 4       # rows per reference block, so float32 activations fit


def param_spec(cfg):
    """``{name: (shape, kind)}``; names are the program's, without the
    model's own prefix (the builder checks that the two sets agree)."""
    h, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    spec = {
        "encoder_word_embed_weight": ((v, h), "normal"),
        "encoder_segment_embed_weight": ((cfg["type_vocab_size"], h), "normal"),
        "encoder_pos_embed_weight": ((cfg["max_position_embeddings"], h),
                                     "normal"),
        "encoder_layernorm0_gamma": ((h,), "ones"),
        "encoder_layernorm0_beta": ((h,), "zeros"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = "encoder_layer%d_" % i
        spec[p + "_maskedattention0_qkv_weight"] = ((3 * h, h), "normal")
        spec[p + "_maskedattention0_proj_weight"] = ((h, h), "normal")
        spec[p + "layernorm0_gamma"] = ((h,), "ones")
        spec[p + "layernorm0_beta"] = ((h,), "zeros")
        spec[p + "ffn_up_weight"] = ((ff, h), "normal")
        spec[p + "ffn_up_bias"] = ((ff,), "zeros")
        spec[p + "ffn_down_weight"] = ((h, ff), "normal")
        spec[p + "ffn_down_bias"] = ((h,), "zeros")
        spec[p + "layernorm1_gamma"] = ((h,), "ones")
        spec[p + "layernorm1_beta"] = ((h,), "zeros")
    spec.update({
        "pooler_weight": ((h, h), "normal"), "pooler_bias": ((h,), "zeros"),
        "mlm_transform_weight": ((h, h), "normal"),
        "mlm_transform_bias": ((h,), "zeros"),
        "layernorm0_gamma": ((h,), "ones"), "layernorm0_beta": ((h,), "zeros"),
        "mlm_decoder_weight": ((v, h), "normal"),
        "mlm_decoder_bias": ((v,), "zeros"),
        "nsp_weight": ((2, h), "normal"), "nsp_bias": ((2,), "zeros"),
    })
    return spec


def make_batches(cfg, batch, seq, picked, pool, seed):
    """``pool`` host batches from the seed. Every seed gets the same set of
    valid lengths (an even grid over seq/2..seq), dealt in another order, so
    the work does not change with the seed. Masked positions lie in the
    first half, which is always valid. Each batch is a dict of numpy arrays."""
    rng = np.random.default_rng(seed)
    lengths = np.linspace(seq // 2, seq, batch * pool).round().astype(np.int64)
    rng.shuffle(lengths)
    out = []
    for b in range(pool):
        segments = np.zeros((batch, seq), np.int64)
        segments[:, seq // 2:] = 1
        out.append({
            "tokens": rng.integers(4, cfg["vocab_size"], (batch, seq)),
            "segments": segments,
            "valid_len": lengths[b * batch:(b + 1) * batch],
            "positions": np.stack([rng.choice(seq // 2, picked, replace=False)
                                   for _ in range(batch)]),
            "labels": rng.integers(4, cfg["vocab_size"], (batch, picked)),
            "weights": np.ones((batch, picked), np.float32),
            "nsp": rng.integers(0, 2, (batch,)),
        })
    return out


def _round_to(x, precision):
    """Round matmul operands to the control's precision, straight through
    for the gradient."""
    if precision == "float32":
        return x
    low = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    return x + jax.lax.stop_gradient(x.astype(low).astype(x.dtype) - x)


def _dense(x, w, b, precision):
    """``x @ w.T + b`` over the last axis, as one 2-D matmul."""
    flat = _round_to(x, precision).reshape(-1, x.shape[-1])
    y = jnp.matmul(flat, _round_to(w, precision).T, precision="highest")
    y = y.reshape(x.shape[:-1] + (w.shape[0],))
    return y if b is None else y + b


def _layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / float(np.sqrt(2.0))))


def block_loss(params, rows, cfg, total_weight, total_rows, precision):
    """This block of rows' share of the batch loss: its MLM log-likelihood
    over the batch's total mask weight plus its NSP terms over the batch's
    rows, so the shares of all blocks add up to the step's loss."""
    h, heads, eps = cfg["hidden_size"], cfg["num_attention_heads"], cfg["layer_norm_eps"]
    tokens, valid = rows["tokens"], rows["valid_len"]
    b, t = tokens.shape
    p = params
    x = (p["encoder_word_embed_weight"][tokens]
         + p["encoder_segment_embed_weight"][rows["segments"]]
         + p["encoder_pos_embed_weight"][jnp.arange(t)][None])
    x = _layer_norm(x, p["encoder_layernorm0_gamma"],
                    p["encoder_layernorm0_beta"], eps)
    keep = (jnp.arange(t)[None, :] < valid[:, None])[:, None, None, :]
    # the layers are alike: stack their leaves and scan, so that one layer
    # is compiled, not twelve (the gradients still come back leaf by leaf)
    names = ("_maskedattention0_qkv_weight", "_maskedattention0_proj_weight",
             "layernorm0_gamma", "layernorm0_beta", "ffn_up_weight",
             "ffn_up_bias", "ffn_down_weight", "ffn_down_bias",
             "layernorm1_gamma", "layernorm1_beta")
    stacked = {n: jnp.stack([p["encoder_layer%d_%s" % (i, n)]
                             for i in range(cfg["num_hidden_layers"])])
               for n in names}

    def layer(x, w):
        qkv = _dense(x, w["_maskedattention0_qkv_weight"], None, precision)
        qkv = qkv.reshape(b, t, 3, heads, h // heads)
        q, k, v = (_round_to(qkv[:, :, j], precision) for j in range(3))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest")
        scores = jnp.where(keep, scores / float(np.sqrt(h // heads)), -1e30)
        probs = _round_to(jax.nn.softmax(scores, axis=-1), precision)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")
        att = _dense(att.reshape(b, t, h), w["_maskedattention0_proj_weight"],
                     None, precision)
        x = _layer_norm(x + att, w["layernorm0_gamma"], w["layernorm0_beta"],
                        eps)
        up = _gelu(_dense(x, w["ffn_up_weight"], w["ffn_up_bias"], precision))
        down = _dense(up, w["ffn_down_weight"], w["ffn_down_bias"], precision)
        return _layer_norm(x + down, w["layernorm1_gamma"],
                           w["layernorm1_beta"], eps), None

    x, _ = jax.lax.scan(layer, x, stacked)
    pooled = jnp.tanh(_dense(x[:, 0], p["pooler_weight"], p["pooler_bias"],
                             precision))
    picked = jnp.take_along_axis(x, rows["positions"][:, :, None], axis=1)
    hm = _gelu(_dense(picked, p["mlm_transform_weight"],
                      p["mlm_transform_bias"], precision))
    hm = _layer_norm(hm, p["layernorm0_gamma"], p["layernorm0_beta"], eps)
    logits = _dense(hm, p["mlm_decoder_weight"], p["mlm_decoder_bias"],
                    precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, rows["labels"][:, :, None], axis=-1)[..., 0]
    mlm = -(ll * rows["weights"]).sum() / (total_weight + 1e-6)
    nsp_logp = jax.nn.log_softmax(
        _dense(pooled, p["nsp_weight"], p["nsp_bias"], precision), axis=-1)
    nsp = -jnp.take_along_axis(nsp_logp, rows["nsp"][:, None], axis=-1).sum()
    return mlm + nsp / total_rows


@partial(jax.jit, static_argnums=(2, 5))
def _block_grad(params, rows, cfg_items, total_weight, total_rows, precision):
    cfg = dict(cfg_items)
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    return jax.value_and_grad(block_loss)(f32, rows, cfg, total_weight,
                                          total_rows, precision)


@partial(jax.jit, static_argnums=(5, 6))
def _adam(w, m, v, g, t, hyper, storage):
    """The update the configuration states (``optimizer`` in its file): Adam
    with bias correction folded into the rate, state and parameters rounded
    to the storage type once a step."""
    lr, b1, b2, eps = hyper
    g = g.astype(storage).astype(jnp.float32)   # the optimizer gets it stored
    w, m, v = (a.astype(jnp.float32) for a in (w, m, v))
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = w - lr_t * m / (jnp.sqrt(v) + eps)
    return w.astype(storage), m.astype(storage), v.astype(storage)


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def leaf_norms(tree):
    """``{name: float}`` L2 norm of every leaf, accumulated in float32."""
    return {k: float(v) for k, v in jax.device_get(_leaf_norms(tree)).items()}


def run_steps(cfg, cell, seed, steps, precision="float32", rows_used=None):
    """Follow the first ``steps`` training steps from the seed. Returns
    ``{"losses": [...], "grad_norms": {leaf: norm of the first gradient as
    the optimizer gets it}, "first_gradient": {leaf: that gradient, on the
    device}, "change_norms": {leaf: norm of the parameters' change after the
    steps}}``.

    ``precision`` other than ``float32`` is the control: matmul operands
    rounded to it. ``rows_used`` (a count) plants the fault "part of the
    batch left out, the mean taken over the rest"."""
    batch, seq, picked = cell["batch"], cell["seq"], cell["picked"]
    storage = cfg["param_dtype"]
    opt = cfg["optimizer"]
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"])
    params = make_weights(param_spec(cfg), seed, storage)
    start = dict(params)
    ms = {k: jnp.zeros_like(v) for k, v in params.items()}
    vs = {k: jnp.zeros_like(v) for k, v in params.items()}
    batches = make_batches(cfg, batch, seq, picked, cell["pool"], seed)
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float))))
    losses, grad_norms, first_gradient = [], None, None
    for t in range(1, steps + 1):
        full = batches[(t - 1) % len(batches)]
        n = rows_used or batch
        host = {k: a[:n] for k, a in full.items()}
        total_weight = float(host["weights"].sum())
        loss, grads = 0.0, None
        for lo in range(0, n, ROW_BLOCK):
            rows = {k: jnp.asarray(a[lo:lo + ROW_BLOCK]) for k, a in host.items()}
            l, g = _block_grad(params, rows, cfg_items, total_weight,
                               float(n), precision)
            loss += float(l)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        losses.append(loss)
        if t == 1:      # as the optimizer gets it: in the storage type
            first_gradient = {k: g.astype(storage) for k, g in grads.items()}
            grad_norms = leaf_norms(first_gradient)
        for k in list(params):
            params[k], ms[k], vs[k] = _adam(params[k], ms[k], vs[k],
                                            grads.pop(k), t, hyper, storage)
    change = leaf_norms({k: params[k].astype(jnp.float32)
                         - start[k].astype(jnp.float32) for k in params})
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "first_gradient": first_gradient}
