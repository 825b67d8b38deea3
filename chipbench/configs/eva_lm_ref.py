"""Plain reference of a byte-level decoder with EVA attention and a
multi-byte prediction head, as one pipeline stage holds it: forward, loss,
gradients and Adam in straightforward ``jax.numpy``, float32 at ``highest``
matmul precision, no kernel. It imports nothing of the program and takes
nothing the program has made: weights and batches come from the seed.

The layer, after EvaByte's ``config.json`` (``attention_class`` eva; the
mechanism is Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", ICLR 2023). Write W = ``window_size``, C = ``chunk_size``,
s = 1/sqrt(head width), ``win(t) = floor(t / W)`` and
RMSNorm1(x) = x / sqrt(mean(x^2) + eps) * (1 + g) (the gain stored as an
offset from one). With n = RMSNorm1(x), for every head h: ``q_t = R_t(W_q
n_t)_h``, ``k_t = R_t(W_k n_t)_h``, ``v_t = (W_v n_t)_h``, R the rotary
rotation by the absolute position t over the whole head (halves pairing).
Chunk summaries, a pair for each head and each chunk c of C consecutive
positions, from two learned vectors a head, mu_h and phi_h:
``kt_c = sum_{m in c} softmax_{m in c}(s mu_h . k_m) k_m`` and
``vt_c = sum_{m in c} softmax_{m in c}(s phi_h . k_m) v_m``. The query at t,
in window w = win(t), sees the positions m <= t of ITS OWN window exactly
and the summaries of every chunk of an EARLIER window, under ONE softmax
with scale s; ``h = x + W_o concat_h(o)``. With m = RMSNorm1(h): ``out = h
+ W_down(silu(W_gate m) * W_up m)``. After the last layer RMSNorm1, then a
head of ``num_pred_heads`` x ``vocab_size`` rows: ``logits[t, j]`` predicts
the byte at t + 1 + j; the loss is the mean over j of the mean
cross-entropy over the positions that have such a byte.

What the configuration's row did not settle is listed under ``assumed`` in
the configuration's file (which vector pools keys and which weighs values,
the scale on both pooling logits and no ``-|k|^2 / 2`` term, equal weights
of the prediction heads); EvaByte's own ``eva_pt_ref.py`` is the authority
and is not on this machine.

Parameters and Adam state are held in the stated storage type (rounded once
a step, as the storage does) and everything between is float32. A step is
walked a LAYER at a time (forward keeping each layer's input, then each
layer's vector-Jacobian product and its Adam update, last layer first), so
the device holds the stored parameters, one layer's float32 copy and
gradient and one layer's activations whatever the depth; the moments wait on
the host. ``loss`` is the same model in one expression, which the tests
differentiate whole.
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import weights as seeded

QUERY_BLOCK = 512       # queries per block of the reference's attention
POSITION_BLOCK = 2048   # positions per block of the feed-forward and head
FAULTS = ("summaries_left_out", "own_window_summaries_seen",
          "first_head_only")


def dims(cfg):
    heads = cfg["num_attention_heads"]
    return cfg["hidden_size"], heads, cfg["hidden_size"] // heads


def layer_spec(cfg):
    """``{short name: (shape, kind)}`` of ONE layer's leaves."""
    h, heads, d = dims(cfg)
    ff = cfg["intermediate_size"]
    return {"attn_norm_offset": ((h,), "zeros"),
            "attn_q_weight": ((h, h), "normal"),
            "attn_k_weight": ((h, h), "normal"),
            "attn_v_weight": ((h, h), "normal"),
            "attn_mu": ((heads, d), "normal"),
            "attn_phi": ((heads, d), "normal"),
            "attn_proj_weight": ((h, h), "normal"),
            "ffn_norm_offset": ((h,), "zeros"),
            "ffn_gate_weight": ((ff, h), "normal"),
            "ffn_up_weight": ((ff, h), "normal"),
            "ffn_down_weight": ((h, ff), "normal")}


def param_spec(cfg):
    """``{name: (shape, kind)}``; names are the program's, without the
    model's own prefix. A norm's leaf is its gain's OFFSET from one."""
    h = cfg["hidden_size"]
    spec = {"embed_weight": ((cfg["vocab_size"], h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        for k, v in layer_spec(cfg).items():
            spec["layer%d_%s" % (i, k)] = v
    spec["norm_offset"] = ((h,), "zeros")
    spec["head_weight"] = ((cfg["num_pred_heads"] * cfg["vocab_size"], h),
                           "normal")
    return spec


@partial(jax.jit, static_argnums=(1, 2))
def _rescale(weights, scale, dtype):
    return {k: (scale * v.astype(jnp.float32)).astype(dtype)
            for k, v in weights.items()}


def make_params(cfg, seed, dtype):
    """The seed's weights in ``dtype``: N(0, ``init_std``) for every matrix
    and for mu and phi, norm offsets of nought."""
    drawn = seeded.make_weights(param_spec(cfg), seed, "float32")
    return _rescale(drawn, cfg["init_std"] / seeded.INIT_STD, dtype)


def make_batches(cfg, batch, seq, pool, seed):
    """``pool`` host batches of byte ids, uniform over the vocabulary."""
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg["vocab_size"], (batch, seq))}
            for _ in range(pool)]


def make_labels(tokens, heads):
    """``(B, S, heads)``: the label of position t for head j is the byte at
    t + 1 + j, and -1 where the row has none."""
    tokens = np.asarray(tokens)
    batch, seq = tokens.shape
    labels = np.full((batch, seq, heads), -1, np.int64)
    for j in range(heads):
        labels[:, :seq - 1 - j, j] = tokens[:, 1 + j:]
    return labels


def _round_to(x, precision):
    """Round matmul operands to the control's precision, straight through
    for the gradient."""
    if precision == "float32":
        return x
    low = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    return x + jax.lax.stop_gradient(x.astype(low).astype(x.dtype) - x)


def _dense(x, w, precision):
    """``x @ w.T`` over the last axis (``w`` stored (out, in))."""
    return jnp.matmul(_round_to(x, precision), _round_to(w, precision).T,
                      precision="highest")


def rms_norm1(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + offset)


def rotary(x, theta):
    """Rotate ``x (S, ..., D)`` by its position along axis 0: the pair of
    element i is element i + D/2, frequency ``theta ** (-2 i / D)``."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (float(theta) ** (np.arange(0, d, 2, dtype=np.float64) / d))
    angle = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angle), jnp.float32).reshape(shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, mu, phi, chunk, precision="float32"):
    """``(kt, vt)``, each ``(S / chunk, H, D)``, of one sequence's rotated
    keys and its values ``(S, H, D)``."""
    s, heads, d = k.shape
    scale = 1.0 / float(np.sqrt(d))
    kc = k.reshape(s // chunk, chunk, heads, d)
    vc = v.reshape(s // chunk, chunk, heads, d)

    def pooled(vector, what):
        logits = scale * jnp.einsum("nchd,hd->nch", _round_to(kc, precision),
                                    _round_to(vector, precision),
                                    precision="highest")
        weights = jax.nn.softmax(logits, axis=1)
        return jnp.einsum("nch,nchd->nhd", weights, what, precision="highest")

    return pooled(mu, kc), pooled(phi, vc)


def attention(w, n, cfg, precision, fault=None):
    """EVA attention of ONE sequence ``n (S, hidden)`` (already normed),
    the scores computed a block of queries at a time."""
    h, heads, d = dims(cfg)
    s = n.shape[0]
    window, chunk = cfg["window_size"], cfg["chunk_size"]
    per_window = window // chunk
    q, k, v = (_dense(n, w["attn_%s_weight" % x], precision).reshape(
        s, heads, d) for x in "qkv")
    q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    kt, vt = summaries(k, v, w["attn_mu"], w["attn_phi"], chunk, precision)
    q, k, v, kt, vt = (_round_to(a, precision) for a in (q, k, v, kt, vt))
    scale = 1.0 / float(np.sqrt(d))
    block = min(QUERY_BLOCK, window)
    if window % block:
        block = window
    chunk_window = jnp.arange(s // chunk) // per_window

    def one(args):
        qb, pos = args
        mine = pos[0] // window
        kw = jax.lax.dynamic_slice_in_dim(k, mine * window, window)
        vw = jax.lax.dynamic_slice_in_dim(v, mine * window, window)
        key_pos = mine * window + jnp.arange(window)
        exact = jnp.einsum("qhd,khd->hqk", qb, kw, precision="highest")
        exact = jnp.where(pos[None, :, None] >= key_pos[None, None, :],
                          exact * scale, -1e30)
        far = jnp.einsum("qhd,chd->hqc", qb, kt, precision="highest") * scale
        if fault == "summaries_left_out":
            seen = jnp.zeros_like(chunk_window, bool)
        elif fault == "own_window_summaries_seen":
            seen = chunk_window <= mine
        else:
            seen = chunk_window < mine
        far = jnp.where(seen[None, None, :], far, -1e30)
        probs = _round_to(jax.nn.softmax(
            jnp.concatenate([exact, far], -1), axis=-1), precision)
        return (jnp.einsum("hqk,khd->qhd", probs[..., :window], vw,
                           precision="highest")
                + jnp.einsum("hqc,chd->qhd", probs[..., window:], vt,
                             precision="highest"))

    out = jax.lax.map(jax.checkpoint(one), (
        q.reshape(s // block, block, heads, d),
        jnp.arange(s).reshape(s // block, block)))
    return _dense(out.reshape(s, h), w["attn_proj_weight"], precision)


def _position_blocks(s):
    block = min(POSITION_BLOCK, s)
    return block if s % block == 0 else s


def feed_forward(w, m, precision):
    """``W_down(silu(W_gate m) * W_up m)``, a block of positions at a
    time."""
    def one(mb):
        up = jax.nn.silu(_dense(mb, w["ffn_gate_weight"], precision)) \
            * _dense(mb, w["ffn_up_weight"], precision)
        return _dense(up, w["ffn_down_weight"], precision)

    s, block = m.shape[0], _position_blocks(m.shape[0])
    return jax.lax.map(jax.checkpoint(one),
                       m.reshape(s // block, block, -1)).reshape(m.shape)


def layer(w, x, cfg, precision="float32", fault=None):
    """One decoder layer on ONE sequence ``x (S, hidden)``; ``w`` holds the
    layer's leaves under their short names."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(w, rms_norm1(x, w["attn_norm_offset"], eps), cfg,
                      precision, fault)
    return x + feed_forward(w, rms_norm1(x, w["ffn_norm_offset"], eps),
                            precision)


def head_loss(w, x, labels, cfg, precision="float32", fault=None):
    """The loss of rows ``x (B, S, hidden)`` (the last layer's output)
    against ``labels (B, S, heads)``: final norm, then the head a block of
    positions at a time. ``w`` holds ``norm_offset`` and ``head_weight``."""
    heads, vocab = cfg["num_pred_heads"], cfg["vocab_size"]
    flat = x.reshape(-1, x.shape[-1])
    lab = labels.reshape(-1, heads)
    counts = jnp.maximum((lab >= 0).sum(0), 1).astype(jnp.float32)

    def one(args):
        xb, lb = args
        nb = rms_norm1(xb, w["norm_offset"], cfg["rms_norm_eps"])
        logits = _dense(nb, w["head_weight"], precision)
        logp = jax.nn.log_softmax(logits.reshape(-1, heads, vocab), axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[..., None],
                                 axis=-1)[..., 0]
        return -jnp.where(lb >= 0, ll, 0.0).sum(0)

    t, block = flat.shape[0], _position_blocks(flat.shape[0])
    nll = jax.lax.map(jax.checkpoint(one), (
        flat.reshape(t // block, block, -1),
        lab.reshape(t // block, block, heads))).sum(0) / counts
    if fault == "first_head_only":
        return nll[0]
    return nll.mean()


def layer_leaves(params, i):
    """Layer ``i``'s leaves of ``params`` under their short names."""
    prefix = "layer%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss(params, tokens, cfg, precision="float32", fault=None):
    """The whole model's loss on ``tokens (B, S)`` in one expression."""
    tokens = jnp.asarray(tokens)
    labels = jnp.asarray(make_labels(tokens, cfg["num_pred_heads"]))
    x = params["embed_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.vmap(lambda row, w=layer_leaves(params, i): layer(
            w, row, cfg, precision, fault))(x)
    return head_loss(params, x, labels, cfg, precision, fault)


# ---- a step, a layer at a time ---------------------------------------------

def _f32(leaves):
    return {k: v.astype(jnp.float32) for k, v in leaves.items()}


@partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_forward(w, x, cfg_items, precision, fault):
    cfg = dict(cfg_items)
    return jax.vmap(lambda row: layer(_f32(w), row, cfg, precision, fault))(x)


@partial(jax.jit, static_argnums=(3, 4, 5), donate_argnums=(2,))
def _layer_backward(w, x, dy, cfg_items, precision, fault):
    """``(dx, dw)`` of one layer, its forward run again."""
    cfg = dict(cfg_items)
    _, pull = jax.vjp(lambda w, x: jax.vmap(lambda row: layer(
        w, row, cfg, precision, fault))(x), _f32(w), x)
    dw, dx = pull(dy)
    return dx, dw


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head_backward(w, x, labels, cfg_items, precision, fault):
    cfg = dict(cfg_items)
    value, (dw, dx) = jax.value_and_grad(
        lambda w, x: head_loss(w, x, labels, cfg, precision, fault),
        argnums=(0, 1))(_f32(w), x)
    return value, dx, dw


@partial(jax.jit, static_argnums=(5, 6))
def _adam(w, m, v, g, t, hyper, storage):
    """Adam with bias correction folded into the rate, state and parameters
    rounded to the storage type once a step (``bert_ref._adam``)."""
    lr, b1, b2, eps = hyper
    g = g.astype(storage).astype(jnp.float32)
    w, m, v = (a.astype(jnp.float32) for a in (w, m, v))
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = w - lr_t * m / (jnp.sqrt(v) + eps)
    return w.astype(storage), m.astype(storage), v.astype(storage)


@jax.jit
def _norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))))


def cfg_items(cfg):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool))))


def loss_and_gradient(params, tokens, cfg, precision="float32", fault=None,
                      took=None):
    """``(loss, gradients)`` of one batch ``tokens (B, S)``, the model
    walked a layer at a time; ``took(name, gradient)`` is called with each
    leaf's float32 gradient as soon as it is whole (so the caller can update
    and drop it), and the gradients are returned only where it is None."""
    items, layers = cfg_items(cfg), cfg["num_hidden_layers"]
    kept = {} if took is None else None
    took = took or kept.__setitem__
    tokens = jnp.asarray(tokens)
    labels = jnp.asarray(make_labels(tokens, cfg["num_pred_heads"]))
    x = params["embed_weight"].astype(jnp.float32)[tokens]
    inputs = []
    for i in range(layers):
        inputs.append(np.asarray(x))            # waits on the host
        x = _layer_forward(layer_leaves(params, i), x, items, precision,
                           fault)
    top = {k: params[k] for k in ("norm_offset", "head_weight")}
    value, dx, dw = _head_backward(top, x, labels, items, precision, fault)
    for k, g in dw.items():
        took(k, g)
    for i in reversed(range(layers)):
        dx, dw = _layer_backward(layer_leaves(params, i),
                                 jnp.asarray(inputs.pop()), dx, items,
                                 precision, fault)
        for k in list(dw):
            took("layer%d_%s" % (i, k), dw.pop(k))
    took("embed_weight", jnp.zeros(params["embed_weight"].shape, jnp.float32)
         .at[tokens.reshape(-1)].add(dx.reshape(-1, dx.shape[-1])))
    return float(value), kept


def run_steps(cfg, cell, seed, steps, precision="float32", fault=None):
    """Follow the first ``steps`` training steps from the seed; returns what
    ``bert_ref.run_steps`` returns.

    ``precision`` other than ``float32`` is the control (matmul operands
    rounded to it); ``fault`` plants one of :data:`FAULTS`. The start of
    every parameter, the first gradient and both moments go to the HOST: the
    device holds the stored parameters and one layer's work."""
    storage = cfg["param_dtype"]
    opt = cfg["optimizer"]
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"])
    params = make_params(cfg, seed, storage)
    start = {k: np.asarray(v) for k, v in params.items()}
    ms = {k: np.zeros_like(v) for k, v in start.items()}
    vs = {k: np.zeros_like(v) for k, v in start.items()}
    batches = make_batches(cfg, cell["batch"], cell["seq"], cell["pool"], seed)
    losses, first_gradient = [], {}
    for t in range(1, steps + 1):
        def took(k, g, t=t):
            if t == 1:      # as the optimizer gets it: in the storage type
                first_gradient[k] = np.asarray(g.astype(storage))
            w, m, v = _adam(params[k], ms[k], vs[k], g, t, hyper, storage)
            params[k], ms[k], vs[k] = w, np.asarray(m), np.asarray(v)

        value, _ = loss_and_gradient(
            params, batches[(t - 1) % len(batches)]["tokens"], cfg,
            precision, fault, took)
        losses.append(value)
    grad_norms = {k: float(np.sqrt(np.sum(np.square(g.astype(np.float32)))))
                  for k, g in first_gradient.items()}
    change = {k: float(_norm(params[k], start[k])) for k in params}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "first_gradient": first_gradient}
