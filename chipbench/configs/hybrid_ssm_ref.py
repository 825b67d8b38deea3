"""Plain reference of a hybrid decoder whose layers are Mamba-2 state-space
mixers with a softmax-attention mixer now and then, as one pipeline stage
holds it: forward, loss, gradients and Adam in straightforward ``jax.numpy``,
float32 at ``highest`` matmul precision, no kernel. It imports nothing of the
program and takes nothing the program has made: weights and batches come from
the seed.

The model, after Granite 4.0-H's ``config.json`` (``model_type``
``granitemoehybrid`` with no experts) and Dao and Gu, "Transformers are SSMs"
(ICML 2024). With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g and r =
``residual_multiplier``, every layer is ``x += r * mixer(RMSNorm(x))`` then
``x += r * W_down(silu(W_gate m) * W_up m)``, m = RMSNorm(x). ``layer_types``
says which mixer a layer has.

``mamba``: ``[z | xBC | dt] = n W_in`` (inner + (inner + 2 N) + H columns);
``xBC = silu(conv(xBC))``, a causal depthwise convolution of ``mamba_d_conv``
taps with a bias (tap K - 1 weighs the position itself; nothing before the
sequence); ``xBC -> x (H heads of P), B (N), C (N)``, one group: B and C are
shared by the heads. ``Delta[t, h] = softplus(dt[t, h] + dt_bias[h])``,
``A[h] = -exp(A_log[h])``. The state of head h, (P, N):
``h_t = exp(Delta_t A) h_{t-1} + Delta_t x_t B_t^T``, from nought; ``y_t =
h_t C_t + D x_t``. Then ``y = RMSNorm(y * silu(z))`` over all the inner
columns with a gain, and ``out = y W_out``. The state is written HERE as that
recurrence, a position at a time (a ``scan`` over positions inside a
checkpointed ``scan`` over blocks of them), never as the chunked algebra the
program runs.

``attention``: ``num_attention_heads`` query heads and ``num_key_value_heads``
key-value heads of ``head_dim``, query head j reading key-value head
``j // (heads / kv heads)``, no bias, no positions, scores times
``attention_multiplier``, causal softmax, a block of queries at a time.

The embedding is multiplied by ``embedding_multiplier``; after the last layer
RMSNorm, and the head IS the embedding (tied): logits = n E^T /
``logits_scaling``; the loss is the mean next-token cross-entropy over the
positions that have a next token.

Departures from the published description, each noted in the configuration's
file too: the feed-forward's one (hidden, 2 x intermediate) input matrix is
held as its two halves ``gate`` and ``up`` (the same product); ``head_dim`` is
hidden / heads; no ``time_step_limit`` clamps Delta; the initializers are this
file's (``make_params``), not the published weights.

Parameters and Adam state are held in the stated storage type (rounded once
a step, as the storage does) and everything between is float32. A step is
walked a LAYER at a time, as ``eva_lm_ref`` walks its own, so the device
holds the stored parameters, one layer's float32 copy and gradient and one
layer's activations whatever the depth. ``loss`` is the same model in one
expression, which the tests differentiate whole.
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import weights as seeded

QUERY_BLOCK = 256       # queries per block of the reference's attention
POSITION_BLOCK = 2048   # positions per block of the feed-forward and head
SCAN_BLOCK = 256        # positions per checkpointed block of the recurrence
FAULTS = ("state_not_carried", "conv_tap_dropped", "skip_dropped",
          "residual_one", "kv_heads_misgrouped")


def dims(cfg):
    """``(hidden, inner, H, P, N, K)`` of the state-space mixer."""
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if cfg.get("mamba_n_groups", 1) != 1:
        raise ValueError("one group of B and C only")
    return (cfg["hidden_size"], heads * p, heads, p, cfg["mamba_d_state"],
            cfg["mamba_d_conv"])


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_spec(cfg, kind):
    """``{short name: (shape, kind of draw)}`` of ONE layer's leaves."""
    h, inner, heads, _, n, taps = dims(cfg)
    ff = cfg["shared_intermediate_size"]
    spec = {"mixer_norm_gamma": ((h,), "ones")}
    if kind == "mamba":
        conv = inner + 2 * n
        spec.update({
            "mixer_in_weight": ((inner + conv + heads, h), "normal"),
            "mixer_conv_weight": ((conv, taps), "uniform_half"),
            "mixer_conv_bias": ((conv,), "uniform_half"),
            "mixer_dt_bias": ((heads,), "dt_bias"),
            "mixer_A_log": ((heads,), "a_log"),
            "mixer_D": ((heads,), "ones"),
            "mixer_gate_gamma": ((inner,), "ones"),
            "mixer_out_weight": ((h, inner), "normal")})
    elif kind == "attention":
        q = cfg["num_attention_heads"] * head_dim(cfg)
        kv = cfg["num_key_value_heads"] * head_dim(cfg)
        spec.update({"mixer_q_weight": ((q, h), "normal"),
                     "mixer_k_weight": ((kv, h), "normal"),
                     "mixer_v_weight": ((kv, h), "normal"),
                     "mixer_proj_weight": ((h, q), "normal")})
    else:
        raise ValueError("unknown layer type %r" % kind)
    spec.update({"ffn_norm_gamma": ((h,), "ones"),
                 "ffn_gate_weight": ((ff, h), "normal"),
                 "ffn_up_weight": ((ff, h), "normal"),
                 "ffn_down_weight": ((h, ff), "normal")})
    return spec


def layer_types(cfg):
    kinds = list(cfg["layer_types"])
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers %d"
                         % (len(kinds), cfg["num_hidden_layers"]))
    return kinds


def param_spec(cfg):
    """``{name: (shape, kind)}``; names are the program's, without the
    model's own prefix. The embedding is also the head."""
    h = cfg["hidden_size"]
    spec = {"embed_weight": ((cfg["vocab_size"], h), "normal")}
    for i, kind in enumerate(layer_types(cfg)):
        for k, v in layer_spec(cfg, kind).items():
            spec["layer%d_%s" % (i, k)] = v
    spec["norm_gamma"] = ((h,), "ones")
    return spec


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec, dtype):
    out = {}
    for i, (name, shape, kind) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if kind == "normal":
            leaf = seeded.INIT_STD * jax.random.normal(k, shape, jnp.float32)
        elif kind == "ones":
            leaf = jnp.ones(shape, jnp.float32)
        elif kind == "uniform_half":    # a 4-tap depthwise filter's default
            leaf = jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
        elif kind == "a_log":           # A = -uniform(1, 16)
            leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1., 16.))
        elif kind == "dt_bias":     # softplus(dt_bias) log-uniform in [1e-3, 0.1]
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(0.1)))
            leaf = step + jnp.log(-jnp.expm1(-step))
        else:
            raise ValueError("unknown leaf kind %r for %s" % (kind, name))
        out[name] = leaf.astype(dtype)
    return out


def make_params(cfg, seed, dtype):
    """The seed's weights in ``dtype``: N(0, 0.02) for every matrix,
    ``A_log = log(uniform(1, 16))``, ``dt_bias`` the inverse softplus of a
    log-uniform step in [0.001, 0.1], ``D`` and the gains one, the
    convolution's taps and bias uniform(-1/2, 1/2)."""
    flat = tuple((name, tuple(shape), kind)
                 for name, (shape, kind) in param_spec(cfg).items())
    return _make(seeded.seed_key(seed), flat, dtype)


def make_batches(cfg, batch, seq, pool, seed):
    """``pool`` host batches of token ids, uniform over the vocabulary."""
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg["vocab_size"], (batch, seq))}
            for _ in range(pool)]


def make_labels(tokens):
    """The label of position t is token t + 1, and -1 at a row's last."""
    tokens = np.asarray(tokens)
    return np.concatenate(
        [tokens[:, 1:], np.full((len(tokens), 1), -1, tokens.dtype)], axis=1)


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


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def causal_conv(x, taps, bias, fault=None):
    """``out[t, c] = bias[c] + sum_k taps[c, k] x[t - (K - 1) + k, c]`` for
    ``x (S, channels)``, nothing before the sequence."""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x], 0)
    out = bias
    for j in range(k):
        if fault == "conv_tap_dropped" and j == 0:
            continue
        out = out + taps[:, j] * padded[j:j + s]
    return out


def state_recurrence(x, delta, a, b, c, chunk, fault=None):
    """``y (S, H, P)`` of the recurrence ``h_t = exp(delta_t a) h_{t-1} +
    delta_t x_t b_t^T``, ``y_t = h_t c_t`` for ``x (S, H, P)``, ``delta
    (S, H)``, ``a (H,)``, ``b`` and ``c (S, N)``: a position at a time, a
    block of ``SCAN_BLOCK`` positions checkpointed. ``chunk`` is only where
    the planted fault drops the state."""
    s, heads, p = x.shape
    n = b.shape[-1]
    block = min(SCAN_BLOCK, s)
    if s % block:
        block = s

    def position(h, args):
        xt, dt, bt, ct, fresh = args
        if fault == "state_not_carried":
            h = jnp.where(fresh, 0.0, h)
        h = jnp.exp(dt * a)[:, None, None] * h \
            + (dt[:, None] * xt)[:, :, None] * bt[None, None, :]
        return h, jnp.sum(h * ct[None, None, :], axis=-1)

    @jax.checkpoint
    def positions(h, args):
        return jax.lax.scan(position, h, args)

    # where the program's chunks begin (the fault drops the state there)
    fresh = (jnp.arange(s) % chunk) == 0
    _, y = jax.lax.scan(positions, jnp.zeros((heads, p, n), jnp.float32), (
        x.reshape(s // block, block, heads, p),
        delta.reshape(s // block, block, heads),
        b.reshape(s // block, block, n), c.reshape(s // block, block, n),
        fresh.reshape(s // block, block)))
    return y.reshape(s, heads, p)


def mamba_mixer(w, n, cfg, precision, fault=None):
    """The state-space mixer of ONE sequence ``n (S, hidden)`` (already
    normed)."""
    _, inner, heads, p, states, _ = dims(cfg)
    s = n.shape[0]
    zxbcdt = _dense(n, w["mixer_in_weight"], precision)
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:2 * inner + 2 * states]
    dt = zxbcdt[:, 2 * inner + 2 * states:]
    xbc = jax.nn.silu(causal_conv(xbc, w["mixer_conv_weight"],
                                  w["mixer_conv_bias"], fault))
    x = _round_to(xbc[:, :inner], precision).reshape(s, heads, p)
    b = _round_to(xbc[:, inner:inner + states], precision)
    c = _round_to(xbc[:, inner + states:], precision)
    delta = jax.nn.softplus(dt + w["mixer_dt_bias"])
    y = state_recurrence(x, delta, -jnp.exp(w["mixer_A_log"]), b, c,
                         cfg["mamba_chunk_size"], fault)
    if fault != "skip_dropped":
        y = y + w["mixer_D"][None, :, None] * x
    y = rms_norm(y.reshape(s, inner) * jax.nn.silu(z), w["mixer_gate_gamma"],
                 cfg["rms_norm_eps"])
    return _dense(y, w["mixer_out_weight"], precision)


def attention_mixer(w, n, cfg, precision, fault=None):
    """Grouped-query causal attention of ONE sequence ``n (S, hidden)``
    (already normed), no positions, a block of queries at a time."""
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], head_dim(cfg))
    s = n.shape[0]
    q = _dense(n, w["mixer_q_weight"], precision).reshape(s, heads, d)
    k = _dense(n, w["mixer_k_weight"], precision).reshape(s, kv_heads, d)
    v = _dense(n, w["mixer_v_weight"], precision).reshape(s, kv_heads, d)
    group = heads // kv_heads
    reads = jnp.arange(heads) % kv_heads if fault == "kv_heads_misgrouped" \
        else jnp.arange(heads) // group
    q, k, v = (_round_to(a, precision) for a in (q, k[:, reads], v[:, reads]))
    block = min(QUERY_BLOCK, s)
    if s % block:
        block = s
    key_pos = jnp.arange(s)

    def one(args):
        qb, pos = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest") \
            * cfg["attention_multiplier"]
        scores = jnp.where(pos[None, :, None] >= key_pos[None, None, :],
                           scores, -1e30)
        probs = _round_to(jax.nn.softmax(scores, axis=-1), precision)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")

    out = jax.lax.map(jax.checkpoint(one), (
        q.reshape(s // block, block, heads, d),
        jnp.arange(s).reshape(s // block, block)))
    return _dense(out.reshape(s, heads * d), w["mixer_proj_weight"],
                  precision)


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


def layer(w, x, cfg, kind, precision="float32", fault=None):
    """One decoder layer of type ``kind`` on ONE sequence ``x (S, hidden)``;
    ``w`` holds the layer's leaves under their short names."""
    eps = cfg["rms_norm_eps"]
    r = 1.0 if fault == "residual_one" else cfg["residual_multiplier"]
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    x = x + r * mixer(w, rms_norm(x, w["mixer_norm_gamma"], eps), cfg,
                      precision, fault)
    return x + r * feed_forward(w, rms_norm(x, w["ffn_norm_gamma"], eps),
                                precision)


def head_loss(w, x, labels, cfg, precision="float32"):
    """The loss of rows ``x (B, S, hidden)`` (the last layer's output)
    against ``labels (B, S)``: final norm, then the tied head a block of
    positions at a time. ``w`` holds ``norm_gamma`` and ``embed_weight``."""
    flat = x.reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)
    count = jnp.maximum((lab >= 0).sum(), 1).astype(jnp.float32)

    def one(args):
        xb, lb = args
        nb = rms_norm(xb, w["norm_gamma"], cfg["rms_norm_eps"])
        logits = _dense(nb, w["embed_weight"], precision) \
            / cfg["logits_scaling"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(lb, 0)[:, None],
                                 axis=-1)[:, 0]
        return -jnp.where(lb >= 0, ll, 0.0).sum()

    t, block = flat.shape[0], _position_blocks(flat.shape[0])
    return jax.lax.map(jax.checkpoint(one), (
        flat.reshape(t // block, block, -1),
        lab.reshape(t // block, block))).sum() / count


def layer_leaves(params, i):
    """Layer ``i``'s leaves of ``params`` under their short names."""
    prefix = "layer%d_" % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def embed(params, tokens, cfg):
    return cfg["embedding_multiplier"] * params["embed_weight"][tokens]


def loss(params, tokens, cfg, precision="float32", fault=None):
    """The whole model's loss on ``tokens (B, S)`` in one expression."""
    tokens = jnp.asarray(tokens)
    labels = jnp.asarray(make_labels(tokens))
    x = embed(params, tokens, cfg)
    for i, kind in enumerate(layer_types(cfg)):
        x = jax.vmap(lambda row, w=layer_leaves(params, i), kind=kind: layer(
            w, row, cfg, kind, precision, fault))(x)
    return head_loss(params, x, labels, cfg, precision)


# ---- a step, a layer at a time ---------------------------------------------

def _f32(leaves):
    return {k: v.astype(jnp.float32) for k, v in leaves.items()}


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_forward(w, x, cfg_items, kind, precision, fault):
    cfg = dict(cfg_items)
    return jax.vmap(lambda row: layer(_f32(w), row, cfg, kind, precision,
                                      fault))(x)


@partial(jax.jit, static_argnums=(3, 4, 5, 6), donate_argnums=(2,))
def _layer_backward(w, x, dy, cfg_items, kind, precision, fault):
    """``(dx, dw)`` of one layer, its forward run again."""
    cfg = dict(cfg_items)
    _, pull = jax.vjp(lambda w, x: jax.vmap(lambda row: layer(
        w, row, cfg, kind, precision, fault))(x), _f32(w), x)
    dw, dx = pull(dy)
    return dx, dw


@partial(jax.jit, static_argnums=(3, 4))
def _head_backward(w, x, labels, cfg_items, precision):
    cfg = dict(cfg_items)
    value, (dw, dx) = jax.value_and_grad(
        lambda w, x: head_loss(w, x, labels, cfg, precision),
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
    and drop it), and the gradients are returned only where it is None. The
    embedding's gradient is the sum of the head's and the lookup's."""
    items, kinds = cfg_items(cfg), layer_types(cfg)
    kept = {} if took is None else None
    took = took or kept.__setitem__
    tokens = jnp.asarray(tokens)
    labels = jnp.asarray(make_labels(tokens))
    x = embed(_f32({"embed_weight": params["embed_weight"]}), tokens, cfg)
    inputs = []
    for i, kind in enumerate(kinds):
        inputs.append(np.asarray(x))            # waits on the host
        x = _layer_forward(layer_leaves(params, i), x, items, kind, precision,
                           fault)
    top = {k: params[k] for k in ("norm_gamma", "embed_weight")}
    value, dx, dw = _head_backward(top, x, labels, items, precision)
    took("norm_gamma", dw.pop("norm_gamma"))
    d_embed = dw.pop("embed_weight")
    for i in reversed(range(len(kinds))):
        dx, dw = _layer_backward(layer_leaves(params, i),
                                 jnp.asarray(inputs.pop()), dx, items,
                                 kinds[i], precision, fault)
        for k in list(dw):
            took("layer%d_%s" % (i, k), dw.pop(k))
    took("embed_weight", d_embed.at[tokens.reshape(-1)].add(
        cfg["embedding_multiplier"] * dx.reshape(-1, dx.shape[-1])))
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
