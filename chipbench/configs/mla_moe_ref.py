"""Plain reference of a latent-attention (MLA) decoder with held sparse
experts, as one chip of an expert- and vocabulary-parallel deployment holds
it: forward, next-token loss, gradients and Adam in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision, no kernel, no sort.
It imports nothing of the program and takes nothing the program has made:
weights and batches come from the seed.

The layer, after DeepSeek-V2/V3 as Kimi-VL-A3B's ``config.json`` sets it.
With n = RMSNorm(x): ``q = W_q n`` (heads of ``[q_nope | q_rope]``);
``[c | k_rope] = W_kva n`` (ONE rotary key a position, shared by the
heads); ``[k_nope_h | v_h] = W_kvb RMSNorm(c)``; rotary on every head's
``q_rope`` and on ``k_rope``; ``score_h = (q_nope_h . k_nope_h + q_rope_h .
k_rope) / sqrt(nope + rope)``, causal softmax, ``h = x + W_o concat_h(P_h
v_h)``. With m = RMSNorm(h): ``s = sigmoid(W_g m)`` over ALL routed experts;
the ``num_experts_per_tok`` largest of ``s + b`` are chosen (b chooses and
does not weigh); ``w_i = routed_scaling_factor * s_i / sum_chosen s_j``;
``out = h + sum_i w_i E_i(m) + Shared(m)``, ``E(m) = W_down(silu(W_gate m)
* W_up m)``. The first ``first_k_dense_replace`` layers have a dense gated
feed-forward in place of the experts. Final RMSNorm, untied head,
cross-entropy on the next token.

The share (the configuration's ``deployment``): the router is as wide as
published (``router_width``); of the experts only ``n_routed_experts``
from ``experts_held_first`` on are held, and what the others would have
added is left out (it is the other chips' to add); the vocabulary is the
slice the configuration states. Departures from the published code, all
listed under ``assumed`` in the configuration: rotary pairs the two halves
of the 64 (the published code permutes interleaved pairs into halves first,
which random weights make immaterial); no auxiliary loss term; the router's
correction bias is drawn from the seed and takes no gradient.

Parameters and Adam state are held in the stated storage type (rounded once
a step, as the storage does) and everything between is float32, as
``bert_ref`` does and for the same reason.
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from chipbench.weights import make_weights

QUERY_BLOCK = 512       # queries per block of the reference's attention
BIAS_SCALE = 0.5        # router bias N(0, 0.01) = half the N(0, 0.02) leaf
FAULTS = ("expert_dropped", "no_rope_on_shared_key")


def dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def param_spec(cfg):
    """``{name: (shape, kind)}``; names are the program's, without the
    model's own prefix. Expert matrices are stored (expert, in, out)."""
    h, heads, dn, dr, dv, rank = dims(cfg)
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held, width, v = (cfg["n_routed_experts"], cfg["router_width"],
                      cfg["vocab_size"])
    spec = {"embed_weight": ((v, h), "normal")}
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        spec[p + "attn_norm_gamma"] = ((h,), "ones")
        spec[p + "attn_q_weight"] = ((heads * (dn + dr), h), "normal")
        spec[p + "attn_kva_weight"] = ((rank + dr, h), "normal")
        spec[p + "attn_kv_norm_gamma"] = ((rank,), "ones")
        spec[p + "attn_kvb_weight"] = ((heads * (dn + dv), rank), "normal")
        spec[p + "attn_proj_weight"] = ((h, heads * dv), "normal")
        spec[p + "ffn_norm_gamma"] = ((h,), "ones")
        if i < cfg["first_k_dense_replace"]:
            spec[p + "ffn_gate_weight"] = ((ff, h), "normal")
            spec[p + "ffn_up_weight"] = ((ff, h), "normal")
            spec[p + "ffn_down_weight"] = ((h, ff), "normal")
        else:
            spec[p + "moe_router_weight"] = ((width, h), "normal")
            spec[p + "moe_router_bias"] = ((width,), "normal")
            spec[p + "moe_expert_gate_weight"] = ((held, h, fe), "normal")
            spec[p + "moe_expert_up_weight"] = ((held, h, fe), "normal")
            spec[p + "moe_expert_down_weight"] = ((held, fe, h), "normal")
            spec[p + "moe_shared_gate_weight"] = ((fs, h), "normal")
            spec[p + "moe_shared_up_weight"] = ((fs, h), "normal")
            spec[p + "moe_shared_down_weight"] = ((h, fs), "normal")
    spec["norm_gamma"] = ((h,), "ones")
    spec["head_weight"] = ((v, h), "normal")
    return spec


def takes_gradient(name):
    """The router's correction bias chooses and does not weigh: no
    gradient reaches it."""
    return not name.endswith("router_bias")


@partial(jax.jit, static_argnums=(1,))
def _halve_bias(weights, dtype):
    return {k: (BIAS_SCALE * v.astype(jnp.float32)).astype(dtype)
            if not takes_gradient(k) else v for k, v in weights.items()}


def make_params(cfg, seed, dtype):
    """The seed's weights in ``dtype``: N(0, 0.02) matrices, gains of one,
    router bias N(0, 0.01) (published: learnt, zero at the start; drawn here
    so that choosing by ``s + b`` differs from choosing by ``s``)."""
    return _halve_bias(make_weights(param_spec(cfg), seed, dtype), dtype)


def make_batches(cfg, batch, seq, pool, seed):
    """``pool`` host batches of token ids, uniform over the vocabulary
    slice, from the seed."""
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, cfg["vocab_size"], (batch, seq))}
            for _ in range(pool)]


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


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gamma


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


def gated(x, w_gate, w_up, w_down, precision):
    up = jax.nn.silu(_dense(x, w_gate, precision)) * _dense(x, w_up, precision)
    return _dense(up, w_down, precision)


def attention(p, prefix, n, cfg, precision, fault=None):
    """Latent attention of ONE sequence ``n (S, hidden)`` (already normed),
    the scores computed a block of queries at a time."""
    h, heads, dn, dr, dv, rank = dims(cfg)
    s = n.shape[0]
    q = _dense(n, p[prefix + "attn_q_weight"], precision)
    q = q.reshape(s, heads, dn + dr)
    kva = _dense(n, p[prefix + "attn_kva_weight"], precision)
    c, k_rope = kva[:, :rank], kva[:, rank:]
    kv = _dense(rms_norm(c, p[prefix + "attn_kv_norm_gamma"],
                         cfg["rms_norm_eps"]),
                p[prefix + "attn_kvb_weight"], precision)
    kv = kv.reshape(s, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], cfg["rope_theta"])],
                        -1)
    if fault != "no_rope_on_shared_key":
        k_rope = rotary(k_rope, cfg["rope_theta"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (s, heads, dr))],
        -1)
    q, k, v = (_round_to(a, precision) for a in (q, k, kv[..., dn:]))
    scale = 1.0 / float(np.sqrt(dn + dr))
    block = min(QUERY_BLOCK, s)
    if s % block:
        block = s
    key_pos = jnp.arange(s)

    def one(args):
        qb, pos = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest")
        scores = jnp.where(pos[None, :, None] >= key_pos[None, None, :],
                           scores * scale, -1e30)
        probs = _round_to(jax.nn.softmax(scores, axis=-1), precision)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision="highest")

    out = jax.lax.map(jax.checkpoint(one), (
        q.reshape(s // block, block, heads, dn + dr),
        key_pos.reshape(s // block, block)))
    return _dense(out.reshape(s, heads * dv), p[prefix + "attn_proj_weight"],
                  precision)


def route(m, w_router, bias, cfg):
    """``(chosen (T, k) expert ids, weights (T, k))`` over ALL the router's
    experts; float32 throughout, whatever the control's precision (the
    published code routes in float32)."""
    scores = jax.nn.sigmoid(jnp.matmul(m, w_router.T, precision="highest"))
    _, chosen = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, picked * cfg["routed_scaling_factor"]


def routed_part(p, prefix, m, cfg, first, precision, fault=None):
    """What the experts held (``first`` on, as many as the stored matrices
    have) add for tokens ``m (T, hidden)``: every token through every held
    expert, kept where the router chose it. Returns ``(y, rows)`` with
    ``rows`` the tokens routed to each held expert."""
    chosen, weights = route(m, p[prefix + "moe_router_weight"],
                            p[prefix + "moe_router_bias"], cfg)
    w_gate, w_up, w_down = (p[prefix + "moe_expert_%s_weight" % n]
                            for n in ("gate", "up", "down"))
    held = w_gate.shape[0]
    dropped = held // 2 if fault == "expert_dropped" else -1

    def one(y, expert):
        e, gate, up, down = expert
        mine = chosen == first + e
        weight = jnp.where(mine, weights, 0.0).sum(-1)
        weight = jnp.where(e == dropped, 0.0, weight)
        out = gated(m, gate.T, up.T, down.T, precision)
        return y + weight[:, None] * out, mine.sum()

    # a loop over the held experts (one compiled body, not one an expert)
    return jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(m),
                        (jnp.arange(held), w_gate, w_up, w_down))


def shared_part(p, prefix, m, precision):
    return gated(m, p[prefix + "moe_shared_gate_weight"],
                 p[prefix + "moe_shared_up_weight"],
                 p[prefix + "moe_shared_down_weight"], precision)


def hidden_states(p, tokens, cfg, precision="float32", fault=None):
    """Final-normed hidden states of ONE sequence of token ids, each layer
    recomputed in the backward pass (``jax.checkpoint``)."""
    eps, first = cfg["rms_norm_eps"], cfg.get("experts_held_first", 0)
    x = p["embed_weight"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        prefix = "layer%d_" % i

        def layer(x, w, prefix=prefix, dense=i < cfg["first_k_dense_replace"]):
            x = x + attention(w, prefix, rms_norm(
                x, w[prefix + "attn_norm_gamma"], eps), cfg, precision, fault)
            m = rms_norm(x, w[prefix + "ffn_norm_gamma"], eps)
            if dense:
                return x + gated(m, w[prefix + "ffn_gate_weight"],
                                 w[prefix + "ffn_up_weight"],
                                 w[prefix + "ffn_down_weight"], precision)
            y, _ = routed_part(w, prefix, m, cfg, first, precision, fault)
            return x + y + shared_part(w, prefix, m, precision)

        mine = {k: v for k, v in p.items() if k.startswith(prefix)}
        x = jax.checkpoint(layer)(x, mine)
    return rms_norm(x, p["norm_gamma"], eps)


def row_loss(p, tokens, cfg, total_targets, precision="float32", fault=None):
    """This sequence's share of the batch loss: the summed negative
    log-likelihood of token t + 1 at position t over the batch's targets."""
    x = hidden_states(p, tokens, cfg, precision, fault)[:-1]
    logits = _dense(x, p["head_weight"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[1:, None], axis=-1)[:, 0]
    return -ll.sum() / total_targets


@partial(jax.jit, static_argnums=(3, 5, 6), donate_argnums=(2,))
def _row_grad(params, tokens, acc, cfg_items, total_targets, precision, fault):
    """Loss and gradient of one sequence, the gradient ADDED to ``acc``
    (donated: the sum is kept once). What is not trained rides as a
    constant."""
    cfg = dict(cfg_items)
    f32 = {k: v.astype(jnp.float32) for k, v in params.items()}
    trained = {k: v for k, v in f32.items() if takes_gradient(k)}
    fixed = {k: v for k, v in f32.items() if not takes_gradient(k)}
    loss, grads = jax.value_and_grad(
        lambda t: row_loss(dict(t, **fixed), tokens, cfg, total_targets,
                           precision, fault))(trained)
    return loss, {k: acc[k] + g for k, g in grads.items()}


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


def run_steps(cfg, cell, seed, steps, precision="float32", rows_used=None,
              fault=None):
    """Follow the first ``steps`` training steps from the seed; returns what
    ``bert_ref.run_steps`` returns, over the trained leaves.

    ``precision`` other than ``float32`` is the control (matmul operands
    rounded to it; the router stays float32). ``fault`` plants one of
    :data:`FAULTS`; ``rows_used`` plants "part of the batch left out". The
    start of every parameter and the first gradient go to the HOST, so the
    device holds the parameters, two moments and one float32 gradient."""
    batch, seq = cell["batch"], cell["seq"]
    storage = cfg["param_dtype"]
    opt = cfg["optimizer"]
    hyper = (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"])
    params = make_params(cfg, seed, storage)
    trained = [k for k in params if takes_gradient(k)]
    start = jax.device_get({k: params[k] for k in trained})
    ms = {k: jnp.zeros_like(params[k]) for k in trained}
    vs = {k: jnp.zeros_like(params[k]) for k in trained}
    batches = make_batches(cfg, batch, seq, cell["pool"], seed)
    items = cfg_items(cfg)
    losses, grad_norms, first_gradient = [], None, None
    for t in range(1, steps + 1):
        n = rows_used or batch
        rows = batches[(t - 1) % len(batches)]["tokens"][:n]
        targets = float(n * (seq - 1))
        loss = 0.0
        grads = {k: jnp.zeros(params[k].shape, jnp.float32) for k in trained}
        for r in range(n):
            l, grads = _row_grad(params, jnp.asarray(rows[r]), grads, items,
                                 targets, precision, fault)
            loss += float(l)
        losses.append(loss)
        if t == 1:      # as the optimizer gets it: in the storage type
            first_gradient = {k: np.asarray(g.astype(storage))
                              for k, g in grads.items()}
            grad_norms = {k: float(np.sqrt(np.sum(np.square(
                g.astype(np.float32))))) for k, g in first_gradient.items()}
        for k in trained:
            params[k], ms[k], vs[k] = _adam(params[k], ms[k], vs[k],
                                            grads.pop(k), t, hyper, storage)
    change = {k: float(_norm(params[k], start[k])) for k in trained}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change,
            "first_gradient": first_gradient}
