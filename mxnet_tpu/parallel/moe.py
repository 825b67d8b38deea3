"""Mixture-of-Experts: two layers for two purposes.

**The Switch top-1 oracle** (``moe_ffn``, ``moe_ffn_sharded``,
``init_moe_params``): softmax top-1 routing with a static capacity, experts
sharded over an ``ep`` mesh axis, token exchange by ``lax.all_to_all``
(dispatch einsums -> all_to_all -> expert FFN -> all_to_all back -> weighted
combine). Over-capacity tokens are dropped (their output is the zero
vector). It is the routing oracle of the planner's tests and of
``models.moe_transformer``; no model at a published width runs on it.

**The dropless held-expert layer for models** (``held_experts_ffn``,
``gated_ffn``, ``route_top_k``, ``grouped_matmul``): what one chip of an
expert-parallel deployment computes. The layer is told which experts it
holds (``first``, and as many as its stacked matrices have), routes over
ALL the router's experts (sigmoid scores, choice by score + correction
bias, weights normalised over the chosen and scaled, in float32), leaves
out the assignments to experts it does not hold, sorts the rest by expert
and runs a grouped matrix product over them: no capacity, no dropped token,
no (tokens, experts, capacity) tensor, static shapes. On one chip there is
no exchange and nothing stands in for the absent chips. It writes the
``moe_router`` and ``moe_experts`` scopes (``moe_shared`` around the shared
experts) into the traced program and returns the rows routed to each held
expert beside the result.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_sharded", "init_moe_params",
           "held_experts_ffn", "gated_ffn", "route_top_k", "grouped_matmul"]


def init_moe_params(rng, d_model, d_hidden, n_experts, dtype=np.float32):
    """(gate_w, w1, w2) with fan-in scaling."""
    r1, r2, r3 = (np.random.RandomState(rng + i) for i in range(3))
    gate = (r1.randn(d_model, n_experts) / np.sqrt(d_model)).astype(dtype)
    w1 = (r2.randn(n_experts, d_model, d_hidden) /
          np.sqrt(d_model)).astype(dtype)
    w2 = (r3.randn(n_experts, d_hidden, d_model) /
          np.sqrt(d_hidden)).astype(dtype)
    return jnp.asarray(gate), jnp.asarray(w1), jnp.asarray(w2)


def _route(x, gate_w, capacity):
    """Top-1 routing -> (combine (t, E, C), dispatch (t, E, C), aux_loss)."""
    T = x.shape[0]
    E = gate_w.shape[1]
    logits = x @ gate_w                              # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # (T,)
    gate = jnp.max(probs, axis=-1)                   # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=x.dtype)            # (T, E)
    # Switch load-balancing loss: E * sum_e fraction_e * mean_prob_e
    density = onehot.mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = E * jnp.sum(density * density_proxy)
    # position of each token within its expert (0-based), capacity mask
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot            # (T, E)
    pos_tok = jnp.sum(pos, axis=-1).astype(jnp.int32)            # (T,)
    keep = (pos_tok < capacity)
    pos_oh = jax.nn.one_hot(pos_tok, capacity, dtype=x.dtype)    # (T, C)
    dispatch = (onehot * keep[:, None])[:, :, None] * \
        pos_oh[:, None, :]                                       # (T, E, C)
    combine = dispatch * gate[:, None, None]
    return combine, dispatch, aux


def _expert_ffn(buf, w1, w2):
    """buf (E, C, d) through each expert's 2-layer FFN."""
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", buf, w1))
    return jnp.einsum("ech,ehd->ecd", h, w2)


def moe_ffn(x, gate_w, w1, w2, capacity_factor=1.25):
    """Single-device Switch FFN. x (..., T, d) -> same shape + aux loss."""
    lead = x.shape[:-2]
    T, D = x.shape[-2], x.shape[-1]
    xt = x.reshape(-1, D)
    E = gate_w.shape[1]
    C = max(1, int(capacity_factor * xt.shape[0] / E))
    combine, dispatch, aux = _route(xt, gate_w, C)
    buf = jnp.einsum("tec,td->ecd", dispatch, xt)
    out = _expert_ffn(buf, w1, w2)
    y = jnp.einsum("tec,ecd->td", combine, out)
    return y.reshape(lead + (T, D)), aux


def moe_ffn_sharded(x, gate_w, w1, w2, mesh, capacity_factor=1.25,
                    axis="ep"):
    """Expert-parallel Switch FFN over mesh axis ``axis``.

    Tokens are sharded over ``axis`` (batch dim), experts are sharded over
    ``axis`` (dim 0 of w1/w2); the two all_to_alls exchange (expert, cap)
    dispatch buffers across the ring. Requires n_experts % ep == 0.
    """
    ep = mesh.shape[axis]
    E = gate_w.shape[1]
    assert E % ep == 0, "n_experts %d not divisible by ep=%d" % (E, ep)

    def local(xs, gw, w1s, w2s):
        # xs (t_local, d); w1s (E/ep, d, h)
        t_local, D = xs.shape
        C = max(1, int(capacity_factor * t_local / E))
        combine, dispatch, aux = _route(xs, gw, C)
        buf = jnp.einsum("tec,td->ecd", dispatch, xs)   # (E, C, d)
        # (E, C, d) -> (ep, E/ep, C, d): concat of per-destination blocks
        buf = buf.reshape(ep, E // ep, C, D)
        # exchange: device i sends block j to device j, receives its own
        # experts' tokens from everyone -> (ep, E/ep, C, d) recv layout
        buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                             tiled=False)
        # compute local experts on tokens from all ep peers
        out = jax.vmap(_expert_ffn, in_axes=(0, None, None))(buf, w1s, w2s)
        # send results back
        out = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                             tiled=False)
        out = out.reshape(E, C, D)
        y = jnp.einsum("tec,ecd->td", combine, out)
        return y, lax.pmean(aux, axis)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(axis), P(), P(axis), P(axis)),
                       out_specs=(P(axis), P()))
    lead = x.shape[:-1]
    y, aux = fn(x.reshape(-1, x.shape[-1]), gate_w, w1, w2)
    # a dead ep peer wedges the all_to_all exchange silently — bound the
    # wait (collective watchdog; free unless the deadline knob is armed)
    from ..resilience.elastic import guard_wait
    y, aux = guard_wait((y, aux), op="moe.dispatch")
    return y.reshape(lead + (x.shape[-1],)), aux


# ------------------------------------------------- dropless held experts

def route_top_k(x, router_w, router_b, top_k, scale=1.0, normalize=True):
    """``(chosen (T, k) int32, weights (T, k) float32)`` of tokens
    ``x (T, d)`` over all ``router_w (E, d)`` experts: sigmoid scores, the
    ``top_k`` largest of score + ``router_b`` chosen (the bias chooses and
    does not weigh, and takes no gradient), the chosen scores normalised to
    one and scaled. Float32 whatever ``x`` is stored in: in bfloat16 the
    last choice flips for tokens with near-tied scores."""
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router_w.astype(jnp.float32).T,
        precision="highest"))
    bias = lax.stop_gradient(router_b.astype(jnp.float32))
    _, chosen = lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), picked * scale


def _megablox_usable(lhs, rhs):
    """The Pallas grouped product takes the call where an accelerator is
    present, the sizes tile (128s) and the visible mesh is one device: GSPMD
    cannot partition a Mosaic kernel, ``lax.ragged_dot`` it can."""
    from .mesh import current_scope
    scope = current_scope()
    if scope is not None and scope[0].size > 1:
        return False
    return (any(d.platform != "cpu" for d in jax.devices())
            and lhs.shape[0] % 128 == 0 and lhs.shape[1] % 128 == 0
            and rhs.shape[2] % 128 == 0)


def _gmm_tiling(m, k, n):
    """Tile sizes (rows, contraction, columns) for the Pallas grouped
    product. The rows' tile must divide ``m``; the other two may leave a
    partial last tile (the kernel masks it), so a dimension up to 1408 (11
    x 128, no divisor between 128 and itself) is taken whole as columns."""
    tm = next(t for t in (512, 256, 128) if m % t == 0)
    return tm, min(k, 512), n if n <= 1408 else 1024


@jax.custom_vjp
def _pallas_gmm(lhs, rhs, group_sizes):
    """The Pallas grouped product (``jax.experimental``'s megablox
    kernels) with its gradient rule written here, so that the forward AND
    the backward kernels are traced with 64-bit types off: this framework
    turns ``jax_enable_x64`` on, and Mosaic has no 64-bit types."""
    return _gmm_call(lhs, rhs, group_sizes, False)


def _megablox():
    """The module of the grouped-product kernels (the package exports a
    function under the same name, ``gmm``)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_call(lhs, rhs, group_sizes, transpose_rhs):
    backend = _megablox()
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with jax.enable_x64(False):
        return backend.gmm(lhs, rhs, group_sizes, lhs.dtype,
                           _gmm_tiling(lhs.shape[0], lhs.shape[1], n),
                           transpose_rhs=transpose_rhs)


def _pallas_gmm_fwd(lhs, rhs, group_sizes):
    return _gmm_call(lhs, rhs, group_sizes, False), (lhs, rhs, group_sizes)


def _pallas_gmm_bwd(res, grad):
    backend = _megablox()
    lhs, rhs, group_sizes = res
    d_lhs = _gmm_call(grad, rhs, group_sizes, True)
    m, k, n = lhs.shape[0], lhs.shape[1], rhs.shape[2]
    with jax.enable_x64(False):
        d_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                             _gmm_tiling(m, k, n), None, rhs.shape[0])
    return d_lhs, d_rhs, None


_pallas_gmm.defvjp(_pallas_gmm_fwd, _pallas_gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group:
    ``lhs (M, K)``, ``rhs (G, K, N)``, ``group_sizes (G,)`` int32 whose sum
    may be less than M. Rows past the last group are NOT defined (mask
    them). The work follows the rows in the groups, not M."""
    group_sizes = group_sizes.astype(jnp.int32)
    if _megablox_usable(lhs, rhs):
        return _pallas_gmm(lhs, rhs, group_sizes)
    return lax.ragged_dot(lhs, rhs, group_sizes)


def gated_ffn(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)`` with matrices stored (out, in)."""
    up = jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)
    return up @ w_down.T


def _sorted_rows_ffn(rows, w_gate, w_up, w_down, live, sizes):
    """The held experts' gated feed-forward over rows sorted by expert:
    grouped gate/up, SiLU product, grouped down. Dead rows (past the
    groups) are zero going in and zero coming out, in both directions."""
    up = jax.nn.silu(grouped_matmul(rows, w_gate, sizes).astype(jnp.float32)) \
        * grouped_matmul(rows, w_up, sizes).astype(jnp.float32)
    up = jnp.where(live[:, None], up, 0).astype(rows.dtype)
    return jnp.where(live[:, None], grouped_matmul(up, w_down, sizes), 0)


def _held_rows(rows, at, held, k):
    """Every token's ``k``-th choice's row of the sorted buffer, float32,
    zero where that choice is not held: ``(T, d)``."""
    return jnp.where(held[:, k, None], rows[at[:, k]], 0).astype(jnp.float32)


def _tiers(tokens, top_k, held, experts):
    """Row counts the sorted buffers are compiled for: twice what a uniform
    router sends the held experts (``tokens * top_k * held / experts``,
    rounded up to the grouped product's 128-row tile), so that an uneven
    batch does not step over it, then doubling up to the worst case, every
    choice of every token held. The gathers and elementwise passes cost by
    the buffer's rows, so a router that drifts past one size pays for the
    next, not for the worst. One ``lax.switch`` picks by the rows there
    are."""
    worst = tokens * min(top_k, held)
    size = -(-2 * tokens * top_k * held // (experts * 128)) * 128
    tiers = []
    while size < worst:
        tiers.append(size)
        size *= 2
    return tuple(tiers) + (worst,)


def _pick_tier(routed, tiers, make, *operands):
    if len(tiers) == 1:
        return make(tiers[0])(*operands)
    index = jnp.sum(routed > jnp.asarray(tiers[:-1], jnp.int32))
    return lax.switch(index, [make(m) for m in tiers], *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed_experts(x, weights, w_gate, w_up, w_down, routing, top_k, tiers):
    """``sum_k weights[t, k] * E_chosen(t, k)(x[t])`` over the held choices,
    float32 ``(T, d)``. ``routing = (order, place, held, sizes)``: the
    assignments sorted by held expert (not held last), each assignment's
    place in that order, which are held, and the rows of each held expert.

    Gathers only, forward and backward (a scatter-add of rows is slow on
    the chip): the rows of the sorted buffer are gathered from ``x``; a
    token's result gathers its ``top_k`` rows back; the backward pass
    gathers the other way. Nothing is kept for the backward pass but the
    operands: it recomputes the sorted rows and their products."""
    return _routed_fwd(x, weights, w_gate, w_up, w_down, routing, top_k,
                       tiers)[0]


def _routed_fwd(x, weights, w_gate, w_up, w_down, routing, top_k, tiers):
    order, place, held, sizes = routing
    routed = jnp.sum(sizes)

    def sized(m):
        def run(x, weights, w_gate, w_up, w_down):
            live = jnp.arange(m, dtype=jnp.int32) < routed
            rows = jnp.where(live[:, None], x[order[:m] // top_k], 0)
            out = _sorted_rows_ffn(rows, w_gate, w_up, w_down, live, sizes)
            at = jnp.minimum(place, m - 1)
            # a choice at a time: (T, d) gathers, no (T, top_k, d) array
            return sum(_held_rows(out, at, held, k) * weights[:, k, None]
                       for k in range(top_k))
        return run

    y = _pick_tier(routed, tiers, sized, x, weights, w_gate, w_up, w_down)
    return y, (x, weights, w_gate, w_up, w_down, routing)


def _routed_bwd(top_k, tiers, res, dy):
    x, weights, w_gate, w_up, w_down, routing = res
    order, place, held, sizes = routing
    routed = jnp.sum(sizes)

    def sized(m):
        def run(x, weights, w_gate, w_up, w_down, dy):
            live = jnp.arange(m, dtype=jnp.int32) < routed
            first = order[:m]
            rows = jnp.where(live[:, None], x[first // top_k], 0)
            out, pull = jax.vjp(
                lambda r, g, u, w: _sorted_rows_ffn(r, g, u, w, live, sizes),
                rows, w_gate, w_up, w_down)
            # a sorted row's cotangent: its token's, times its weight
            gate = weights.reshape(-1)[first]
            d_out = jnp.where(live[:, None],
                              dy[first // top_k] * gate[:, None], 0)
            d_rows, d_gate, d_up, d_down = pull(d_out.astype(out.dtype))
            at = jnp.minimum(place, m - 1)
            d_x = sum(_held_rows(d_rows, at, held, k) for k in range(top_k))
            d_weights = jnp.stack(
                [jnp.sum(_held_rows(out, at, held, k) * dy, axis=-1)
                 for k in range(top_k)], axis=-1)
            return d_x.astype(x.dtype), d_weights, d_gate, d_up, d_down
        return run

    grads = _pick_tier(routed, tiers, sized, x, weights, w_gate, w_up,
                       w_down, dy)
    return grads + (None,)


_routed_experts.defvjp(_routed_fwd, _routed_bwd)


def held_experts_ffn(x, router_w, router_b, w_gate, w_up, w_down, first=0,
                     top_k=1, scale=1.0, normalize=True):
    """The routed part of an expert layer as the holder of experts
    ``first .. first + G`` computes it, for tokens ``x (T, d)``:
    ``w_gate`` / ``w_up (G, d, f)``, ``w_down (G, f, d)``. Returns
    ``(y (T, d), rows (G,) float32)``: the held experts' weighted sum for
    every token (zero for a token none of whose choices is held) and the
    rows routed to each held expert.

    Every assignment is computed, however skewed the routing (dropless):
    the buffers hold the worst case, ``T * min(top_k, G)`` rows; the
    grouped products work on the rows there are, and the gathers and
    elementwise passes run on the smallest buffer that holds them, of twice
    a uniform router's ``T * top_k * G / E`` rows, doubling (:func:`_tiers`)."""
    T, G = x.shape[0], w_gate.shape[0]
    with jax.named_scope("moe_router"):
        chosen, weights = route_top_k(x, router_w, router_b, top_k, scale,
                                      normalize)
    with jax.named_scope("moe_experts"):
        local = chosen - first
        held = (local >= 0) & (local < G)
        flat = jnp.where(held, local, G).reshape(-1)            # (T*k,)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        sizes = jnp.sum(flat[:, None] == jnp.arange(G, dtype=jnp.int32),
                        axis=0).astype(jnp.int32)
        place = jnp.zeros((T * top_k,), jnp.int32).at[order].set(
            jnp.arange(T * top_k, dtype=jnp.int32)).reshape(T, top_k)
        y = _routed_experts(x, jnp.where(held, weights, 0.0), w_gate, w_up,
                            w_down, (order, place, held, sizes), top_k,
                            _tiers(T, top_k, G, router_w.shape[0]))
        return y.astype(x.dtype), lax.stop_gradient(sizes.astype(jnp.float32))
