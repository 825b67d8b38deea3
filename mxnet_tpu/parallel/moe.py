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
no (tokens, experts, capacity) tensor, static shapes. Every pass between
the router and the grouped products, and between them and a token's result,
costs by the rows of the sorted buffer (at most twice the rows routed),
never by tokens x ``top_k``: the buffer's rows are gathered from the tokens,
and the combine is one gather of the buffer into token order and a sum over
each token's adjacent rows (:func:`_sum_by_token`). On one chip there is no
exchange and nothing stands in for the absent chips. It writes the
``moe_router`` and ``moe_experts`` scopes (``moe_shared`` around the shared
experts) into the traced program, and inside ``moe_experts`` the scopes
``moe_sort`` (the sort and the maps between the orders), ``moe_products``
(the grouped products) and ``moe_combine`` (back to the tokens); what is
left of ``moe_experts`` is the buffer's gathers and the SiLU passes. It
returns the rows routed to each held expert beside the result.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_sharded", "init_moe_params",
           "held_experts_ffn", "gated_ffn", "route_top_k", "grouped_matmul",
           "combine_stats"]


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


def _megablox_usable(m, k, n):
    """The Pallas grouped products take a call of ``m`` rows, contraction
    ``k`` and ``n`` columns where an accelerator is present, the sizes tile
    (128s) and the visible mesh is one device: GSPMD cannot partition a
    Mosaic kernel, ``lax.ragged_dot`` and ``segment_sum`` it can."""
    from .mesh import current_scope
    scope = current_scope()
    if scope is not None and scope[0].size > 1:
        return False
    return (any(d.platform != "cpu" for d in jax.devices())
            and m % 128 == 0 and k % 128 == 0 and n % 128 == 0)


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
    may be less than M. Rows past the last group are NOT defined where the
    Pallas kernels run (zero elsewhere): nothing may sum over them. The
    kernels themselves do not: a row's result reads that row alone, and the
    weights' gradient (``tgmm``) selects the groups' rows before it
    multiplies. The work follows the rows in the groups, not M."""
    group_sizes = group_sizes.astype(jnp.int32)
    with jax.named_scope("moe_products"):
        if _megablox_usable(lhs.shape[0], lhs.shape[1], rhs.shape[2]):
            return _pallas_gmm(lhs, rhs, group_sizes)
        live = jnp.arange(lhs.shape[0], dtype=jnp.int32) < jnp.sum(group_sizes)
        return jnp.where(live[:, None],
                         lax.ragged_dot(lhs, rhs, group_sizes), 0)


def gated_ffn(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * W_up x)`` with matrices stored (out, in)."""
    up = jax.nn.silu(x @ w_gate.T) * (x @ w_up.T)
    return up @ w_down.T


def _sorted_rows_ffn(rows, w_gate, w_up, w_down, sizes):
    """The held experts' gated feed-forward over rows sorted by expert:
    grouped gate/up, SiLU product, grouped down. Rows past the groups are
    not defined, going in or coming out, in either direction."""
    up = jax.nn.silu(grouped_matmul(rows, w_gate, sizes).astype(jnp.float32)) \
        * grouped_matmul(rows, w_up, sizes).astype(jnp.float32)
    return grouped_matmul(up.astype(rows.dtype), w_down, sizes)


# Tokens a group of the combine's grouped segment sum: one MXU tile.
_TOKEN_BLOCK = 128

# Where :func:`_sum_by_token` sent each sum it traced (once a trace):
# "grouped" = megablox's ``tgmm`` over blocks of tokens, "xla" = segment_sum.
_COMBINES = {"grouped": 0, "xla": 0}


def combine_stats():
    """How many of the combine's sums over a token's rows were traced as
    the grouped product (``grouped``) and as ``jax.ops.segment_sum``
    (``xla``) since import."""
    return dict(_COMBINES)


def _sum_by_token(rows, token, scale, held, dtype):
    """``out[t] = sum of scale[i] * rows[i] over the i with token[i] == t``,
    summed in float32, ``(T, d)`` of ``dtype``: ``rows (m, d)`` in token
    order, ``token (m,)`` non-decreasing over the first ``held.sum()`` rows,
    which are the only ones read (the others are not defined), ``scale (m,)``
    float32 or ``None`` for ones, ``held (T, top_k)`` which assignments the
    rows stand for.

    On the chip the sum is the grouped product the weights' gradients use
    (``tgmm``): the groups are blocks of ``_TOKEN_BLOCK`` tokens, the
    transposed operand a one-hot of the token within its block that carries
    the scale. A float32 operand meets the MXU in float32 (the kernel's
    product is traced at the highest precision; Mosaic's own rounds float32
    operands to bfloat16), so a float32 scale times a bfloat16 row is what
    it is in XLA. Elsewhere ``jax.ops.segment_sum``."""
    (m, d), T, block = rows.shape, held.shape[0], _TOKEN_BLOCK
    if T % block == 0 and _megablox_usable(m, block, d):
        _COMBINES["grouped"] += 1
        hot = (token % block)[:, None] == jnp.arange(block, dtype=jnp.int32)
        hot = hot.astype(rows.dtype) if scale is None else \
            jnp.where(hot, scale[:, None], 0.0)
        counts = jnp.sum(held.reshape(T // block, -1), axis=-1,
                         dtype=jnp.int32)
        exact = "highest" if hot.dtype == jnp.float32 else None
        with jax.enable_x64(False), jax.default_matmul_precision(exact):
            out = _megablox().tgmm(hot.swapaxes(0, 1), rows, counts, dtype,
                                   (128, block, min(d, 2048)))
        return out.reshape(T, d)
    _COMBINES["xla"] += 1
    rows = rows.astype(jnp.float32)
    if scale is not None:
        rows = rows * scale[:, None]
    live = jnp.arange(m, dtype=jnp.int32) < jnp.sum(held, dtype=jnp.int32)
    return jax.ops.segment_sum(rows, jnp.where(live, token, T), T,
                               indices_are_sorted=True).astype(dtype)


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
    summed in float32, ``(T, d)`` of ``x``'s type. ``routing = (order, perm,
    rank, held, sizes)``: the assignments sorted by held expert (not held
    last); the sorted buffer's row of the i-th HELD assignment in token
    order; each assignment's number among the held ones in token order;
    which are held; the rows of each held expert.

    Gathers of the buffer's ``m`` rows only, forward and backward (a
    scatter-add of rows is slow on the chip): the sorted buffer is gathered
    from ``x``; a token's result gathers the buffer into token order once
    and sums each token's adjacent rows (:func:`_sum_by_token`); the
    backward pass gathers the other way, and a weight's gradient is a row
    sum taken in the sorted buffer, where the result's cotangent already
    is. No pass is sized by tokens x ``top_k`` but the scalar maps. Nothing
    is kept for the backward pass but the operands: it recomputes the sorted
    rows and their products."""
    return _routed_fwd(x, weights, w_gate, w_up, w_down, routing, top_k,
                       tiers)[0]


def _routed_fwd(x, weights, w_gate, w_up, w_down, routing, top_k, tiers):
    order, perm, _, held, sizes = routing

    def sized(m):
        def run(x, weights, w_gate, w_up, w_down):
            first, back = order[:m], perm[:m]
            token = first // top_k
            out = _sorted_rows_ffn(x[token], w_gate, w_up, w_down, sizes)
            with jax.named_scope("moe_combine"):
                return _sum_by_token(
                    out[back], token[back], weights.reshape(-1)[first][back],
                    held, x.dtype)
        return run

    y = _pick_tier(jnp.sum(sizes), tiers, sized, x, weights, w_gate, w_up,
                   w_down)
    return y, (x, weights, w_gate, w_up, w_down, routing)


def _routed_bwd(top_k, tiers, res, dy):
    x, weights, w_gate, w_up, w_down, routing = res
    order, perm, rank, held, sizes = routing

    def sized(m):
        def run(x, weights, w_gate, w_up, w_down, dy):
            first, back = order[:m], perm[:m]
            token = first // top_k
            out, pull = jax.vjp(
                lambda r, g, u, w: _sorted_rows_ffn(r, g, u, w, sizes),
                x[token], w_gate, w_up, w_down)
            # a sorted row's cotangent: its token's, times its weight
            dy_rows = dy[token].astype(jnp.float32)
            gate = weights.reshape(-1)[first]
            d_rows, d_gate, d_up, d_down = pull(
                (dy_rows * gate[:, None]).astype(out.dtype))
            with jax.named_scope("moe_combine"):
                d_x = _sum_by_token(d_rows[back], token[back], None, held,
                                    x.dtype)
                d_sorted = jnp.sum(out.astype(jnp.float32) * dy_rows, axis=-1)
                d_weights = jnp.where(
                    held, d_sorted[back][jnp.minimum(rank, m - 1)], 0)
            return d_x, d_weights, d_gate, d_up, d_down
        return run

    grads = _pick_tier(jnp.sum(sizes), tiers, sized, x, weights, w_gate,
                       w_up, w_down, dy)
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
    grouped products work on the rows there are, and the gathers, the
    combine and the elementwise passes run on the smallest buffer that
    holds them, of twice a uniform router's ``T * top_k * G / E`` rows,
    doubling (:func:`_tiers`). Only the sort and the scalar maps between
    the orders cost by ``T * top_k``."""
    T, G = x.shape[0], w_gate.shape[0]
    with jax.named_scope("moe_router"):
        chosen, weights = route_top_k(x, router_w, router_b, top_k, scale,
                                      normalize)
    with jax.named_scope("moe_experts"):
        with jax.named_scope("moe_sort"):
            local = chosen - first
            held = (local >= 0) & (local < G)
            flat = jnp.where(held, local, G).reshape(-1)        # (T*k,)
            order = jnp.argsort(flat, stable=True).astype(jnp.int32)
            sizes = jnp.sum(flat[:, None] == jnp.arange(G, dtype=jnp.int32),
                            axis=0).astype(jnp.int32)
            # token order: the held assignments as they come, then the rest
            # in their sorted places, so that any prefix that holds the
            # rows routed is a permutation of itself
            at = jnp.arange(T * top_k, dtype=jnp.int32)
            rank = jnp.cumsum(held.reshape(-1), dtype=jnp.int32) - 1
            perm = jnp.zeros_like(at).at[
                jnp.where(at < jnp.sum(sizes), rank[order], at)].set(
                    at, unique_indices=True)
        y = _routed_experts(x, jnp.where(held, weights, 0.0), w_gate, w_up,
                            w_down, (order, perm, rank.reshape(T, top_k),
                                     held, sizes), top_k,
                            _tiers(T, top_k, G, router_w.shape[0]))
        return y, lax.stop_gradient(sizes.astype(jnp.float32))
