"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

Beyond-reference capability (MXNet 1.6 predates MoE; SURVEY §2.4 lists
expert parallelism as a first-class strategy for the TPU rebuild): Switch
-style top-1 routing with static capacity, experts sharded across the
``ep`` axis, token exchange via ``lax.all_to_all`` over ICI — the standard
TPU MoE dataflow (dispatch einsums -> all_to_all -> expert FFN matmuls on
the MXU -> all_to_all back -> weighted combine). Everything is
static-shape: over-capacity tokens are dropped (their output is the zero
vector), exactly like production Switch implementations.

``moe_ffn`` is the single-device reference (also the routing oracle in
tests); ``moe_ffn_sharded`` runs the same math SPMD.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_sharded", "init_moe_params"]


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
