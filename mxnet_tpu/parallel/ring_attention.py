"""Ring attention: exact attention over sequence-sharded inputs.

Not present in the reference (MXNet 1.6 predates it — SURVEY §5.7), but
first-class here: long-context scaling is a core requirement of the TPU
rebuild. Design follows the ring-attention recipe (blockwise attention with
K/V blocks rotating around the ICI ring via ``lax.ppermute``, online
softmax accumulation in fp32) — each chip holds Q for its sequence shard
and streams K/V shards from its ring neighbours, overlapping compute with
ICI transfers. Memory per chip is O(seq/chips), enabling context lengths
proportional to the ring size.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["ring_attention", "ring_attention_sharded"]


def _block_attn(q, k, v, acc, row_max, row_sum, causal_mask):
    """One (Q-block x KV-block) tile with online-softmax accumulation."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    d = q.shape[-1]
    s = s * jnp.float32(1.0 / np.sqrt(d))
    if causal_mask is not None:
        s = jnp.where(causal_mask, s, -jnp.inf)
    blk_max = jnp.max(s, axis=-1)
    new_max = jnp.maximum(row_max, blk_max)
    # guard fully-masked rows: exp(-inf - -inf)
    safe = jnp.isfinite(new_max)
    corr = jnp.where(safe, jnp.exp(row_max - new_max), 0.0)
    p = jnp.exp(s - new_max[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    acc = acc * corr[..., None] + pv
    row_sum = row_sum * corr + jnp.sum(p, axis=-1)
    return acc, new_max, row_sum


def ring_attention(q, k, v, axis_name="sp", causal=False):
    """Exact attention where q/k/v are sharded on the sequence axis across
    ``axis_name``. Call INSIDE shard_map/pjit over a mesh with that axis.

    q, k, v: (batch, heads, seq_shard, dim) — local shards.
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape

    # derive carries from q so they share its varying (manual) mesh axes
    acc = jnp.zeros_like(q, dtype=jnp.float32)
    row_max = jnp.full_like(q[..., 0], -jnp.inf, dtype=jnp.float32)
    row_sum = jnp.zeros_like(q[..., 0], dtype=jnp.float32)

    def body(i, carry):
        acc, row_max, row_sum, k_blk, v_blk = carry
        src_idx = (idx - i) % n  # which seq shard this k/v block came from
        if causal:
            q_pos = idx * S + jnp.arange(S)
            k_pos = src_idx * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None]
        else:
            mask = None
        acc, row_max, row_sum = _block_attn(q, k_blk, v_blk, acc, row_max,
                                            row_sum, mask)
        # rotate k/v one step around the ring (overlaps with next compute)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return acc, row_max, row_sum, k_blk, v_blk

    acc, row_max, row_sum, _, _ = lax.fori_loop(
        0, n, body, (acc, row_max, row_sum, k, v))
    out = acc / jnp.maximum(row_sum[..., None], 1e-20)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           batch_axis="dp"):
    """Convenience wrapper: shard (B,H,S,D) arrays over the mesh and run
    ring_attention via shard_map."""
    spec = PartitionSpec(batch_axis, None, axis_name, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    def run(q, k, v):
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

    qs = jax.device_put(q, NamedSharding(mesh, spec))
    ks = jax.device_put(k, NamedSharding(mesh, spec))
    vs = jax.device_put(v, NamedSharding(mesh, spec))
    out = jax.jit(run)(qs, ks, vs)
    # a dead sp peer wedges the K/V rotation ring silently — bound the
    # wait (collective watchdog; free unless the deadline knob is armed)
    from ..resilience.elastic import guard_wait
    return guard_wait(out, op="ring.dispatch")
