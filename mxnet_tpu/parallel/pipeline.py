"""Pipeline parallelism: GPipe-style microbatching over the 'pp' mesh axis.

Absent in the reference (SURVEY §2.4: closest is `PartialForward`
`graph_executor.cc:83` and `group2ctx` device placement) — first-class here.
Design: uniform stages (equal activation shapes, e.g. transformer layers),
each pp rank holds its stage's parameters; microbatch activations rotate
rank→rank+1 via ``lax.ppermute`` each tick, so chip-to-chip transfers ride
ICI neighbours and compute overlaps communication. fori_loop keeps the
schedule compiled as one XLA loop (bubble fraction = (S-1)/(M+S-1)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["pipeline_apply", "pipeline_spmd"]


def pipeline_apply(fn, local_params, batch, n_micro, axis_name="pp"):
    """Run ``y = stage_S-1(...stage_0(x))`` over a pipeline ring.

    Call INSIDE shard_map over a mesh with ``axis_name``. Each rank passes
    its own stage's ``local_params``; ``fn(local_params, x)`` must preserve
    the activation shape. ``batch`` is the full local batch (same on every
    rank); it is split into ``n_micro`` microbatches.

    Returns the full output batch (valid on every rank — final psum).
    """
    n_stages = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B = batch.shape[0]
    assert B % n_micro == 0, "batch not divisible into microbatches"
    mb = B // n_micro
    micro = batch.reshape((n_micro, mb) + batch.shape[1:])

    # mark loop carries as device-varying over the pp axis (their values
    # diverge per rank inside the loop)
    def _vary(x):
        return lax.pcast(x, axis_name, to="varying")

    state = _vary(jnp.zeros_like(micro[0]))
    outputs = _vary(jnp.zeros_like(micro))
    micro = _vary(micro)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(t, carry):
        state, outputs = carry
        # stage 0 consumes microbatch t (when in range); others consume the
        # activation handed over from the previous stage
        feed_idx = jnp.clip(t, 0, n_micro - 1)
        fresh = lax.dynamic_index_in_dim(micro, feed_idx, keepdims=False)
        x = jnp.where(idx == 0, fresh, state)
        y = fn(local_params, x)
        # last stage completes microbatch t-(S-1)
        out_t = t - (n_stages - 1)
        write = (idx == n_stages - 1) & (out_t >= 0)
        safe_t = jnp.clip(out_t, 0, n_micro - 1)
        updated = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(write, y, lax.dynamic_index_in_dim(
                outputs, safe_t, keepdims=False)), safe_t, axis=0)
        outputs = updated
        state = lax.ppermute(y, axis_name, fwd)
        return state, outputs

    _, outputs = lax.fori_loop(0, n_micro + n_stages - 1, body,
                               (state, outputs))
    # only the last stage holds real outputs; broadcast to all ranks
    mask = (idx == n_stages - 1).astype(outputs.dtype)
    outputs = lax.psum(outputs * mask, axis_name)
    return outputs.reshape((B,) + batch.shape[1:])


def pipeline_spmd(fn, stacked_params, batch, mesh, n_micro, axis_name="pp"):
    """Convenience wrapper: jit+shard_map a pipeline forward.

    ``stacked_params``: pytree whose leaves have a leading ``n_stages`` axis
    (stage-sharded over ``axis_name``); ``fn(stage_params, x)`` is one
    stage. Returns the full-batch output (replicated).
    """
    p_stage = PartitionSpec(axis_name)
    p_rep = PartitionSpec()

    def run(params, x):
        local = jax.tree_util.tree_map(
            lambda v: jnp.squeeze(v, axis=0), params)
        return pipeline_apply(fn, local, x, n_micro, axis_name)

    shmapped = shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: p_stage, stacked_params),
                  p_rep),
        out_specs=p_rep)
    params_sh = jax.tree_util.tree_map(
        lambda v: jax.device_put(v, NamedSharding(mesh, p_stage)),
        stacked_params)
    x_sh = jax.device_put(batch, NamedSharding(mesh, p_rep))
    out = jax.jit(shmapped)(params_sh, x_sh)
    # a dead pp peer wedges the ppermute ring silently — bound the wait
    # (collective watchdog; free unless the deadline knob is armed)
    from ..resilience.elastic import guard_wait
    return guard_wait(out, op="pipeline.dispatch")
