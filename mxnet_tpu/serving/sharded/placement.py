"""Mesh placement for the sharded serving lane.

Three small, composable pieces sit between a
:class:`~mxnet_tpu.parallel.planner.ShardingPlan` and the serving
engines:

- :func:`place_params` — commit a block's parameters onto the plan's
  mesh by the documented naming convention (``stack_expert_*`` →
  ``P('pp', 'ep')``, ``stack_*`` → ``P('pp')``, everything else
  replicated). The committed shardings are what makes ``jax.jit``
  compile ONE SPMD program: the serving engines' CachedOps see sharded
  inputs/closures and XLA's partitioner inserts the all_to_alls the
  placement implies — no shard_map in the decode path.
- :func:`arena_spec` — the PartitionSpec for a
  :class:`~mxnet_tpu.serving.generation.kvcache.SlotKVCache` arena
  ``(layers, slots, seq, heads, head_dim)``: layers over ``pp``, slots
  over the data axes, and only when the sizes divide evenly (a dim that
  doesn't divide is left whole rather than producing a ragged shard).
- :class:`MeshCommittedOp` — a CachedOp that commits every *uncommitted*
  input onto the mesh (replicated) before dispatch. Program identity on
  a mesh includes the committed input shardings (see
  ``cached_op._active_sharding``); committing the small host-side args
  (tokens, lengths, temperatures, keys) makes that identity exact and
  stable, so AOT export re-lowers the very program dispatch runs and a
  restart from the artifact compiles nothing.
"""
from __future__ import annotations

import re

from ...cached_op import CachedOp

__all__ = ["place_params", "arena_spec", "arena_sharding",
           "MeshCommittedOp"]


def place_params(block, mesh, rules):
    """Commit ``block``'s parameters onto ``mesh`` per (regex ->
    PartitionSpec) ``rules`` (first match wins; unmatched params are
    replicated). The placement happens IN the block's parameter storage
    — the engines' traced programs read ``param.data()._data`` and close
    over the committed values — and each value is copied into an owned
    buffer first (the ShardedTrainer idiom: device_put alone can alias
    the source buffer for the shard landing on the source device).
    Returns ``{param_name: NamedSharding}`` for introspection."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    placed = {}
    for p in block.collect_params().values():
        spec = PartitionSpec()
        for pat, s in rules:
            if re.search(pat, p.name):
                spec = s
                break
        s = NamedSharding(mesh, spec)
        nd_handle = p.data()
        v = jnp.array(nd_handle._data, copy=True)
        nd_handle._data = jax.device_put(v, s)
        placed[p.name] = s
    return placed


def arena_spec(plan, arena_shape):
    """PartitionSpec for a KV arena ``(layers, slots, seq, heads,
    head_dim)`` under ``plan``: layers over ``pp``, slots over the data
    axes — each only when the dim divides evenly, else that dim stays
    whole. ``sp`` belongs to the data axes at serving time (one token
    per slot per step: there is no sequence dim to split), so it shards
    slots, keeping every mesh axis in the arena's sharding."""
    from jax.sharding import PartitionSpec

    layers, slots = int(arena_shape[0]), int(arena_shape[1])
    layer_axis = "pp" if plan.pp > 1 and layers % plan.pp == 0 else None
    data = tuple(ax for ax, size in
                 (("dp", plan.dp), ("ep", plan.ep), ("sp", plan.sp))
                 if size > 1)
    n_data = plan.dp * plan.ep * plan.sp
    slot_axes = data if data and slots % n_data == 0 else None
    return PartitionSpec(layer_axis, slot_axes)


def arena_sharding(plan, mesh, arena_shape):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, arena_spec(plan, arena_shape))


class MeshCommittedOp(CachedOp):
    """CachedOp whose every input is committed onto one mesh.

    Inputs already committed onto the mesh (the arenas, the placed
    params closed over by the traced fn) pass through untouched;
    uncommitted host-side arrays are device_put replicated. The result:
    the per-signature committed-sharding record CachedOp keeps for AOT
    export covers EVERY argument, so the serialized SPMD program and
    the dispatched one are the same program, and a deserialized
    executable never sees an input placement it wasn't compiled for
    (which would demote the AOT hit to a recompile)."""

    def __init__(self, fn, mesh, batch_axes=None, **kwargs):
        """``batch_axes``: optional mesh-axis tuple — inputs whose
        leading dim divides the axes' total size are committed
        batch-sharded over them instead of replicated (the predict-lane
        rule; the decode lane leaves its small per-slot vectors
        replicated and shards only the arenas)."""
        super().__init__(fn, **kwargs)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        self._mesh = mesh
        self._batch_axes = axes = tuple(batch_axes or ())
        self._replicated = NamedSharding(mesh, PartitionSpec())
        self._batch = None
        self._batch_n = 1
        if axes:
            self._batch = NamedSharding(mesh, PartitionSpec(axes))
            n = 1
            for ax in axes:
                n *= int(mesh.shape[ax])
            self._batch_n = n
        self._device_put = jax.device_put

    def _make_pure(self, train):
        """Every trace of this op's body (dispatch, AOT export, AOT load)
        runs inside the lane's :func:`~mxnet_tpu.parallel.mesh.mesh_scope`
        — a full prefill's flash kernel must see the mesh to shard
        itself."""
        from ...parallel.mesh import mesh_scope
        pure, n_out_box, aux_handles_box = super()._make_pure(train)

        def scoped(rng_key, *vals):
            with mesh_scope(self._mesh, self._batch_axes):
                return pure(rng_key, *vals)

        return scoped, n_out_box, aux_handles_box

    def _commit(self, a):
        from ...ndarray.ndarray import NDArray
        if not isinstance(a, NDArray):
            return a
        s = getattr(a._data, "sharding", None)
        mesh = getattr(s, "mesh", None)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            return a
        target = self._replicated
        if self._batch is not None and a.shape and \
                a.shape[0] % self._batch_n == 0:
            target = self._batch
        return NDArray(self._device_put(a._data, target))

    def __call__(self, *args, **kwargs):
        import jax
        if any(isinstance(getattr(a, "_data", None), jax.core.Tracer)
               for a in args):
            return super().__call__(*args, **kwargs)
        return super().__call__(*[self._commit(a) for a in args], **kwargs)
