"""Sharded decode: the PR 15 planner threaded through the decode engine.

:class:`ShardedDecodeEngine` is a
:class:`~mxnet_tpu.serving.generation.decode.DecodeEngine` whose
programs compile against a serving :class:`ShardingPlan
<mxnet_tpu.parallel.planner.ShardingPlan>`:

- the model's parameters are committed onto ``plan.mesh()`` by the
  naming convention (``stack_expert_*`` over ``('pp', 'ep')`` — the MoE
  stack serves expert-parallel through the plain ``moe_ffn`` einsums,
  GSPMD inserting the all_to_alls);
- the :class:`SlotKVCache` arenas are committed onto the SAME mesh
  (:func:`~.placement.arena_spec`), and every commit re-asserts the
  canonical arena sharding so a program output whose sharding GSPMD
  chose differently can never change the next step's program identity
  (which would silently recompile behind the stable cache signature);
- every host-side input is committed replicated
  (:class:`~.placement.MeshCommittedOp`), making the committed-sharding
  part of program identity exact — the fused decode step still compiles
  exactly once, and membership churn still compiles nothing.

AOT: :meth:`export_artifacts` writes ALL program families (decode,
prefill, chunk, prefix insert/extract) into one ``.mxa`` whose
fingerprint covers the mesh axis names and sizes
(``aot.fingerprint(mesh)``), so a multi-chip replica restart
deserializes machine code for its exact mesh — and a single-chip
artifact can never be silently installed into a sharded lane (typed
fallback + ``cachedop.pcache.fallback`` row instead).
"""
from __future__ import annotations

import os

from ... import aot as _aot
from ... import config as _config
from ... import pcache as _pcache
from ...parallel.planner import plan_serving
from ..generation.decode import DecodeEngine
from ..generation.kvcache import SlotKVCache
from .placement import (MeshCommittedOp, arena_sharding, arena_spec,
                        place_params)

__all__ = ["ShardedDecodeEngine", "ShardedSlotKVCache"]

# which positional args of each program family are the K/V arenas; the
# families that run the model take its parameters after these (placed by
# the plan), everything else dispatches replicated
_ARENA_ARGS = {
    "decode": (4, 5),          # tokens, lengths, temps, key, K, V, *params
    "prefill": (3, 4),         # tokens, length, slot, K, V, *params
    "chunk": (3, 4),           # tokens, start, slot, K, V, *params
    "prefix_insert": (3, 4),   # k_slab, v_slab, slot, K, V
    "prefix_extract": (0, 1),  # K, V, slot
}
_MODEL_FAMILIES = ("decode", "prefill", "chunk")


class ShardedSlotKVCache(SlotKVCache):
    """SlotKVCache whose arenas live committed on a mesh.

    :meth:`bind` places the freshly-zeroed arenas; :meth:`commit`
    re-asserts the canonical sharding on every functional update — a
    device_put that is a no-op when the program output already carries
    it (the common case), and a reshard rather than a recompile when
    GSPMD picked a different output layout."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.arena_sharding = None

    def bind(self, sharding):
        """Commit both arenas onto ``sharding`` (NamedSharding over the
        plan's mesh); subsequent commits keep them there."""
        import jax
        from ...ndarray.ndarray import NDArray
        self.arena_sharding = sharding
        self.k_arena = NDArray(jax.device_put(self.k_arena._data, sharding))
        self.v_arena = NDArray(jax.device_put(self.v_arena._data, sharding))
        return self

    def _reassert(self, arena):
        import jax
        from ...ndarray.ndarray import NDArray
        if getattr(arena._data, "sharding", None) == self.arena_sharding:
            return arena
        return NDArray(jax.device_put(arena._data, self.arena_sharding))

    def commit(self, k_arena, v_arena):
        if self.arena_sharding is not None:
            k_arena = self._reassert(k_arena)
            v_arena = self._reassert(v_arena)
        super().commit(k_arena, v_arena)


class ShardedDecodeEngine(DecodeEngine):
    """Slot-batched decoder compiled against a serving ShardingPlan.

    Parameters beyond :class:`DecodeEngine`'s:

    plan : ShardingPlan, optional
        The placement to serve under. When omitted, one is computed
        with :func:`~mxnet_tpu.parallel.planner.plan_serving` from the
        model's own profile at ``(num_slots, max_seq)`` geometry — the
        latency-weighted serving objective, honoring the
        ``MXNET_SERVE_PLAN_*`` knobs.
    devices / n_devices : optional
        The device pool to mesh over (default: all local devices).
        ``replan`` after a chip-host loss is a rebuild on the surviving
        pool — see :class:`~.replica.ShardedReplica`.
    hbm_bytes / kv_bytes : optional
        Per-device memory budget and KV-arena burden for the plan
        search (``kv_bytes`` defaults to this engine's actual arena
        footprint).
    param_rules : optional
        Extra (regex -> PartitionSpec) placement rules, PREPENDED to
        the plan's naming-convention rules (first match wins).
    """

    def __init__(self, model, plan=None, profile=None, devices=None,
                 n_devices=None, hbm_bytes=None, kv_bytes=None,
                 num_slots=None, max_seq=None, dtype="float32",
                 param_rules=None, name="sharded_generation", **kwargs):
        import jax
        import numpy as _np
        num_slots = int(num_slots or _config.get("MXNET_GEN_SLOTS"))
        max_seq = int(max_seq or min(_config.get("MXNET_GEN_MAX_SEQ"),
                                     model.max_len))
        if devices is None:
            devices = list(jax.devices())
            if n_devices:
                devices = devices[:int(n_devices)]
        if kv_bytes is None:
            kv_bytes = (2 * model.num_layers * num_slots * max_seq *
                        model.num_heads * model.head_dim *
                        _np.dtype(dtype).itemsize)
        if plan is None:
            if profile is None:
                profile = model.profile(num_slots, seq=max_seq)
            plan = plan_serving(len(devices), profile,
                                hbm_bytes=hbm_bytes, kv_bytes=int(kv_bytes))
        self.plan = plan
        self._mesh = plan.mesh(devices)
        rules = list(param_rules or []) + list(plan.param_rules())
        self._param_shardings = place_params(model, self._mesh, rules)
        cache = ShardedSlotKVCache.for_model(model, num_slots, max_seq,
                                             dtype=dtype, name=name)
        cache.bind(arena_sharding(plan, self._mesh,
                                  cache.k_arena.shape))
        super().__init__(model, cache=cache, name=name, **kwargs)
        # re-home every program family on mesh-committed dispatch: the
        # recorded per-signature shardings then cover ALL inputs, and
        # AOT export re-lowers exactly the SPMD programs dispatch ran
        for attr in ("_decode_op", "_prefill_op", "_chunk_op",
                     "_insert_op", "_extract_op"):
            op = getattr(self, attr)
            setattr(self, attr,
                    MeshCommittedOp(op._fn, self._mesh, name=op._name))

    def _sample_first(self, logits_row, temperature):
        # the fused sampler runs EAGERLY on one logits row; a
        # mesh-committed row can't mix with the host-side temps/key
        # (nd.array commits them to the current context's device), so
        # bring it there first — one (V,) vector, the same bytes
        # asnumpy() would move anyway
        import jax
        from ...context import current_context
        from ...ndarray.ndarray import NDArray
        dev = current_context().jax_device
        data = logits_row._data
        if data.sharding.device_set != {dev}:
            logits_row = NDArray(jax.device_put(data, dev))
        return super()._sample_first(logits_row, temperature)

    # ---- introspection ----------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    def mesh_info(self):
        """The mesh identity the fleet/gateway layers carry per replica:
        axis names+sizes (the fingerprint's ``mesh`` entry), chip count,
        and the plan that produced it."""
        p = self.plan
        return {"axes": _aot.mesh_axes(self._mesh),
                "n_devices": int(self._mesh.size),
                "plan": {"dp": p.dp, "pp": p.pp, "ep": p.ep, "sp": p.sp},
                "arena_spec": str(arena_spec(p, self.cache.k_arena.shape))}

    def param_shardings(self):
        """``{param_name: NamedSharding}`` as placed at build."""
        return dict(self._param_shardings)

    def _op_families(self):
        return (("decode", self._decode_op),
                ("prefill", self._prefill_op),
                ("chunk", self._chunk_op),
                ("prefix_insert", self._insert_op),
                ("prefix_extract", self._extract_op))

    def _family_shardings(self, family, sig):
        """Committed input shardings for one artifact record: arenas on
        the canonical arena sharding, the model's parameters (trailing
        arguments of the families that run it) as the plan placed them,
        everything else replicated — the exact placement
        :class:`MeshCommittedOp` dispatches under."""
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self._mesh, PartitionSpec())
        arena_pos = _ARENA_ARGS.get(family, ())
        shapes, _train = sig
        params = tuple(self._param_shardings[p.name] for p in self._params) \
            if family in _MODEL_FAMILIES else ()
        return tuple(self.cache.arena_sharding if i in arena_pos else repl
                     for i in range(len(shapes) - len(params))) + params

    # ---- AOT: sharded executables in the .mxa container -------------------
    def export_artifacts(self, directory):
        """Serialize every resident program of every family into ONE
        ``executables.mxa`` whose fingerprint covers the mesh
        (``aot.fingerprint(self.mesh)``). The header's ``extra``
        records the family layout (record counts per family, in order)
        and the plan, so :meth:`load_artifacts` can route records back
        and the fleet manifest carries the mesh with the artifact.
        Returns the header dict."""
        records, families = [], []
        for fam, op in self._op_families():
            recs = op.serialize()
            if recs:
                families.append([fam, len(recs)])
                records.extend(recs)
        if not records:
            raise _aot.ArtifactError(
                "no compiled executables to export — serve traffic (or "
                "prefill+decode once) before export_artifacts()")
        os.makedirs(directory, exist_ok=True)
        p = self.plan
        return _aot.write_artifact(
            os.path.join(directory, _aot.ARTIFACT_NAME), records,
            extra={"name": self._name, "engine": "sharded_decode",
                   "families": families,
                   "plan": {"dp": p.dp, "pp": p.pp, "ep": p.ep,
                            "sp": p.sp},
                   "mesh": _aot.mesh_axes(self._mesh)},
            fp=_aot.fingerprint(self._mesh))

    def load_artifacts(self, directory, strict=False):
        """Install a sharded artifact: fingerprint-gated on THIS lane's
        mesh (``current=aot.fingerprint(self.mesh)``), so a single-chip
        artifact — or one exported for any other mesh shape — is
        skipped with a ``cachedop.pcache.fallback`` row and the lane
        compiles normally, never crashes. Loaded signatures are
        re-seeded with their committed input shardings
        (:meth:`CachedOp.record_shardings`) so a later re-export still
        lowers the same SPMD programs. Returns executables installed."""
        path = directory
        if os.path.isdir(directory):
            path = os.path.join(directory, _aot.ARTIFACT_NAME)
        header = _aot.read_artifact_header(path)   # typed on corrupt
        fp = header.get("fingerprint")
        current = _aot.fingerprint(self._mesh)
        where = "ShardedDecodeEngine(%s)" % self._name
        if not _aot.fingerprint_matches(fp, current=current):
            _pcache.note_aot_fallback(
                "fingerprint mismatch: %s"
                % "; ".join(_aot.fingerprint_diff(fp, current=current)),
                where=where)
            return 0
        header, records = _aot.read_artifact(path)
        families = header.get("extra", {}).get("families") or []
        if not families:
            _pcache.note_aot_fallback(
                "artifact has no family layout (not a sharded-decode "
                "export)", where=where)
            return 0
        ops = dict(self._op_families())
        loaded, idx = 0, 0
        for fam, count in families:
            recs = records[idx:idx + int(count)]
            idx += int(count)
            op = ops.get(fam)
            if op is None:
                _pcache.note_aot_fallback(
                    "unknown program family %r in artifact" % (fam,),
                    where=where)
                continue
            for rec in recs:
                op.record_shardings(
                    rec["signature"],
                    self._family_shardings(fam, rec["signature"]))
            try:
                loaded += op.deserialize(recs)
            except _aot.ArtifactError as exc:
                if strict:
                    raise
                _pcache.note_aot_fallback(str(exc), where=where)
        return loaded
