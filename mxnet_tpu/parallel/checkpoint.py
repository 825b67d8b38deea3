"""Sharded checkpoint/resume for mesh trainers.

Role parity: reference checkpoint stack (SURVEY §5.4 — `Module.
save_checkpoint`, `Trainer.save_states`) extended the TPU-native way:
parameters AND optimizer state are saved directly from their sharded
device buffers via Orbax (each host writes only its shards — the same
mechanism production JAX trainers use on pods) and restored back onto the
trainer's mesh shardings without materializing the full tree on one host.

The single-host formats (`.params` binary, `save_states`) remain for
reference compatibility; this is the path that scales to pod-sized models.
"""
from __future__ import annotations

import json
import os
import shutil

import jax
import numpy as np

from ..observability import tracer as _trace
from ..resilience import chaos as _chaos

__all__ = ["save_checkpoint", "restore_checkpoint"]


def _tree(trainer):
    # keyed by position: gluon's global name counters make auto-generated
    # parameter names differ between otherwise-identical trainers, and the
    # restore target must match the saved structure exactly
    keys = ["p%04d" % i for i in range(len(trainer._params))]
    tree = {
        "step": np.int64(trainer._t),
        # saving topology: restore compares it against the CURRENT mesh
        # and records a reshard when they differ (elastic resume at a
        # smaller/larger world) — the values themselves are re-placed on
        # the restoring trainer's shardings either way
        "world": np.int64(len(trainer._mesh.devices.flat)),
        "names": [p.name for p in trainer._params],
        "values": dict(zip(keys, trainer._values)),
        "states": {k: list(s) for k, s in zip(keys, trainer._states)},
    }
    # the full placement, not just its size: restore onto a different
    # placement counts a re-plan (dp x pp x ep state re-placed under a
    # new factorization) and an impossible reshard can name both sides
    plan = getattr(trainer, "_plan", None)
    if plan is not None:
        tree["plan"] = {k: np.int64(v) for k, v in plan.to_dict().items()}
    # wrappers with their own carried state (resilience.guardrails
    # GuardedStep: loss scale, clean-step counter, skip counter) ride in
    # the same atomic checkpoint, so restore-and-replay reproduces their
    # trajectory bitwise, not just the parameters'
    extra_fn = getattr(trainer, "_checkpoint_extra", None)
    if extra_fn is not None:
        tree["extra"] = extra_fn()
    return tree


def save_checkpoint(trainer, path, force=True):
    """Write the trainer's sharded params + optimizer state + step counter
    to ``path`` (a directory). Safe to call mid-training; blocks until the
    write completes.

    Atomic publish: the tree is staged into ``path + ".tmp"`` and only
    renamed onto ``path`` once fully written — a crash mid-save (exercised
    by the ``checkpoint.save`` chaos point, which fires between staging
    and publish) leaves the previous good checkpoint at ``path`` intact,
    never a partial write that :func:`restore_checkpoint` would load."""
    with _trace.span("checkpoint.save", path=path, step=trainer._t):
        return _save_checkpoint(trainer, path, force)


def _save_checkpoint(trainer, path, force):
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    tmp = path + ".tmp"
    old = path + ".old"
    if not os.path.exists(path) and os.path.exists(old):
        # crash landed between the two publish renames below: `old` IS the
        # last good checkpoint — promote it back, never treat it as stale
        os.rename(old, path)
    for stale in (tmp, old):  # leftovers from an earlier crashed save
        if os.path.exists(stale):
            shutil.rmtree(stale)
    if os.path.exists(path) and not force:
        # refused up front: nothing has been staged yet
        raise FileExistsError("checkpoint %s exists (force=False)" % path)
    ckptr = ocp.PyTreeCheckpointer()
    ckptr.save(tmp, _tree(trainer), force=force)
    # advisory plan record INSIDE the staged dir (orbax ignores foreign
    # files): it publishes atomically WITH the checkpoint, so a failed
    # reshard can always name the placement this exact checkpoint was
    # saved under — never a stale claim from a previous save (the
    # authoritative copy rides the tree; this one is readable without an
    # orbax restore, which is the point when the restore itself fails)
    plan = getattr(trainer, "_plan", None)
    if plan is not None:
        with open(os.path.join(tmp, "plan.json"), "w") as f:
            json.dump(plan.to_dict(), f)
    # a "crash" here (fault injected mid-save) must leave `path` untouched
    _chaos.point("checkpoint.save")
    if os.path.exists(path):  # force=False already rejected before the write
        os.rename(path, old)
    os.rename(tmp, path)
    if os.path.exists(old):
        shutil.rmtree(old)
    return path


def restore_checkpoint(trainer, path):
    """Restore a checkpoint written by :func:`save_checkpoint` onto the
    trainer's CURRENT mesh/shardings — the device topology may differ from
    the one that saved (elastic resume), as long as shapes match."""
    with _trace.span("checkpoint.restore", path=path):
        return _restore_checkpoint(trainer, path)


def _restore_checkpoint(trainer, path):
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    if not os.path.exists(path) and os.path.exists(path + ".old"):
        # crash landed between save_checkpoint's two renames: the previous
        # good checkpoint was already moved aside — promote it back
        os.rename(path + ".old", path)
    tpl = _tree(trainer)
    ckptr = ocp.PyTreeCheckpointer()
    # the wrapper population may have changed between save and restore
    # (e.g. the trainer was wrapped in a GuardedStep AFTER the incident
    # the checkpoint predates): adapt the template to the saved tree
    # instead of failing on a top-level key mismatch
    try:
        saved = ckptr.metadata(path)
        # orbax >= 0.11 hands back a StepMetadata around the tree's own
        # metadata; older versions the tree itself
        saved = getattr(saved, "item_metadata", saved)
        saved = getattr(saved, "tree", saved)
        saved_keys = set(saved.keys())
    except Exception:  # noqa: BLE001 — older layouts: keep strict template
        saved, saved_keys = None, set(tpl.keys())
    if "extra" in tpl and "extra" not in saved_keys:
        # pre-wrapper checkpoint: restore the trainer state; the wrapper
        # keeps its current (fresh) guard state
        tpl.pop("extra")
    elif saved is not None and "extra" in saved_keys and "extra" not in tpl:
        # wrapper checkpoint restored into a bare trainer: materialize the
        # extra subtree from metadata so orbax accepts it, then discard
        tpl["extra"] = jax.tree_util.tree_map(
            lambda m: np.zeros(m.shape, m.dtype), saved["extra"])
    if "world" in tpl and saved is not None and "world" not in saved_keys:
        tpl.pop("world")  # checkpoint from before topology was recorded
    # same both-ways adaptation for the recorded plan (a plan-stamped
    # checkpoint restores into a planless trainer and vice versa)
    if "plan" in tpl and "plan" not in saved_keys:
        tpl.pop("plan")
    elif saved is not None and "plan" in saved_keys and "plan" not in tpl:
        tpl["plan"] = jax.tree_util.tree_map(
            lambda m: np.zeros(m.shape, m.dtype), saved["plan"])
    # reshard-impossible fast path: when metadata is readable, a saved
    # value whose SHAPE cannot land on the current trainer is a typed
    # plan/topology mismatch, not a deferred orbax/tensorstore failure
    if saved is not None and "values" in saved_keys:
        try:
            saved_vals = dict(saved["values"].items())
        except (AttributeError, TypeError):
            saved_vals = {}
        for k, v in tpl["values"].items():
            m = saved_vals.get(k)
            if m is not None and hasattr(m, "shape") \
                    and tuple(m.shape) != tuple(v.shape):
                raise _wrap_mismatch(trainer, path, ValueError(
                    "param %s saved with shape %s cannot reshard onto "
                    "current shape %s" % (k, tuple(m.shape),
                                          tuple(v.shape))))

    def _restore(tpl):
        restore_args = jax.tree_util.tree_map(
            lambda v: ocp.ArrayRestoreArgs(sharding=v.sharding)
            if isinstance(v, jax.Array) else ocp.RestoreArgs(), tpl)
        return ckptr.restore(
            path, args=ocp.args.PyTreeRestore(item=tpl,
                                              restore_args=restore_args))

    try:
        restored = _restore(tpl)
    except (ValueError, KeyError) as e:
        # tree-structure mismatch with metadata() unavailable: the only
        # template adaptations that couldn't happen up front are the
        # optional "plan"/"world" keys — an older checkpoint may lack
        # EITHER or BOTH, so retry the combinations most-likely first (a
        # pre-planner checkpoint still has "world": dropping both at
        # once would un-match it again). Runtime/shape errors are NOT
        # retried: they would only fail again and mask the primary
        # error.
        restored = None
        if saved is None:
            candidates = []
            for drop in (("plan",), ("world",), ("plan", "world")):
                if all(k in tpl for k in drop):
                    candidates.append({k: v for k, v in tpl.items()
                                       if k not in drop})
            if "plan" not in tpl:
                # the reverse direction: a plan-stamped checkpoint into a
                # planless trainer — the plan subtree's template is
                # statically known (int64 scalars), so it can be ADDED
                # and the restored copy simply ignored
                t3 = dict(tpl)
                t3["plan"] = {k: np.int64(0) for k in
                              ("dp", "pp", "ep", "sp", "n_devices")}
                candidates.append(t3)
            for t2 in candidates:
                try:
                    restored = _restore(t2)
                    break
                except (ValueError, KeyError):
                    continue
        if restored is None:
            if saved is None:
                # no metadata to rule a reshard in or out: best-effort
                # placement context (the message embeds the raw error)
                raise _wrap_mismatch(trainer, path, e) from e
            # metadata WAS readable and the shape pre-check above passed:
            # this failure is not a placement mismatch (an IO blip on a
            # legitimate re-plan restore must not be mislabeled as an
            # impossible reshard — a retry on the same placement is the
            # right recovery, not a re-plan)
            raise
    keys = ["p%04d" % i for i in range(len(trainer._params))]
    trainer._t = int(restored["step"])
    trainer._values = [restored["values"][k] for k in keys]
    trainer._states = [tuple(restored["states"][k]) for k in keys]
    if "extra" in restored and hasattr(trainer, "_restore_extra"):
        trainer._restore_extra(restored["extra"])
    if "world" in restored:
        saved_world = int(restored["world"])
        now_world = len(trainer._mesh.devices.flat)
        if saved_world != now_world:
            # the elastic reshard path fired: state written under one
            # topology landed on another — make the transition visible
            from ..resilience import elastic as _elastic
            _elastic._count("resharded_restores")
            _trace.instant("elastic.reshard", saved_world=saved_world,
                           world=now_world, step=trainer._t)
    cur_plan = getattr(trainer, "_plan", None)
    if "plan" in restored and cur_plan is not None:
        saved_plan = {k: int(v) for k, v in restored["plan"].items()}
        if saved_plan != cur_plan.to_dict():
            # the elastic RE-PLAN path: dp x pp x ep state written under
            # one placement landed on a planner-chosen different one
            from ..parallel.planner import _describe_dict
            from ..resilience import elastic as _elastic
            _elastic._count("replans")
            _trace.instant("elastic.replan",
                           saved=_describe_dict(saved_plan),
                           current=cur_plan.describe(),
                           step=trainer._t)
    return trainer


def _wrap_mismatch(trainer, path, exc):
    """Dress a restore failure in placement context: when the sidecar
    names a saved plan that differs from the restoring trainer's, the
    failure IS a reshard-impossible transition — surface the typed
    :class:`~mxnet_tpu.parallel.planner.PlanMismatchError` naming both
    placements instead of the raw orbax/pytree error. Returns the
    exception to raise (the original one when no plan context exists)."""
    saved_plan = None
    try:
        with open(os.path.join(path, "plan.json")) as f:
            saved_plan = json.load(f)
    except (OSError, ValueError):
        pass
    cur = getattr(trainer, "_plan", None)
    cur_d = cur.to_dict() if cur is not None else None
    if saved_plan is not None and saved_plan != cur_d:
        from .planner import PlanMismatchError
        return PlanMismatchError(saved_plan, cur_d,
                                 "%s: %s" % (type(exc).__name__, exc))
    return exc
