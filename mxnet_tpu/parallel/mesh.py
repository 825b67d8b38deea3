"""Device mesh management.

Role parity: the reference's device topology layer
(`src/kvstore/gpu_topology.h` link-matrix tree building + ctx lists in
Module/Trainer). TPU-native: a named ``jax.sharding.Mesh`` with the
standard axes — dp (data), tp (tensor), pp (pipeline), sp (sequence) — and
PartitionSpec rules. XLA lays collectives on ICI along mesh axes; there is
no topology detection code to write (the scaling-book recipe: pick a mesh,
annotate shardings, let XLA insert collectives).
"""
from __future__ import annotations

import contextlib
import math
import threading

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["MeshConfig", "make_mesh", "current_scope", "mesh_scope",
           "replicated", "batch_sharding", "PartitionSpec", "NamedSharding"]

# per thread: trainers and serving lanes trace their programs concurrently
_SCOPE = threading.local()

AXES = ("dp", "pp", "ep", "tp", "sp")


class MeshConfig:
    """Sizes per logical axis; -1 on dp means 'use remaining devices'."""

    def __init__(self, dp=-1, pp=1, ep=1, tp=1, sp=1):
        self.dp, self.pp, self.ep = dp, pp, ep
        self.tp, self.sp = tp, sp

    def resolve(self, n_devices):
        fixed = self.pp * self.ep * self.tp * self.sp
        dp = self.dp
        if dp == -1:
            if n_devices % fixed:
                raise ValueError(
                    "device count %d not divisible by pp*ep*tp*sp=%d"
                    % (n_devices, fixed))
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                "mesh %s does not cover %d devices" % (
                    (dp, self.pp, self.ep, self.tp, self.sp), n_devices))
        return (dp, self.pp, self.ep, self.tp, self.sp)


def make_mesh(dp=-1, pp=1, ep=1, tp=1, sp=1, devices=None):
    """Create a Mesh over ``devices``.

    Without ``devices`` the mesh takes what its axis sizes ask for from
    the front of ``jax.devices()``: all of them when ``dp=-1``, the first
    ``dp*pp*ep*tp*sp`` otherwise (``make_mesh(dp=1)`` is one chip on any
    host). An explicit ``devices`` list must be covered exactly.

    Axis order is (dp, pp, ep, tp, sp): tp/sp innermost so tensor/
    sequence collectives ride the fastest ICI links (scaling-book layout
    rule); ep sits between pp and tp so expert all_to_alls stay within a
    stage's slice. A :class:`~mxnet_tpu.parallel.planner.ShardingPlan`
    chooses the axis sizes for composed placements.
    """
    if devices is None:
        devices = jax.devices()
        if dp != -1:
            want = math.prod((dp, pp, ep, tp, sp))
            if want > len(devices):
                raise ValueError("mesh %s needs %d devices, host has %d"
                                 % ((dp, pp, ep, tp, sp), want,
                                    len(devices)))
            devices = devices[:want]
    shape = MeshConfig(dp, pp, ep, tp, sp).resolve(len(devices))
    arr = np.array(devices).reshape(shape)
    mesh = Mesh(arr, AXES)
    return mesh


@contextlib.contextmanager
def mesh_scope(mesh, batch_axes=()):
    """Make ``mesh`` and the axes the batch dim is sharded over visible
    to the ops traced inside the ``with`` block. ShardedTrainer and the
    sharded serving lanes enter it around their traced programs; ops
    that GSPMD cannot partition (the Pallas attention kernels) read it
    through :func:`current_scope` and wrap themselves in ``shard_map``."""
    prev = getattr(_SCOPE, "value", None)
    _SCOPE.value = (mesh, tuple(batch_axes))
    try:
        yield mesh
    finally:
        _SCOPE.value = prev


def current_scope():
    """``(mesh, batch_axes)`` of the innermost :func:`mesh_scope` on
    this thread, or ``None``."""
    return getattr(_SCOPE, "value", None)



def replicated(mesh):
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh, axes=("dp",)):
    """Shard the leading (batch) dim over the data axes."""
    return NamedSharding(mesh, PartitionSpec(axes))
