"""Parallelism & distribution (SURVEY §2.4 / §5.8).

The reference's distribution stack (KVStore local/device/NCCL/dist —
`src/kvstore/`) is replaced TPU-natively by mesh + shardings + XLA
collectives over ICI. This package holds the mesh tools, the SPMD
ShardedTrainer, ring attention for sequence parallelism, and multi-host
bootstrap helpers.
"""
from jax.sharding import PartitionSpec, NamedSharding, Mesh  # re-export

from .mesh import (MeshConfig, make_mesh, current_scope, mesh_scope,
                   replicated, batch_sharding)
from .functional import functionalize, functional_optimizer, shard_params
from .trainer import ShardedTrainer
from .datafeed import DeviceFeed, feed_stats
from .checkpoint import save_checkpoint, restore_checkpoint
from .ring_attention import ring_attention, ring_attention_sharded
from .pipeline import pipeline_apply, pipeline_spmd
from .planner import (ModelProfile, PlanError, PlanMismatchError,
                      ShardingPlan, plan_sharding)


def initialize(coordinator_address=None, num_processes=None, process_id=None):
    """Multi-host bootstrap (replaces `tools/launch.py` + DMLC_* env vars,
    reference §5.6: the dmlc tracker/ps-lite launcher). On TPU pods the
    standard `jax.distributed.initialize()` discovers peers natively."""
    import jax
    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    jax.distributed.initialize(**kwargs)


def rank():
    import jax
    return jax.process_index()


def size():
    import jax
    return jax.process_count()
