"""Elastic training worker driven by `tools/launch.py --supervise`.

The CPU-oracle simulation of a multi-host data-parallel job: each
"host" (process) holds a full replica trained deterministically from the
same seed and the same regenerated batch schedule, so replicas stay
bitwise-identical without cross-process collectives (those are exercised
separately by tests/dist/dist_sync_kvstore_worker.py) and ANY survivor's
rolling checkpoint can resume the run. What this worker exercises is the
elastic surface itself:

- membership registration + per-step heartbeats into MXTPU_RDZV_DIR;
- chaos-injected `host_loss` (abrupt exit 137) or `preempt`
  (self-SIGTERM) at a fixed step on a chosen rank, gen 0 only;
- a real SIGTERM (from the supervisor's teardown or an external kill)
  -> PreemptionHandler -> emergency checkpoint -> exit 75;
- resume-on-restart: `elastic_fit` restores the rolling checkpoint onto
  the CURRENT mesh — the supervisor re-spreads the device pool over the
  surviving world (--total-devices), so the restore is a genuine
  reshard — and replays the remaining schedule.

Env protocol (beyond the launcher's MXTPU_*):
  ELASTIC_WORKDIR       base dir: ckpt-rank<r>/ + out/ live here (required)
  ELASTIC_STEPS         total steps in the run (default 12)
  ELASTIC_CKPT_EVERY    rolling-checkpoint cadence (default 2)
  ELASTIC_FAIL_RANK     rank to inject the fault on (default: none)
  ELASTIC_FAIL_STEP     trainer.step call to fire at (1-based)
  ELASTIC_FAIL_KIND     host_loss | preempt (default host_loss)
  ELASTIC_STEP_SLOW_MS  per-step injected latency (lets an external
                        SIGTERM land mid-run deterministically)

Each generation's rank 0 writes out/result_gen<G>_rank0.json with the
resumed start step, this generation's per-step losses (full float
precision), the final parameter digest, and the mesh size — the bitwise
evidence the e2e test and benchmark/elastic_bench.py compare.
"""
import hashlib
import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402


def _batches(nd, steps, batch=8, features=16, classes=4):
    """The run's batch schedule — regenerated identically by every
    generation and every rank (elastic_fit's replay contract)."""
    rng = np.random.RandomState(1234)
    out = []
    for _ in range(steps):
        x = rng.randn(batch, features).astype(np.float32)
        y = rng.randint(0, classes, size=(batch,)).astype(np.float32)
        out.append((nd.array(x), nd.array(y)))
    return out


def main():
    rank = int(os.environ.get("MXTPU_PROCESS_ID", "0"))
    world = int(os.environ.get("MXTPU_NUM_PROCESSES", "1"))
    gen = int(os.environ.get("MXTPU_GENERATION", "0"))
    rdzv = os.environ.get("MXTPU_RDZV_DIR")
    workdir = os.environ["ELASTIC_WORKDIR"]
    steps = int(os.environ.get("ELASTIC_STEPS", "12"))
    ckpt_every = int(os.environ.get("ELASTIC_CKPT_EVERY", "2"))
    fail_rank = int(os.environ.get("ELASTIC_FAIL_RANK", "-1"))
    fail_step = int(os.environ.get("ELASTIC_FAIL_STEP", "0"))
    fail_kind = os.environ.get("ELASTIC_FAIL_KIND", "host_loss")
    slow_ms = float(os.environ.get("ELASTIC_STEP_SLOW_MS", "0"))

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.parallel.mesh import replicated
    from mxnet_tpu.resilience import chaos, elastic

    # the eviction notice must be catchable from the first step on
    handler = elastic.PreemptionHandler().install()

    member = None
    if rdzv:
        member = elastic.ElasticMember(rdzv, rank, world_size=world,
                                       generation=gen)

    if fail_rank == rank and gen == 0 and fail_step > 0:
        chaos.arm("trainer.step", fail_kind, at=fail_step)
    if slow_ms > 0:
        chaos.arm("trainer.step", "slow", delay_ms=slow_ms, every=1)

    mx.random.seed(0)
    np.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 16)))
    mesh = parallel.make_mesh(dp=-1)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=mesh)

    ckpt_dir = os.path.join(workdir, "ckpt-rank%d" % rank)
    out_dir = os.path.join(workdir, "out")
    os.makedirs(out_dir, exist_ok=True)

    # preserve the exact state this generation resumed from: the test's
    # reference replay restarts from THIS snapshot and must match bitwise
    rolling = os.path.join(ckpt_dir, "resume_ckpt")
    if os.path.exists(rolling):
        snap = os.path.join(out_dir,
                            "restored_gen%d_rank%d" % (gen, rank))
        if not os.path.exists(snap):
            shutil.copytree(rolling, snap)

    try:
        start, losses = elastic.elastic_fit(
            trainer, _batches(nd, steps), ckpt_dir, member=member,
            preemption=handler, ckpt_every=ckpt_every, seed=0)
    except elastic.Preempted as p:
        print("rank %d preempted: %s" % (rank, p), flush=True)
        sys.exit(elastic.EXIT_PREEMPTED)

    values = [np.asarray(jax.device_put(v, replicated(mesh)))
              for v in trainer._values]
    digest = hashlib.sha256()
    for v in values:
        digest.update(v.tobytes())
    if rank == 0:
        path = os.path.join(out_dir, "result_gen%d_rank0.json" % gen)
        with open(path, "w") as f:
            json.dump({"gen": gen, "world": world, "rank": rank,
                       "devices": len(jax.devices()),
                       "start_step": start, "end_step": trainer._t,
                       "losses": losses,
                       "params_sha256": digest.hexdigest()}, f)
    print("rank %d OK gen=%d start=%d end=%d devices=%d"
          % (rank, gen, start, trainer._t, len(jax.devices())), flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
