"""Elastic 3D-parallel (dp x pp x ep) MoE worker driven by
`tools/launch.py --supervise` — the planner's end-to-end acceptance
workload.

Same CPU-oracle protocol as tests/dist/elastic_worker.py (each process
is a full deterministic replica; the elastic surface, not cross-process
collectives, is what's under test), but the model is the stage-stacked
MoE transformer (models/moe_transformer.py) and the placement is CHOSEN
BY THE PLANNER from the local device pool:

- generation 0 runs at world N with total_devices/N forced host devices
  per worker -> one plan;
- after a host loss the supervisor evicts, re-forms at world N-1 and
  re-spreads the pool (planner.respread), so the restarted worker plans
  a DIFFERENT placement and `elastic_fit`'s restore re-plans + reshards
  the dp x pp x ep state bitwise.

Env protocol (beyond the launcher's MXTPU_* and elastic_worker's):
  ELASTIC_WORKDIR / ELASTIC_STEPS / ELASTIC_CKPT_EVERY /
  ELASTIC_FAIL_RANK / ELASTIC_FAIL_STEP / ELASTIC_FAIL_KIND /
  ELASTIC_STEP_SLOW_MS   as in elastic_worker.py

Each generation's rank 0 writes out/result_gen<G>_rank0.json with the
chosen plan, resumed start step, per-step losses (full precision) and
the final parameter digest — the bitwise evidence for
tests/test_planner.py and benchmark/planner_bench.py.
"""
import hashlib
import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

import numpy as np  # noqa: E402

# batch geometry sized so the cost model has a real trade to make: the
# token volume makes dp worth its allreduce, and the tight memory budget
# below (25% headroom over the tightest feasible placement — the "barely
# fits" regime this planner exists for) excludes pp=1 placements, so the
# chosen plan genuinely spans dp x pp x ep on the 8-device pool
VOCAB, BATCH, SEQ = 64, 48, 64


def _batches(nd, steps):
    """Deterministic schedule regenerated identically by every
    generation/rank (elastic_fit's replay contract)."""
    rng = np.random.RandomState(4321)
    out = []
    for _ in range(steps):
        x = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.int32)
        y = rng.randint(0, VOCAB, size=(BATCH, SEQ)).astype(np.float32)
        out.append((nd.array(x), nd.array(y)))
    return out


def main():
    rank = int(os.environ.get("MXTPU_PROCESS_ID", "0"))
    world = int(os.environ.get("MXTPU_NUM_PROCESSES", "1"))
    gen = int(os.environ.get("MXTPU_GENERATION", "0"))
    rdzv = os.environ.get("MXTPU_RDZV_DIR")
    workdir = os.environ["ELASTIC_WORKDIR"]
    steps = int(os.environ.get("ELASTIC_STEPS", "10"))
    ckpt_every = int(os.environ.get("ELASTIC_CKPT_EVERY", "2"))
    fail_rank = int(os.environ.get("ELASTIC_FAIL_RANK", "-1"))
    fail_step = int(os.environ.get("ELASTIC_FAIL_STEP", "0"))
    fail_kind = os.environ.get("ELASTIC_FAIL_KIND", "host_loss")
    slow_ms = float(os.environ.get("ELASTIC_STEP_SLOW_MS", "0"))

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, parallel
    from mxnet_tpu.models.moe_transformer import moe_lm_tiny
    from mxnet_tpu.parallel import planner
    from mxnet_tpu.resilience import chaos, elastic

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
    net = moe_lm_tiny(vocab_size=VOCAB)
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 4), dtype="int32"))

    # the tentpole wiring: placement chosen by the planner from THIS
    # incarnation's device pool under a memory budget the job barely
    # fits (the model-does-not-fit-one-chip regime); a re-formed
    # generation gets a different pool, plans differently, and the
    # restore re-plans + reshards
    n_dev = len(jax.devices())
    profile = net.profile(batch=BATCH, seq=SEQ)
    # 25% headroom over the tightest placement: enough slack that the
    # cost model can buy dp with it, not enough for any pp=1 placement
    # to replicate the stage stack — on the 8-device re-formed pool the
    # winner spans all of dp x pp x ep (dp2·pp2·ep2)
    budget = int(planner.min_memory_per_device(n_dev, profile) * 1.25)
    plan = planner.plan_sharding(n_dev, profile, hbm_bytes=budget)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-2}, plan=plan)
    print("rank %d gen=%d devices=%d plan=%s" %
          (rank, gen, len(jax.devices()), plan.describe()), flush=True)

    ckpt_dir = os.path.join(workdir, "ckpt-rank%d" % rank)
    out_dir = os.path.join(workdir, "out")
    os.makedirs(out_dir, exist_ok=True)

    # preserve the exact snapshot this generation resumed from: the
    # reference replay restarts from it and must match bitwise
    rolling = os.path.join(ckpt_dir, "resume_ckpt")
    if os.path.exists(rolling):
        snap = os.path.join(out_dir, "restored_gen%d_rank%d" % (gen, rank))
        if not os.path.exists(snap):
            shutil.copytree(rolling, snap)

    try:
        start, losses = elastic.elastic_fit(
            trainer, _batches(nd, steps), ckpt_dir, member=member,
            preemption=handler, ckpt_every=ckpt_every, seed=0)
    except elastic.Preempted as p:
        print("rank %d preempted: %s" % (rank, p), flush=True)
        sys.exit(elastic.EXIT_PREEMPTED)

    from mxnet_tpu.parallel.mesh import replicated
    values = [np.asarray(jax.device_put(v, replicated(trainer.mesh)))
              for v in trainer._values]
    digest = hashlib.sha256()
    for v in values:
        digest.update(v.tobytes())
    if rank == 0:
        path = os.path.join(out_dir, "result_gen%d_rank0.json" % gen)
        with open(path, "w") as f:
            json.dump({"gen": gen, "world": world, "rank": rank,
                       "devices": len(jax.devices()),
                       "plan": plan.to_dict(),
                       "plan_str": plan.describe(),
                       "replans": elastic.elastic_stats()["replans"],
                       "start_step": start, "end_step": trainer._t,
                       "losses": losses,
                       "params_sha256": digest.hexdigest()}, f)
    print("rank %d OK gen=%d start=%d end=%d plan=%s"
          % (rank, gen, start, trainer._t, plan.describe()), flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
