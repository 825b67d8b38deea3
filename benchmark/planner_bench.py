"""Elastic 3D-parallelism benchmark: the planner's two headline claims.

1. **Recovery** — a dp x pp x ep MoE job (tests/dist/planner_worker.py,
   placement chosen by the planner per generation) loses a host to
   injected ``host_loss`` under ``tools/launch.py --supervise``; the
   supervisor evicts, re-forms at world-1 with a planner re-spread pool,
   and the restore RE-PLANS onto the new placement. Reported:
   ``recovery_s`` (loss detected -> re-formed world registered and
   beating) and ``bitwise_equal`` vs an uninterrupted restore-and-replay
   from the same snapshot at the surviving topology.

2. **Placement** — on the memory-constrained MoE config at EQUAL
   devices, the planner's placement vs pure-dp: pure-dp must replicate
   every expert on every device (modeled bytes/device over the budget),
   the planner's ep/pp sharding fits; measured step time for both is
   recorded honestly (CPU oracle: all "devices" share one socket, so
   the memory ratio — not wall clock — is the portable signal).

Zero-drift guard: the planner path must compile NOTHING through the
serving-side CachedOp machinery (``new_cachedop_compiles == 0``) and
must not even import ``mxnet_tpu.serving`` — the decode/serving suites
ride this PR untouched.

Writes ``ELASTIC3D.json`` (stamped via benchmark/_artifact.py).
``--skip-recovery`` runs only the in-process placement section.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCH = os.path.join(REPO, "tools", "launch.py")
WORKER = os.path.join(REPO, "tests", "dist", "planner_worker.py")

BENCH_UNITS, BENCH_HIDDEN, BENCH_EXPERTS, BENCH_LAYERS = 64, 256, 8, 2
BENCH_BATCH, BENCH_SEQ, BENCH_VOCAB = 16, 16, 128


def _bench_net():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.moe_transformer import MoETransformerLM
    import numpy as np

    mx.random.seed(0)
    np.random.seed(0)
    net = MoETransformerLM(BENCH_VOCAB, units=BENCH_UNITS,
                           num_heads=4, num_layers=BENCH_LAYERS,
                           hidden_size=BENCH_HIDDEN,
                           n_experts=BENCH_EXPERTS, max_len=BENCH_SEQ)
    net.initialize(mx.init.Xavier())
    net(nd.zeros((1, 4), dtype="int32"))
    return net


def bench_placement(steps=12):
    """Planner placement vs pure-dp at equal devices on the
    memory-constrained MoE config. Returns the section dict."""
    import numpy as np
    import jax
    from mxnet_tpu import cached_op, gluon, nd, parallel
    from mxnet_tpu.parallel import planner

    serving_loaded_before = any(m.startswith("mxnet_tpu.serving")
                                for m in sys.modules)
    compiles_before = cached_op.cache_stats()["misses"]

    n_dev = len(jax.devices())
    net = _bench_net()
    profile = net.profile(batch=BENCH_BATCH, seq=BENCH_SEQ)
    pure_dp = planner.ShardingPlan(dp=n_dev)
    dp_mem = pure_dp.memory_per_device(profile)
    # the memory-constrained config: a budget pure-dp (every expert
    # replicated on every device) cannot meet, sized off the model so
    # the bench stays meaningful if the config changes. Floored at the
    # tightest feasible placement so a small pool (a single real chip)
    # still plans instead of erroring — there the comparison honestly
    # reports beats_pure_dp=false rather than failing.
    budget = int(max(dp_mem * 0.6,
                     planner.min_memory_per_device(n_dev, profile) * 1.05))
    plan = planner.plan_sharding(n_dev, profile, hbm_bytes=budget)
    plan_mem = plan.memory_per_device(profile)
    dp_reason = pure_dp.feasible(profile, hbm_bytes=budget)

    def timed(p):
        net_i = _bench_net()
        tr = parallel.ShardedTrainer(
            net_i, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
            {"learning_rate": 1e-2}, plan=p)
        rng = np.random.RandomState(0)
        bx = [(nd.array(rng.randint(0, BENCH_VOCAB,
                                    (BENCH_BATCH, BENCH_SEQ)).astype("int32")),
               nd.array(rng.randint(0, BENCH_VOCAB,
                                    (BENCH_BATCH, BENCH_SEQ)).astype(
                                        "float32")))
              for _ in range(4)]
        tr.step(*bx[0]).asnumpy()  # compile + settle
        t0 = time.perf_counter()
        for i in range(steps):
            loss = tr.step(*bx[i % len(bx)])
        loss.asnumpy()
        return (time.perf_counter() - t0) / steps

    plan_step_s = timed(plan)
    dp_step_s = timed(pure_dp)
    return {
        "devices": n_dev,
        "config": {"units": BENCH_UNITS, "hidden": BENCH_HIDDEN,
                   "experts": BENCH_EXPERTS, "layers": BENCH_LAYERS,
                   "batch": BENCH_BATCH, "seq": BENCH_SEQ},
        "hbm_budget_bytes": budget,
        "planner_plan": plan.describe(),
        "planner_bytes_per_device": plan_mem,
        "pure_dp_bytes_per_device": dp_mem,
        "pure_dp_infeasible_reason": dp_reason,
        "memory_ratio_vs_pure_dp": round(plan_mem / dp_mem, 4),
        "planner_step_s": round(plan_step_s, 5),
        "pure_dp_step_s": round(dp_step_s, 5),
        "step_time_ratio": round(plan_step_s / dp_step_s, 3),
        # the acceptance headline: at equal devices the planner placement
        # fits the budget pure-dp cannot — the memory-constrained win
        "beats_pure_dp": bool(dp_reason) and plan_mem < dp_mem,
        "zero_drift": {
            "new_cachedop_compiles":
                cached_op.cache_stats()["misses"] - compiles_before,
            "serving_modules_imported":
                (not serving_loaded_before)
                and any(m.startswith("mxnet_tpu.serving")
                        for m in sys.modules),
        },
    }


def _elastic_bench():
    """The supervised-run helpers live in elastic_bench (same worker env
    protocol + event-log schema) — one definition, both benches."""
    try:
        from benchmark import elastic_bench
    except ImportError:  # run as a script: benchmark/ is sys.path[0]
        import elastic_bench
    return elastic_bench


def _env(workdir, **extra):
    return _elastic_bench()._env(workdir, **extra)


def _one(events, kind, **match):
    return _elastic_bench()._one(events, kind, **match)


def bench_recovery(args):
    """Supervised 3D job + host loss: detect -> re-formed-live, and the
    bitwise comparison against uninterrupted restore-and-replay."""
    workdir = tempfile.mkdtemp(prefix="planner_bench_")
    events_path = os.path.join(workdir, "events.jsonl")
    env = _env(workdir, ELASTIC_STEPS=args.steps,
               ELASTIC_CKPT_EVERY=args.ckpt_every,
               ELASTIC_FAIL_RANK=1, ELASTIC_FAIL_STEP=args.fail_step,
               ELASTIC_FAIL_KIND="host_loss",
               ELASTIC_STEP_SLOW_MS=args.step_slow_ms)
    cmd = [sys.executable, LAUNCH, "-n", "2", "--supervise",
           "--max-restarts", "0", "--total-devices", str(args.devices),
           "--rdzv-dir", os.path.join(workdir, "rdzv"),
           "--event-log", events_path, "--grace-ms", "20000",
           sys.executable, WORKER]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit("supervised run failed rc=%d" % proc.returncode)
    with open(events_path) as f:
        events = [json.loads(ln) for ln in f.read().splitlines()]

    fail = _one(events, "worker_failed")
    stopped = _one(events, "generation_stopped", gen=fail["gen"])
    live = _one(events, "generation_live", gen=fail["gen"] + 1)
    _one(events, "run_complete")
    gen1 = fail["gen"] + 1
    with open(os.path.join(workdir, "out",
                           "result_gen%d_rank0.json" % gen1)) as f:
        resumed = json.load(f)

    # uninterrupted restore-and-replay from the SAME snapshot at the
    # surviving topology — the bitwise baseline
    ref = os.path.join(workdir, "ref")
    os.makedirs(os.path.join(ref, "ckpt-rank0"))
    shutil.copytree(
        os.path.join(workdir, "out", "restored_gen%d_rank0" % gen1),
        os.path.join(ref, "ckpt-rank0", "resume_ckpt"))
    renv = _env(ref, ELASTIC_STEPS=args.steps, MXTPU_GENERATION=gen1)
    renv["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=%d" % args.devices
    rproc = subprocess.run([sys.executable, WORKER], env=renv,
                           capture_output=True, text=True, timeout=300)
    if rproc.returncode != 0:
        sys.stderr.write(rproc.stderr[-4000:])
        raise SystemExit("reference replay failed rc=%d" % rproc.returncode)
    with open(os.path.join(ref, "out",
                           "result_gen%d_rank0.json" % gen1)) as f:
        refres = json.load(f)
    bitwise = (resumed["losses"] == refres["losses"]
               and resumed["params_sha256"] == refres["params_sha256"]
               and resumed["start_step"] == refres["start_step"])
    out = {
        "recovery_s": round(live["t"] - fail["t"], 3),
        "teardown_s": round(stopped["t"] - fail["t"], 3),
        "respawn_to_live_s": round(live["t"] - stopped["t"], 3),
        "world_before": 2, "world_after": 1,
        "plan_after": resumed["plan_str"],
        "replans": resumed["replans"],
        "resumed_from_step": resumed["start_step"],
        "bitwise_equal": bitwise,
    }
    shutil.rmtree(workdir, ignore_errors=True)
    if not bitwise:
        raise SystemExit("3D resumed trajectory diverged from "
                         "restore-and-replay:\n%s" % json.dumps(out))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--fail-step", type=int, default=4)
    ap.add_argument("--ckpt-every", type=int, default=2)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--step-slow-ms", type=float, default=150.0)
    ap.add_argument("--skip-recovery", action="store_true",
                    help="placement comparison only")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "ELASTIC3D.json"))
    args = ap.parse_args()

    artifact = {"metric": "elastic3d_recovery_s", "unit": "s"}
    artifact["placement"] = bench_placement()
    if not args.skip_recovery:
        rec = bench_recovery(args)
        artifact.update({"value": rec["recovery_s"], "recovery": rec})
    from benchmark._artifact import stamp
    artifact = stamp(artifact, platform="cpu")  # oracle by construction
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact.get("value"),
        "plan": artifact["placement"]["planner_plan"],
        "beats_pure_dp": artifact["placement"]["beats_pure_dp"],
        "bitwise_equal": artifact.get("recovery", {}).get("bitwise_equal"),
    }))


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main()
