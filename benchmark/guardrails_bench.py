"""Guardrails benchmark: what the in-step guard costs and what it buys.

Two measurements over the same sharded training setup (MLP classifier,
Adam, 8-device CPU mesh oracle or the real chip):

- **overhead**: steady-state per-step wall time, unguarded
  ``ShardedTrainer.step`` vs ``GuardedStep`` (all-finite reduction +
  where-selects fused into the same compiled program). The guard is a few
  extra fused element-wise ops — the artifact records the measured ratio.
- **recovery**: a fresh guarded run with a seeded 2% ``nan`` fault rate
  armed on the ``trainer.grads`` chaos point. The claim the committed
  ``benchmark/GUARDRAILS.json`` backs: **100% of injected-NaN steps are
  skipped** (skip counter == chaos fire counter), parameters stay finite,
  and the run still converges (final loss window well below the initial
  window) — the same stream through the UNGUARDED trainer ends with NaN
  parameters on the first poisoned step.

Usage::

    python benchmark/guardrails_bench.py            # write GUARDRAILS.json
    python benchmark/guardrails_bench.py --quick    # fewer steps (smoke)
    python benchmark/guardrails_bench.py --fault-rate 0.05
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, parallel  # noqa: E402
from mxnet_tpu.resilience import GuardedStep, chaos  # noqa: E402

BATCH, D_IN, D_HID, N_CLS = 64, 128, 256, 10


def _make_trainer(seed):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(D_HID, activation="relu"),
            gluon.nn.Dense(D_HID, activation="relu"),
            gluon.nn.Dense(N_CLS))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, D_IN)))
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=parallel.make_mesh())


def _batches(n, seed):
    rng = np.random.RandomState(seed)
    w = rng.standard_normal((D_IN, N_CLS)).astype("float32")
    out = []
    for _ in range(n):
        x = rng.standard_normal((BATCH, D_IN)).astype("float32")
        y = np.argmax(x @ w + rng.standard_normal((BATCH, N_CLS)) * 0.1,
                      axis=1).astype("float32")
        out.append((mx.nd.array(x), mx.nd.array(y)))
    return out


def _time_steps(stepper, batches, warmup):
    for x, y in batches[:warmup]:
        stepper.step(x, y)
    t0 = time.perf_counter()
    last = None
    for x, y in batches[warmup:]:
        last = stepper.step(x, y)
    np.asarray(last._data)  # drain the async dispatch queue before stopping
    total = time.perf_counter() - t0
    return total / (len(batches) - warmup)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--fault-rate", type=float, default=0.02)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "GUARDRAILS.json"))
    args = ap.parse_args()
    steps = 60 if args.quick else args.steps

    import jax
    platform = jax.devices()[0].platform
    chaos.clear()

    batches = _batches(steps + args.warmup, seed=0)

    plain = _make_trainer(seed=0)
    t_plain = _time_steps(plain, batches, args.warmup)
    print("unguarded  %8.3f ms/step" % (t_plain * 1e3))

    guarded = GuardedStep(_make_trainer(seed=0), detector=False,
                          name="bench.overhead")
    t_guard = _time_steps(guarded, batches, args.warmup)
    guarded.flush()
    overhead = (t_guard - t_plain) / t_plain
    print("guarded    %8.3f ms/step  (overhead %+.1f%%)"
          % (t_guard * 1e3, overhead * 100))
    assert guarded.skipped_steps == 0

    # recovery under a seeded nan-fault rate: every poisoned step must be
    # skipped, params must stay finite, training must still converge
    chaos.arm("trainer.grads", "nan", p=args.fault_rate, seed=0)
    rec = GuardedStep(_make_trainer(seed=0), detector=False,
                      name="bench.recovery")
    losses = []
    for x, y in batches:
        losses.append(float(np.asarray(rec.step(x, y)._data)))
    rec.flush()
    fires = chaos.stats()["trainer.grads"]["fires"]
    chaos.clear()
    finite = [l for l in losses if np.isfinite(l)]
    head = float(np.mean(finite[: max(3, len(finite) // 10)]))
    tail = float(np.mean(finite[-max(3, len(finite) // 10):]))
    params_finite = all(np.isfinite(np.asarray(v)).all()
                        for v in rec.trainer._values)
    print("faulted    fires %d  skipped %d  loss %.4f -> %.4f  "
          "params finite: %s" % (fires, rec.skipped_steps, head, tail,
                                 params_finite))

    artifact = {
        "platform": platform,
        "model": "mlp %d-%d-%d-%d adam" % (D_IN, D_HID, D_HID, N_CLS),
        "batch": BATCH,
        "steps": steps,
        "unguarded_ms_per_step": round(t_plain * 1e3, 3),
        "guarded_ms_per_step": round(t_guard * 1e3, 3),
        "guard_overhead_pct": round(overhead * 100, 2),
        "injected_fault_rate": args.fault_rate,
        "injection_point": "trainer.grads",
        "recovery": {
            "injected_nan_steps": fires,
            "skipped_steps": rec.skipped_steps,
            "all_injected_skipped": rec.skipped_steps == fires,
            "params_finite": params_finite,
            "initial_loss": round(head, 4),
            "final_loss": round(tail, 4),
            "converged": tail < head,
        },
    }
    from benchmark._artifact import stamp
    artifact = stamp(artifact)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print("wrote %s (platform=%s, %d/%d injected NaN steps skipped, "
          "converged=%s)" % (args.out, platform, rec.skipped_steps, fires,
                             artifact["recovery"]["converged"]))


if __name__ == "__main__":
    main()
