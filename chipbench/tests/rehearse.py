"""CPU rehearsal of a cell through the harness's own code, at the tiny
configurations beside this file. It cannot pass for a chip run: it prints
the platform it ran on and the NAMES of the metrics it could read, never a
time, a rate or a share.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m chipbench.tests.rehearse --workload bert_tiny_train [--trace 1]
"""
import argparse
import json
import os
import sys

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))


def rehearse(workload, seed=1, seconds=1.0, trace=False):
    import jax
    index = run.load_json(os.path.join(HERE, "BENCHMARK.tiny.json"))
    result = run.run_cell(index, workload, seed, seconds, trace, jax.devices(),
                          workload_dir=os.path.join(HERE, "workloads"),
                          rehearsal=True)
    return {"rehearsal": True, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "platform": result["device"]["platform"],
            "metrics_read": sorted(result["metrics"]),
            "compared": result["compared"]}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    out = rehearse(args.workload, args.seed, trace=bool(args.trace))
    print(json.dumps(out))
    sys.exit(0 if out["correct"] else 1)
