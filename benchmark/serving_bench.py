"""Serving-path benchmark: throughput & latency vs. batch size/concurrency.

The committed ``benchmark/SERVING.json`` artifact is the CPU-oracle sweep
(``"platform"`` is recorded inside); rerun on a TPU host for chip numbers —
the protocol (bucket warmup excluded, per-request latency measured at the
client) is platform-correct either way.

Three measurements per configuration, all over the same model (a Dense
stack sized so per-dispatch overhead and compute are both visible):

- ``sequential``: one-at-a-time ``InferenceEngine.predict`` — the
  no-batching floor every other row is compared against.
- ``direct_batch``: full batches straight into the engine — the upper
  bound the batcher can approach when traffic saturates.
- ``batched c=K``: K requests kept in flight through ``DynamicBatcher``
  (waves of futures), reporting client-observed p50/p95/p99 latency and
  end-to-end throughput — the serving-path number.

Usage::

    python benchmark/serving_bench.py            # sweep + write SERVING.json
    python benchmark/serving_bench.py --quick    # fewer reps (smoke)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.serving import (DynamicBatcher, InferenceEngine,  # noqa: E402
                               ServingMetrics)

D_IN, D_HID, D_OUT = 256, 512, 64
BUCKETS = (1, 2, 4, 8, 16, 32)


def _model():
    rng = np.random.default_rng(0)
    W1 = nd.array(rng.standard_normal((D_IN, D_HID)).astype("float32"))
    W2 = nd.array(rng.standard_normal((D_HID, D_OUT)).astype("float32"))

    def fn(x):
        return nd.dot(nd.relu(nd.dot(x, W1)), W2)
    return fn


def bench_sequential(eng, x1, n):
    t0 = time.perf_counter()
    lats = []
    for _ in range(n):
        t1 = time.perf_counter()
        eng.predict(x1)[0].asnumpy()
        lats.append(time.perf_counter() - t1)
    total = time.perf_counter() - t0
    return total, lats


def bench_direct_batch(eng, bs, n_batches):
    xb = np.random.default_rng(1).standard_normal(
        (bs, D_IN)).astype("float32")
    eng.predict(xb)  # warm this bucket
    t0 = time.perf_counter()
    for _ in range(n_batches):
        eng.predict(xb)[0].asnumpy()
    total = time.perf_counter() - t0
    return total


def bench_batched(eng, sample, n, concurrency, max_batch, latency_ms):
    metrics = ServingMetrics()
    lats = []
    with DynamicBatcher(eng, max_batch_size=max_batch,
                        max_latency_ms=latency_ms,
                        metrics=metrics) as b:
        b.predict(sample)  # prime
        t0 = time.perf_counter()
        done = 0
        while done < n:
            wave = min(concurrency, n - done)
            t1 = time.perf_counter()
            futs = [b.submit(sample) for _ in range(wave)]
            for f in futs:
                f.result(timeout=60)
            lats.extend([time.perf_counter() - t1] * wave)
            done += wave
        total = time.perf_counter() - t0
        snap = metrics.snapshot()
    return total, lats, snap


def pct(lats, q):
    if not lats:
        return 0.0
    s = sorted(lats)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * len(s))) - 1))
    return s[idx] * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "SERVING.json"))
    args = ap.parse_args()
    n = 64 if args.quick else args.requests

    import jax
    platform = jax.devices()[0].platform

    eng = InferenceEngine(_model(), buckets=BUCKETS)
    print("warming %d buckets..." % len(BUCKETS))
    # cold-start split (ROADMAP item 4): compile+warm wall-clock and
    # restart-to-first-request are first-class artifact numbers, not
    # hidden inside an excluded warmup — coldstart_bench.py measures the
    # full restart paths (persistent cache / AOT) against this cold one
    t_warm0 = time.perf_counter()
    eng.warmup(np.zeros((1, D_IN), "float32"))
    compile_s = time.perf_counter() - t_warm0
    x1 = np.zeros((1, D_IN), "float32")
    t_first0 = time.perf_counter()
    eng.predict(x1)
    time_to_first_request_s = compile_s + time.perf_counter() - t_first0
    print("ladder warm in %.2fs (first request at %.2fs)"
          % (compile_s, time_to_first_request_s))
    sample = x1[0]

    rows = []
    seq_total, seq_lats = bench_sequential(eng, x1, n)
    seq_qps = n / seq_total
    rows.append({"mode": "sequential", "concurrency": 1, "batch_size": 1,
                 "requests": n, "qps": round(seq_qps, 2),
                 "p50_ms": round(pct(seq_lats, 50), 3),
                 "p95_ms": round(pct(seq_lats, 95), 3),
                 "p99_ms": round(pct(seq_lats, 99), 3),
                 "speedup_vs_sequential": 1.0})
    print("sequential            qps %8.1f  p50 %6.2fms"
          % (seq_qps, pct(seq_lats, 50)))

    for bs in (2, 4, 8, 16, 32):
        n_batches = max(4, n // bs)
        total = bench_direct_batch(eng, bs, n_batches)
        qps = n_batches * bs / total
        rows.append({"mode": "direct_batch", "concurrency": 1,
                     "batch_size": bs, "requests": n_batches * bs,
                     "qps": round(qps, 2),
                     "speedup_vs_sequential": round(qps / seq_qps, 2)})
        print("direct batch bs=%-3d   qps %8.1f  (%.2fx)"
              % (bs, qps, qps / seq_qps))

    for conc in (2, 4, 8, 16, 32):
        total, lats, snap = bench_batched(
            eng, sample, n, concurrency=conc,
            max_batch=min(conc, 32), latency_ms=10.0)
        qps = n / total
        rows.append({
            "mode": "dynamic_batcher", "concurrency": conc,
            "batch_size": min(conc, 32), "requests": n,
            "qps": round(qps, 2),
            "p50_ms": round(pct(lats, 50), 3),
            "p95_ms": round(pct(lats, 95), 3),
            "p99_ms": round(pct(lats, 99), 3),
            "avg_batch_size": round(snap["avg_batch_size"], 2),
            "batch_occupancy": round(snap["batch_occupancy"], 3),
            "speedup_vs_sequential": round(qps / seq_qps, 2)})
        print("batcher c=%-3d         qps %8.1f  p50 %6.2fms  p95 %6.2fms  "
              "avg_bs %.1f  (%.2fx)"
              % (conc, qps, pct(lats, 50), pct(lats, 95),
                 snap["avg_batch_size"], qps / seq_qps))

    artifact = {
        "platform": platform,
        "model": "dense %dx%dx%d relu" % (D_IN, D_HID, D_OUT),
        "buckets": list(BUCKETS),
        "requests_per_row": n,
        "coldstart": {
            "compile_s": round(compile_s, 3),
            "time_to_first_request_s": round(time_to_first_request_s, 3),
        },
        "engine_stats": eng.stats(),
        "rows": rows,
    }
    from benchmark._artifact import stamp
    artifact = stamp(artifact, platform=platform)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print("wrote %s (%d rows, platform=%s)"
          % (args.out, len(rows), platform))


if __name__ == "__main__":
    main()
