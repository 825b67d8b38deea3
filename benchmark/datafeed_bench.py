"""Datafeed benchmark: data-fed throughput vs the in-graph ceiling.

`bench.py`'s headline number generates batches IN-GRAPH (`bench_span`), so
it measures pure compute; real training pays host->device staging. This
bench tracks the gap as a number, on the same model/batch:

- **ingraph**: `trainer.bench_span` img/s — the compute ceiling.
- **datafed**: host numpy batches through a depth-K :class:`DeviceFeed`
  into `trainer.step_stream` chunked spans — staging overlapped with
  compute, the path this PR exists to make fast.
- **span**: the same batches through `trainer.step_many` — the identical
  compiled program with its staging paid UP FRONT per span (datafed/span
  isolates what the pipeline adds/removes around the span program).
- **naive**: the same batches through per-call `trainer.step()` — staging
  serialized with compute, span length 1 (the pre-datafeed data path).

CPU-oracle caveat (recorded in the artifact): on the virtual 8-device CPU
mesh the ingraph number is threefry-dominated (in-graph batch generation
costs more than the model) and XLA-CPU runs scan spans several times
slower than the unrolled per-step program, so ratios against ingraph/naive
only mean something on the chip; the CPU-meaningful number is
datafed_vs_span ~= 1.0 (the pipeline adds no overhead around the span)
plus the staged-ahead contract pinned by tests/test_datafeed.py.

Writes `benchmark/DATAFEED.json` and prints ONE JSON line (the bench.py
artifact convention). Env knobs match bench.py: BENCH_BATCH (32),
BENCH_FUSED (steps per compiled span/chunk, 8), BENCH_REPEAT (timed spans,
4), BENCH_IMAGE (224 on the chip, 32 on CPU), plus BENCH_DEPTH
(MXNET_DATAFEED_DEPTH override) and BENCH_MODEL (resnet50 | cnn).

Usage::

    python benchmark/datafeed_bench.py             # write DATAFEED.json
    python benchmark/datafeed_bench.py --quick     # fewer steps (smoke)
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
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.parallel import DeviceFeed  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _make_net(model, image):
    if model == "resnet50":
        from mxnet_tpu.gluon.model_zoo import vision
        net = vision.resnet50_v1()
    else:  # "cnn": small conv net for the CPU oracle
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(16, 3, padding=1, in_channels=3),
                    nn.BatchNorm(in_channels=16),
                    nn.Activation("relu"),
                    nn.Conv2D(32, 3, padding=1, in_channels=16),
                    nn.Activation("relu"),
                    nn.GlobalAvgPool2D(),
                    nn.Dense(10, in_units=32))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, image, image)))
    return net


def _make_trainer(model, image, mesh):
    mx.random.seed(0)
    np.random.seed(0)
    net = _make_net(model, image)
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9}, mesh=mesh)


def _host_batches(n, batch, image, classes, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.standard_normal((batch, 3, image, image)).astype("float32"),
             rng.randint(0, classes, batch).astype("float32"))
            for _ in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "DATAFEED.json"))
    args = ap.parse_args()

    import jax
    platform = jax.devices()[0].platform
    on_cpu = platform == "cpu"

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    chunk = int(os.environ.get("BENCH_FUSED", "8"))
    repeat = int(os.environ.get("BENCH_REPEAT", "2" if args.quick else "4"))
    image = int(os.environ.get("BENCH_IMAGE", "32" if on_cpu else "224"))
    # depth >= chunk keeps each span fully resident before it dispatches
    # (docs/performance.md tuning rule)
    depth = int(os.environ.get("BENCH_DEPTH", str(
        max(chunk, mx.config.get("MXNET_DATAFEED_DEPTH")))))
    model = os.environ.get("BENCH_MODEL", "cnn" if on_cpu else "resnet50")
    classes = 1000 if model == "resnet50" else 10
    steps = chunk * repeat

    log("platform=%s model=%s batch=%d image=%d chunk=%d depth=%d steps=%d"
        % (platform, model, batch, image, chunk, depth, steps))
    mesh = parallel.make_mesh(dp=1) if not on_cpu else parallel.make_mesh()
    shape = (batch, 3, image, image)

    # -- in-graph ceiling (bench.py's program: data generated in the scan) --
    tr = _make_trainer(model, image, mesh)
    tr.bench_span(chunk, shape, classes).asnumpy()  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(repeat):
        l = tr.bench_span(chunk, shape, classes)
    l.asnumpy()  # device->host copy bounds the measurement (PERF.md)
    ingraph = batch * steps / (time.perf_counter() - t0)
    log("ingraph  %10.2f img/s" % ingraph)

    # -- data-fed: DeviceFeed ring + step_stream chunked spans --------------
    tr = _make_trainer(model, image, mesh)
    warm = _host_batches(chunk, batch, image, classes, seed=1)
    tr.step_stream(iter(warm), chunk=chunk).asnumpy()  # compile + warmup
    batches = _host_batches(steps, batch, image, classes, seed=2)
    feed = DeviceFeed(batches, mesh=mesh, depth=depth, name="bench")
    feed.prefill()
    t0 = time.perf_counter()
    l = tr.step_stream(feed, chunk=chunk)
    l.asnumpy()
    datafed = batch * steps / (time.perf_counter() - t0)
    stats = feed.stats()
    feed.close()
    log("datafed  %10.2f img/s  (stage waits %d, %.1f MB staged)"
        % (datafed, stats["stage_waits"], stats["bytes_staged"] / 1e6))

    # -- span: step_many, same compiled program, staging paid up front ------
    tr = _make_trainer(model, image, mesh)
    wx = np.stack([b[0] for b in warm])
    wy = np.stack([b[1] for b in warm])
    tr.step_many(mx.nd.array(wx), mx.nd.array(wy)).asnumpy()  # compile
    sx = [np.stack([b[0] for b in batches[c * chunk:(c + 1) * chunk]])
          for c in range(repeat)]
    sy = [np.stack([b[1] for b in batches[c * chunk:(c + 1) * chunk]])
          for c in range(repeat)]
    t0 = time.perf_counter()
    for c in range(repeat):
        l = tr.step_many(mx.nd.array(sx[c]), mx.nd.array(sy[c]))
    l.asnumpy()
    span = batch * steps / (time.perf_counter() - t0)
    log("span     %10.2f img/s" % span)

    # -- naive: per-call step(), staging serialized with compute ------------
    tr = _make_trainer(model, image, mesh)
    x, y = warm[0]
    tr.step(mx.nd.array(x), mx.nd.array(y)).asnumpy()  # compile + warmup
    t0 = time.perf_counter()
    for x, y in batches:
        l = tr.step(mx.nd.array(x), mx.nd.array(y))
    l.asnumpy()
    naive = batch * steps / (time.perf_counter() - t0)
    log("naive    %10.2f img/s" % naive)

    artifact = {
        "platform": platform,
        "model": model,
        "batch": batch,
        "image": image,
        "steps": steps,
        "chunk": chunk,
        "depth": depth,
        "ingraph_img_s": round(ingraph, 2),
        "datafed_img_s": round(datafed, 2),
        "span_img_s": round(span, 2),
        "naive_step_img_s": round(naive, 2),
        "datafed_vs_ingraph": round(datafed / ingraph, 3),
        "datafed_vs_span": round(datafed / span, 3),
        "datafed_vs_naive": round(datafed / naive, 3),
        "stage_waits": stats["stage_waits"],
        "bytes_staged": stats["bytes_staged"],
    }
    if on_cpu:
        artifact["cpu_caveat"] = (
            "virtual-mesh oracle: ingraph is threefry-dominated and "
            "XLA-CPU runs scan spans slower than unrolled steps — "
            "datafed_vs_span is the meaningful ratio here; chip runs "
            "compare against ingraph")
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    log("wrote %s" % args.out)

    print(json.dumps({
        "metric": "%s_datafed_img_per_sec_b%d" % (model, batch),
        "value": round(datafed, 2),
        "unit": "img/s",
        "vs_ingraph": round(datafed / ingraph, 3),
        "vs_span": round(datafed / span, 3),
        "vs_naive": round(datafed / naive, 3),
    }))


if __name__ == "__main__":
    main()
