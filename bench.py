"""Benchmark round driver: sectioned, crash-isolated, one JSON line.

Headline section matches the reference's benchmark (`BASELINE.md`:
ResNet-50 training, batch 32, 298.51 img/s on 1x V100 fp32,
`docs/.../perf.md:252` in the reference tree). The training span is the
fused SPMD program from mxnet_tpu.parallel (ShardedTrainer.bench_span:
`lax.scan` over fwd+bwd+update steps, bf16 compute, fp32 BN stats), on a
dp=1 mesh — the TPU-idiomatic on-device training loop, which also
amortizes host->device dispatch latency.

Crash isolation: every section runs under its own try/except, so one
crashing section records ``{"status": "FAILED", "reason": ..., "tail":
[...]}`` and the others still report — but a round with a failed section
is a failed round: the JSON line is printed and the exit code is 1.
Sections:

- ``resnet50_train`` — the headline img/s (its fields are ALSO merged
  to the top level, so older round parsers keep working);
- ``roofline_attribution`` — the per-executable roofline table the
  train span populated (op, arithmetic intensity, achieved vs ceiling,
  bound-by classification);
- ``serving_probe`` — a small bucket-laddered serving engine's
  requests/s, so serving regressions surface in chip rounds too;
- ``elastic3d`` — the sharding planner's placement check.

Prints ONE JSON line; compare rounds with ``tools/bench_diff.py``.

This script measures the chip and nothing else: it refuses to start on
any platform other than ``tpu``.

Env knobs: BENCH_BATCH (32), BENCH_FUSED (steps per compiled span, 512),
BENCH_REPEAT (timed spans, 2), BENCH_IMAGE (224), BENCH_SECTIONS
(comma-separated subset, default all).
"""
import json
import os
import sys
import time
import traceback

_T0 = time.time()   # cold-start clock: everything after interpreter boot

import numpy as np

BASELINE_IMG_S = 298.51  # reference perf.md:252 (V100, fp32, batch 32)
RESNET50_TRAIN_GFLOP_PER_IMG = 12.3  # ~3x fwd (4.1 GFLOP @ 224x224)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _require_tpu():
    """The device list, or exit non-zero: a number from any other
    platform must never be published under the per-chip metric."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("bench.py measures the chip: jax came up on %r (%s), "
                 "not tpu" % (devs[0].platform, devs))
    return devs


def _peak_tflops(device):
    """Published bf16 peak of ``device`` (telemetry's table, keyed by
    ``device_kind``); a kind that is not in the table is an error."""
    from mxnet_tpu.observability import telemetry

    peak = telemetry.device_peaks(device)[0]
    if peak is None:
        raise RuntimeError("no published peak for device kind %r"
                           % (device.device_kind,))
    return peak / 1e12


# ---------------------------------------------------------------------------
# sections (each isolated by _run_sections)
# ---------------------------------------------------------------------------

def section_resnet50_train(ctx):
    batch = ctx["batch"]
    fused = int(os.environ.get("BENCH_FUSED", "512"))
    repeat = int(os.environ.get("BENCH_REPEAT", "2"))
    image = int(os.environ.get("BENCH_IMAGE", "224"))

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    np.random.seed(0)

    net = vision.resnet50_v1()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, image, image)))  # resolve deferred shapes
    net.cast("bfloat16")

    mesh = parallel.make_mesh(dp=1, devices=ctx["devices"][:1])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, loss_fn, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        mesh=mesh)

    # batches are generated IN-GRAPH (bench_span): the span length is then
    # bounded by compute, not by HBM residency of a staged input tensor
    log("compiling + warmup (1 span of %d steps)..." % fused)
    t0 = time.time()
    l = trainer.bench_span(fused, (batch, 3, image, image), 1000,
                           dtype="bfloat16")
    lv = l.asnumpy()  # full host sync
    # the cold-start trajectory, first-class (ROADMAP item 4): how long
    # until the FIRST useful step, and how much of that was compile+warm
    # — the number the persistent compile cache / AOT artifacts attack
    compile_s = time.time() - t0
    time_to_first_step_s = time.time() - _T0
    log("warmup done in %.1fs (%.1fs from process start), last loss=%.4f"
        % (compile_s, time_to_first_step_s, lv[-1]))

    t0 = time.time()
    for _ in range(repeat):
        l = trainer.bench_span(fused, (batch, 3, image, image), 1000,
                               dtype="bfloat16")
    _ = l.asnumpy()  # host sync bounds the measurement
    dt = time.time() - t0
    imgs = batch * fused * repeat
    img_s = imgs / dt
    tflops = img_s * RESNET50_TRAIN_GFLOP_PER_IMG / 1e3
    log("%.2f img/s  |  est %.1f TFLOP/s  |  est MFU %.1f%% of bf16 peak"
        % (img_s, tflops,
           100.0 * tflops / _peak_tflops(ctx["devices"][0])))

    return {
        "metric": "resnet50_train_img_per_sec_per_chip_b%d" % batch,
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "time_to_first_step_s": round(time_to_first_step_s, 2),
        "compile_s": round(compile_s, 2),
    }


def section_roofline_attribution(ctx):
    """The attribution plane's verdict on everything the round has
    dispatched so far (the train span, mostly): top executables by
    dispatch time with AI + bound-by — the chip round's answer to
    'WHICH programs do I write Pallas kernels for'."""
    from mxnet_tpu.observability import attribution

    rows = attribution.snapshot()[:8]
    return {
        "ridge_flop_per_byte": attribution.ridge_point(),
        "executables": [
            {"op": r["op"], "bucket": r["bucket"], "calls": r["calls"],
             "total_s": round(r["total_s"], 4),
             "ai": round(r["ai"], 3),
             "achieved_gflops": round(r["achieved_flops_s"] / 1e9, 3),
             "ceiling_gflops": (round(r["ceiling_flops_s"] / 1e9, 3)
                                if r["ceiling_flops_s"] else None),
             "bound": r["bound"],
             "pct_of_total": round(r["pct_of_total"], 1)}
            for r in rows],
    }


def section_serving_probe(ctx):
    """Small bucket-laddered serving engine requests/s — cheap enough
    for every chip round, so serving regressions stop hiding behind the
    train headline."""
    import mxnet_tpu as mx  # noqa: F401 — backend already up
    from mxnet_tpu import nd
    from mxnet_tpu.serving import DynamicBatcher, InferenceEngine

    rng = np.random.default_rng(0)
    w1 = nd.array(rng.standard_normal((256, 512)).astype("float32"))
    w2 = nd.array(rng.standard_normal((512, 64)).astype("float32"))

    def model(x):
        return nd.dot(nd.relu(nd.dot(x, w1)), w2)

    requests = int(os.environ.get("BENCH_SERVING_REQUESTS", "200"))
    engine = InferenceEngine(model, buckets=(1, 4, 8),
                             retry_policy=False, name="bench_serving")
    engine.warmup(np.zeros((1, 256), "float32"))
    batcher = DynamicBatcher(engine, max_batch_size=8,
                             max_latency_ms=0.5, retry_policy=False)
    try:
        x = rng.standard_normal(256).astype("float32")
        batcher.predict(x)  # settle the path
        t0 = time.perf_counter()
        for _ in range(requests):
            batcher.predict(x)
        dt = time.perf_counter() - t0
    finally:
        batcher.close()
    return {"metric": "serving_probe_requests_per_sec",
            "value": round(requests / dt, 2), "unit": "req/s",
            "requests": requests}


def section_elastic3d(ctx):
    """Sharding-planner placement check (ISSUE-15): on the memory-
    constrained MoE config at this round's device count, the planner's
    dp x pp x ep placement vs pure-dp — modeled bytes/device (the
    portable signal) plus measured step time, and the zero-drift guard
    (no new compiles in existing CachedOp paths). The full supervised
    recovery drill stays in benchmark/planner_bench.py (subprocess-
    heavy; writes ELASTIC3D.json)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmark.planner_bench import bench_placement

    return bench_placement(steps=6)


SECTIONS = (
    ("resnet50_train", section_resnet50_train),
    ("serving_probe", section_serving_probe),
    ("elastic3d", section_elastic3d),
    # it summarizes every CachedOp dispatch the round made (the serving
    # probe's ladder, any hybridized block)
    ("roofline_attribution", section_roofline_attribution),
)


def _run_sections(sections, ctx=None):
    """Run each (name, fn) under its own try/except. A crash records a
    FAILED entry (reason + traceback tail) and the loop continues —
    one dead section must never zero the round's other signal."""
    ctx = ctx or {}
    out = {}
    for name, fn in sections:
        t0 = time.perf_counter()
        try:
            res = fn(ctx)
            if not isinstance(res, dict):
                res = {"result": res}
            res.setdefault("status", "OK")
        except (SystemExit, KeyboardInterrupt):
            raise
        except BaseException as e:  # noqa: BLE001 — isolation is the point
            tb = traceback.format_exc().splitlines()
            log("section %s FAILED: %s: %s" % (name, type(e).__name__, e))
            res = {"status": "FAILED",
                   "reason": "%s: %s" % (type(e).__name__, e),
                   "tail": tb[-6:]}
        # bookkeeping, not a performance metric: named so bench_diff's
        # direction heuristics classify it informational (a section's
        # wall includes one-off compiles/warmup — gating on it at 5%
        # would fail CI on machine-load noise)
        res["wall_clock"] = round(time.perf_counter() - t0, 3)
        out[name] = res
    return out


def main():
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    selected = os.environ.get("BENCH_SECTIONS", "")
    wanted = [s.strip() for s in selected.split(",") if s.strip()] \
        if selected else None

    devices = _require_tpu()
    log("devices:", devices)

    sections = [(n, f) for n, f in SECTIONS
                if wanted is None or n in wanted]
    ctx = {"batch": batch, "devices": devices}
    results = _run_sections(sections, ctx)

    out = {
        "bench": "bench.py",
        "sections": results,
        "failed_sections": sorted(n for n, r in results.items()
                                  if r.get("status") != "OK"),
    }
    from benchmark._artifact import stamp
    stamp(out, platform=devices[0].platform,
          device_kind=getattr(devices[0], "device_kind", "") or "")
    # top-level back-compat: older round parsers read the headline
    # metric fields off the root object
    headline = results.get("resnet50_train", {})
    if headline.get("status") == "OK":
        for k in ("metric", "value", "unit", "vs_baseline",
                  "time_to_first_step_s", "compile_s"):
            if k in headline:
                out[k] = headline[k]
    print(json.dumps(out))
    if out["failed_sections"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
