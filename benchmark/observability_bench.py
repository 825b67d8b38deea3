"""Tracer-overhead benchmark: serving, step_stream, ``ShardedTrainer.step``
and ``NDArray.asnumpy`` paths, disabled vs. enabled, written to
``benchmark/OBSERVABILITY.json``.

Two costs matter and are measured separately:

- **disabled overhead** — what the always-present instrumentation costs
  when tracing is OFF (the production default). Measured as the per-call
  cost of the disabled fast path (one attribute check returning a shared
  no-op) times the number of tracer calls each operation actually makes
  (counted from an enabled run), expressed as a percentage of the
  operation's measured time. The bench **asserts this is < 2%** — the
  contract that makes it safe to leave the instrumentation in every hot
  path.
- **enabled overhead** — throughput with recording on vs. off, for
  sizing "can I trace in production". Recorded, not asserted: it depends
  on span density and is paid only while a trace session runs.

The committed artifact is the CPU-oracle run (``"platform"`` recorded
inside); rerun on a TPU host for chip numbers.

Usage::

    python benchmark/observability_bench.py           # write the artifact
    python benchmark/observability_bench.py --quick   # fewer reps (smoke)
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

import jax  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, nd, parallel  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.observability import tracer as tr  # noqa: E402
from mxnet_tpu.parallel import DeviceFeed  # noqa: E402
from mxnet_tpu.serving import DynamicBatcher, InferenceEngine  # noqa: E402

D_IN, D_HID, D_OUT = 64, 128, 16


def _measure_disabled_call_ns(iters=200000):
    """Per-call cost of the disabled fast path (span open+close),
    measured with one attribute kwarg — real instrumentation sites pass
    attrs whose packing happens before span() can return the shared
    no-op, so a bare call would understate the true cost."""
    assert not tr.enabled()
    n = 0
    t0 = time.perf_counter()
    for _ in range(iters):
        with tr.span("bench.noop", t=n):
            n += 1
    return (time.perf_counter() - t0) / iters * 1e9


def _serving_setup():
    rng = np.random.default_rng(0)
    W1 = nd.array(rng.standard_normal((D_IN, D_HID)).astype("float32"))
    W2 = nd.array(rng.standard_normal((D_HID, D_OUT)).astype("float32"))

    def fn(x):
        return nd.dot(nd.relu(nd.dot(x, W1)), W2)

    engine = InferenceEngine(fn, buckets=(1, 2, 4), retry_policy=False)
    engine.warmup(np.zeros((1, D_IN), "float32"))
    return engine


def _bench_serving(engine, requests):
    batcher = DynamicBatcher(engine, max_batch_size=4, max_latency_ms=0.2,
                             retry_policy=False)
    try:
        x = np.random.randn(D_IN).astype("float32")
        batcher.predict(x)  # settle the path
        t0 = time.perf_counter()
        for _ in range(requests):
            batcher.predict(x)
        dt = time.perf_counter() - t0
    finally:
        batcher.close()
    return requests / dt, dt / requests


def _stream_setup():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=16),
                nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh())
    return trainer


def _bench_stream(trainer, steps, chunk=4):
    rng = np.random.RandomState(0)
    batches = [(rng.standard_normal((32, 16)).astype("float32"),
                rng.randint(0, 4, 32).astype("float32"))
               for _ in range(steps)]
    with DeviceFeed(batches, mesh=trainer.mesh, depth=4,
                    name="obs.bench") as feed:
        t0 = time.perf_counter()
        losses = trainer.step_stream(feed, chunk=chunk)
        float(np.asarray(losses)[-1])  # block on the last dispatch
        dt = time.perf_counter() - t0
    return steps / dt, dt / steps


def _bench_step(trainer, steps):
    """``ShardedTrainer.step`` in a user's loop, the loss read two steps
    behind (``NDArray.asnumpy``), as the chip benchmark's cells drive it."""
    rng = np.random.RandomState(0)
    x = nd.array(rng.standard_normal((32, 16)).astype("float32"))
    y = nd.array(rng.randint(0, 4, 32).astype("float32"))
    pending = [trainer.step(x, y), trainer.step(x, y)]  # compile, settle
    t0 = time.perf_counter()
    for _ in range(steps):
        pending.append(trainer.step(x, y))
        float(pending.pop(0).asnumpy())
    for p in pending:
        float(p.asnumpy())
    dt = time.perf_counter() - t0
    return steps / dt, dt / steps


def _measure_asnumpy_ns(iters):
    """Per-call time of ``NDArray.asnumpy`` on small ready values, each
    read once as a loop reads its losses (a second read of one array
    returns the host copy jax keeps), and of the flag test it makes first
    with the tracer off: one attribute chain and a compare, the empty
    loop's time taken off."""
    iters = min(iters, 20000)
    arrays = [nd.array(np.full((4,), i, "float32")) for i in range(iters)]
    nd.waitall()
    t0 = time.perf_counter()
    for arr in arrays:
        arr.asnumpy()
    call_ns = (time.perf_counter() - t0) / iters * 1e9
    tracer = tr.tracer
    t0 = time.perf_counter()
    for arr in arrays:
        if tracer._enabled:
            raise AssertionError("the tracer is on")
    flagged = time.perf_counter() - t0
    t0 = time.perf_counter()
    for arr in arrays:
        pass
    empty = time.perf_counter() - t0
    return call_ns, max(0.0, flagged - empty) / iters * 1e9


def _tracer_calls_per_op(ops):
    """Spans+instants recorded per operation during an enabled run — the
    multiplier for the disabled-path cost model."""
    return tr.event_count() / max(1, ops)


def run(quick=False):
    requests = 100 if quick else 400
    steps = 16 if quick else 64
    micro_iters = 50000 if quick else 200000

    tr.disable()
    tr.clear()
    tr.reset_phase_stats()
    disabled_ns = _measure_disabled_call_ns(micro_iters)

    out = {"platform": jax.devices()[0].platform,
           "disabled_tracer_ns_per_call": disabled_ns}

    # ---- serving path -----------------------------------------------------
    engine = _serving_setup()
    qps_off, per_req_off = _bench_serving(engine, requests)
    tr.enable()
    tr.clear()
    qps_on, per_req_on = _bench_serving(engine, requests)
    calls_per_req = _tracer_calls_per_op(requests)
    tr.disable()
    tr.clear()
    disabled_pct = disabled_ns * 1e-9 * calls_per_req / per_req_off * 100.0
    out["serving"] = {
        "requests": requests,
        "qps_disabled": qps_off,
        "qps_enabled": qps_on,
        # signed on purpose: a negative value means the measurement is
        # warmup/noise-dominated, which the reader should SEE, not have
        # laundered into a confident-looking 0.0
        "enabled_overhead_pct": (per_req_on - per_req_off)
        / per_req_off * 100.0,
        "tracer_calls_per_request": calls_per_req,
        "disabled_overhead_pct": disabled_pct,
    }

    # ---- step_stream path -------------------------------------------------
    trainer = _stream_setup()
    _bench_stream(trainer, steps)  # compile warmup (span programs)
    sps_off, per_step_off = _bench_stream(trainer, steps)
    tr.enable()
    tr.clear()
    sps_on, per_step_on = _bench_stream(trainer, steps)
    calls_per_step = _tracer_calls_per_op(steps)
    tr.disable()
    tr.clear()
    disabled_pct_s = (disabled_ns * 1e-9 * calls_per_step
                      / per_step_off * 100.0)
    out["step_stream"] = {
        "steps": steps,
        "steps_per_s_disabled": sps_off,
        "steps_per_s_enabled": sps_on,
        "enabled_overhead_pct": (per_step_on - per_step_off)
        / per_step_off * 100.0,
        "tracer_calls_per_step": calls_per_step,
        "disabled_overhead_pct": disabled_pct_s,
    }
    # ---- ShardedTrainer.step + NDArray.asnumpy (PR 35) --------------------
    # the step's three no-op span() calls and asnumpy's flag test, by the
    # same model: calls counted from an enabled run (the wait span among
    # them) times the disabled call's cost, over the step's measured time
    trainer = _stream_setup()
    sps_off, per_step_off = _bench_step(trainer, steps)
    tr.enable()
    tr.clear()
    sps_on, per_step_on = _bench_step(trainer, steps)
    calls_per_step = _tracer_calls_per_op(steps)
    tr.disable()
    tr.clear()
    out["trainer_step"] = {
        "steps": steps,
        "steps_per_s_disabled": sps_off,
        "steps_per_s_enabled": sps_on,
        "enabled_overhead_pct": (per_step_on - per_step_off)
        / per_step_off * 100.0,
        "tracer_calls_per_step": calls_per_step,
        "disabled_overhead_pct": (disabled_ns * 1e-9 * calls_per_step
                                  / per_step_off * 100.0),
    }
    asnumpy_ns, flag_ns = _measure_asnumpy_ns(micro_iters)
    out["asnumpy"] = {
        "ns_per_call_disabled": asnumpy_ns,
        "flag_test_ns": flag_ns,
        "disabled_overhead_pct": flag_ns / asnumpy_ns * 100.0,
    }

    out["note"] = ("enabled_overhead_pct is signed: negative means the "
                   "enabled run beat the disabled one, i.e. the "
                   "measurement is warmup/noise-dominated on this "
                   "platform; the asserted contract is "
                   "disabled_overhead_pct only")

    # ---- attribution fast path (roofline accounting, PR 12) ---------------
    # the per-dispatch cost of record_dispatch() — one lock + four float
    # adds into the roofline registry plus one flight-ring append —
    # modeled against the measured per-request time, same methodology as
    # the disabled-tracer budget above. The serving path makes ~1
    # CachedOp dispatch per request (batching amortizes below that), so
    # cost-per-record IS the per-request attribution overhead bound.
    from mxnet_tpu.observability import attribution as attr
    attr.configure()
    assert attr.attribution_enabled(), \
        "attribution must be on (default) for the overhead measurement"
    attr_iters = 50000 if quick else 200000
    t0 = time.perf_counter()
    for _ in range(attr_iters):
        attr.record_dispatch("obs_bench_attr", "sig|train=False", 4,
                             1e6, 5e5, 1e-6)
    attr_ns = (time.perf_counter() - t0) / attr_iters * 1e9
    attr.roofline.reset()   # drop the synthetic row
    attr_pct = attr_ns * 1e-9 / per_req_off * 100.0
    out["attribution"] = {
        "record_ns_per_dispatch": attr_ns,
        "dispatch_overhead_pct": attr_pct,
    }
    assert attr_pct < 1.0, (
        "attribution fast path costs %.3f%% of a serving request — "
        "over the 1%% dispatch-overhead budget" % attr_pct)

    worst = max(out[path]["disabled_overhead_pct"] for path in
                ("serving", "step_stream", "trainer_step", "asnumpy"))
    out["disabled_overhead_worst_pct"] = worst
    out["pass"] = worst < 2.0 and attr_pct < 1.0
    assert worst < 2.0, (
        "disabled tracer overhead %.3f%% exceeds the 2%% budget" % worst)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "OBSERVABILITY.json"))
    args = ap.parse_args(argv)
    out = run(quick=args.quick)
    from benchmark._artifact import stamp
    out = stamp(out, platform=out.get("platform"))
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print("wrote %s" % args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
