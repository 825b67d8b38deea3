"""Profiling a training loop with the mx.profiler API.

Role parity: reference `example/profiler/profiler_executor.py` /
`profiler_ndarray.py`: turn the profiler on around a training region,
dump, and read where the time went.

TPU-native notes: `mx.profiler` fronts jax.profiler — the dump is an
XPlane trace (view in TensorBoard or Perfetto) containing XLA fusion
timings on the device, not per-op host timings: under XLA the unit of
execution IS the fused program (this produced PERF.md's profiler study).
Custom scopes land in the trace via `profiler.scope`/`record_function`.

Usage:  python profile_training.py [--steps 30] [--outdir /tmp/mxtpu_prof]
"""
import argparse
import glob
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon, nd


def train_profiled(steps=30, outdir="/tmp/mxtpu_prof", log=print):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = rng.randn(steps, 64, 32).astype("float32")
    y = rng.randint(0, 10, (steps, 64)).astype("float32")

    # warm up OUTSIDE the profiled region so the trace holds steady-state
    # steps, not compiles (reference examples skip the first batch too)
    with ag.record():
        loss = loss_fn(net(nd.array(x[0])), nd.array(y[0])).mean()
    loss.backward()
    trainer.step(1)
    loss.asnumpy()

    mx.profiler.set_config(profile_all=True,
                           filename=os.path.join(outdir, "profile.json"))
    mx.profiler.set_state("run")
    for i in range(steps):
        with ag.record():
            loss = loss_fn(net(nd.array(x[i])), nd.array(y[i])).mean()
        loss.backward()
        trainer.step(1)
    loss.asnumpy()          # drain before stopping the trace
    mx.profiler.set_state("stop")
    mx.profiler.dump()

    traces = glob.glob(os.path.join(outdir, "**", "*.xplane.pb"),
                       recursive=True) + \
        glob.glob(os.path.join(outdir, "**", "*.trace.json*"),
                  recursive=True)
    log("profiled %d steps -> %d trace file(s) under %s"
        % (steps, len(traces), outdir))
    for t in traces[:3]:
        log("  ", t, os.path.getsize(t), "bytes")
    return traces


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--outdir", default="/tmp/mxtpu_prof")
    args = ap.parse_args()
    train_profiled(steps=args.steps, outdir=args.outdir)
