"""Model-parallel MLP: layers placed on different devices via group2ctx.

Role parity: reference `example/model-parallel/` (the LSTM/matrix-fact
examples split a model's LAYERS across GPUs with `group2ctx`; activations
cross devices at group boundaries while each device holds only its own
parameters).

TPU-native notes: on a TPU pod the same placement maps stages onto mesh
slices and XLA inserts the ICI transfers; here the runnable demo uses the
virtual CPU mesh (`XLA_FLAGS=--xla_force_host_platform_device_count=8`)
exactly like the test-suite does, so the placement machinery — symbol
`ctx_group` attrs, executor `group2ctx` device resolution, cross-device
forward AND backward — is fully exercised on any host. For production
pipeline-parallel training see `mxnet_tpu.parallel` (GPipe ppermute ring),
which subsumes this per-layer style at scale.

Usage:  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        JAX_PLATFORMS=cpu python mlp_model_parallel.py [--steps 200]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


def build_split_mlp(hidden=64, classes=10):
    """Stage 1 (dev1): input -> hidden; stage 2 (dev2): hidden -> logits.
    The `ctx_group` attr on each scope is the reference's placement
    annotation (symbol.py AttrScope(ctx_group=...))."""
    with sym.AttrScope(ctx_group="dev1"):
        data = sym.var("data")
        h = sym.Activation(
            sym.FullyConnected(data, num_hidden=hidden, name="fc1"),
            act_type="relu", name="act1")
    with sym.AttrScope(ctx_group="dev2"):
        label = sym.var("softmax_label")
        logits = sym.FullyConnected(h, num_hidden=classes, name="fc2")
        net = sym.SoftmaxOutput(logits, label, name="softmax")
    return net


def train(steps=200, batch=32, in_dim=20, classes=10, lr=0.1, log=print):
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            "need >=2 devices: run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=2")
    group2ctx = {"dev1": mx.cpu(0) if devs[0].platform == "cpu"
                 else mx.tpu(0),
                 "dev2": mx.cpu(1) if devs[1].platform == "cpu"
                 else mx.tpu(1)}

    rng = np.random.RandomState(0)
    w_true = rng.randn(in_dim, classes).astype("float32")
    n_data = 16 * batch  # fixed dataset, cycled over epochs
    x_all = rng.randn(n_data, in_dim).astype("float32")
    y_all = (x_all @ w_true).argmax(axis=1).astype("float32")

    net = build_split_mlp(classes=classes)
    # bind with explicit group2ctx placement
    arg_shapes, _, _ = net.infer_shape(data=(batch, in_dim),
                                       softmax_label=(batch,))
    args = {n: nd.array(rng.uniform(-0.1, 0.1, s).astype("float32"))
            for n, s in zip(net.list_arguments(), arg_shapes)}
    grads = {n: nd.zeros(s)
             for n, s in zip(net.list_arguments(), arg_shapes)}
    ex = net.bind(mx.cpu(0), args, args_grad=grads, group2ctx=group2ctx)

    # both stages really resolved to distinct devices
    placed = set(ex._placement.values())
    assert len(placed) == 2, "expected 2 distinct devices, got %r" % placed

    first = last = None
    for step in range(steps):
        s = (step * batch) % n_data
        args["data"][:] = x_all[s:s + batch]
        args["softmax_label"][:] = y_all[s:s + batch]
        out = ex.forward(is_train=True)[0]
        ex.backward()
        p = out.asnumpy()
        loss = -np.log(np.maximum(
            p[np.arange(batch), y_all[s:s + batch].astype(int)], 1e-8)
        ).mean()
        if first is None:
            first = loss
        last = loss
        for name in ("fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"):
            args[name] -= lr * grads[name]
        if step % 50 == 0:
            log("step %d loss %.4f" % (step, loss))
    log("loss %.4f -> %.4f (stages on %d devices)"
        % (first, last, len(placed)))
    return first, last, len(placed)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    train(steps=args.steps)
