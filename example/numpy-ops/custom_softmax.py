"""Training with a numpy-implemented custom operator.

Role parity: reference `example/numpy-ops/custom_softmax.py`: the softmax
loss layer is replaced by a user-written CustomOp whose forward and
backward are plain numpy, registered with `mx.operator.register`, then
used inside a symbol graph and trained with Module — the "extend the
framework from Python without touching the engine" demo.

TPU-native notes: custom ops run as host callbacks outside the XLA
program (the reference's CustomOp runs on CPU outside the engine's
threads, same topology). Everything surrounding the custom node still
compiles to XLA; only the custom segment round-trips to host. Use this
for experimentation; promote hot ops to `mxnet_tpu.ops` (jnp/pallas) for
production speed.

Usage:  python custom_softmax.py [--epochs 6]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


class NumpySoftmax(mx.operator.CustomOp):
    """Softmax + cross-entropy gradient, all in numpy (reference
    example/numpy-ops/custom_softmax.py Softmax)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        x = x - x.max(axis=1, keepdims=True)
        e = np.exp(x)
        self.assign(out_data[0], req[0], e / e.sum(axis=1, keepdims=True))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        label = in_data[1].asnumpy().astype(int)
        p = out_data[0].asnumpy().copy()
        p[np.arange(p.shape[0]), label] -= 1.0
        # per-sample gradient; Module's rescale_grad divides by batch
        self.assign(in_grad[0], req[0], p)


@mx.operator.register("numpy_softmax")
class NumpySoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return NumpySoftmax()


def net_symbol(classes=10):
    data = sym.var("data")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=32, name="fc1"),
                       act_type="relu")
    logits = sym.FullyConnected(h, num_hidden=classes, name="fc2")
    label = sym.var("softmax_label")
    return sym.Custom(logits, label, op_type="numpy_softmax",
                      name="softmax")


def train(epochs=6, n=512, in_dim=16, classes=10, log=print):
    rng = np.random.RandomState(0)
    w = rng.randn(in_dim, classes).astype("float32")
    x = rng.randn(n, in_dim).astype("float32")
    y = (x @ w).argmax(axis=1).astype("float32")

    it = mx.io.NDArrayIter(x, y, batch_size=64, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(net_symbol(classes), context=mx.cpu(),
                        data_names=["data"],
                        label_names=["softmax_label"])
    mod.fit(it, eval_metric="acc", optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier(), num_epoch=epochs)
    it.reset()
    acc = dict(mod.score(it, "acc"))["accuracy"]
    log("custom-op training accuracy %.3f" % acc)
    return acc


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=6)
    args = ap.parse_args()
    train(epochs=args.epochs)
