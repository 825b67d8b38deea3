"""Post-training INT8 quantization: train float -> calibrate -> deploy.

Role parity: reference `example/quantization/` (imagenet_gen_qsym_mkldnn /
imagenet_inference): take a trained FP32 network, run calibration batches
to freeze activation ranges, swap compute to int8, compare accuracy
against the float model, and persist the quantized model for deployment.

TPU-native notes: the int8 path runs real int8 x int8 -> int32 matmul/
conv on the MXU (`ops/quantized_ops.py`); ranges travel as (1,) tensors.
Calibrated ranges, int8 weights and scales are registered Parameters, so
`save_parameters`/`load_parameters` carries the whole deployable artifact
(no re-calibration at load time).

Usage:  python quantize_deploy.py [--epochs 3]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon, nd
from mxnet_tpu.contrib.quantization import quantize_net


def build_cnn(classes=10):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, kernel_size=3, padding=1,
                            activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Conv2D(16, kernel_size=3, padding=1,
                            activation="relu"),
            gluon.nn.MaxPool2D(2),
            gluon.nn.Flatten(),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(classes))
    return net


def make_data(n=512, classes=10, seed=0):
    """Tiny image-like task: class = dominant quadrant pattern."""
    rng = np.random.RandomState(seed)
    y = rng.randint(0, classes, n)
    x = rng.randn(n, 1, 12, 12).astype("float32") * 0.3
    for i, c in enumerate(y):
        r, col = divmod(c, 4)
        x[i, 0, r * 3:(r + 1) * 3, col * 3:(col + 1) * 3] += 2.0
    return x, y.astype("float32")


def accuracy(net, x, y, batch=64):
    correct = 0
    for s in range(0, len(y), batch):
        out = net(nd.array(x[s:s + batch])).asnumpy()
        correct += int((out.argmax(1) == y[s:s + batch]).sum())
    return correct / len(y)


def train_float(net, x, y, epochs, batch=64, log=print):
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 5e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for epoch in range(epochs):
        total = 0.0
        for s in range(0, len(y), batch):
            xb, yb = nd.array(x[s:s + batch]), nd.array(y[s:s + batch])
            with ag.record():
                loss = loss_fn(net(xb), yb).mean()
            loss.backward()
            trainer.step(1)
            total += float(loss.asnumpy())
        log("epoch %d loss %.4f" % (epoch, total / (len(y) // batch)))


def main(epochs=3, log=print):
    x, y = make_data()
    x_cal, y_cal = make_data(n=128, seed=1)   # calibration split
    x_test, y_test = make_data(n=256, seed=2)

    net = build_cnn()
    train_float(net, x, y, epochs, log=log)
    acc_fp32 = accuracy(net, x_test, y_test)
    log("fp32 accuracy %.3f" % acc_fp32)

    # calibrate on held-out batches, freeze ranges, swap to int8
    calib = [nd.array(x_cal[s:s + 64]) for s in range(0, 128, 64)]
    qnet = quantize_net(net, calib_data=calib, calib_mode="naive")
    acc_int8 = accuracy(qnet, x_test, y_test)
    log("int8 accuracy %.3f (drop %.3f)" % (acc_int8, acc_fp32 - acc_int8))

    # deploy: persist the quantized artifact, reload into a FRESH net
    path = os.path.join(tempfile.gettempdir(), "quantized_cnn.params")
    qnet.save_parameters(path)
    net2 = build_cnn()
    net2.initialize(mx.init.Xavier())
    net2(nd.array(x[:1]))                    # shape the params
    qnet2 = quantize_net(net2)               # uncalibrated swap
    qnet2.load_parameters(path)              # ranges+weights from file
    acc_loaded = accuracy(qnet2, x_test, y_test)
    log("reloaded int8 accuracy %.3f" % acc_loaded)
    return acc_fp32, acc_int8, acc_loaded


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    args = ap.parse_args()
    main(epochs=args.epochs)
