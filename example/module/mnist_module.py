"""The full Module workflow: fit, checkpoint, resume, score, predict.

Role parity: reference `example/module/` (mnist_mlp.py / the sequential
module demos): build a symbol, `mod.fit` with an optimizer and metric,
`save_checkpoint` each epoch, `Module.load` + `fit(begin_epoch=...)` to
resume, `score` on a validation iter, `predict` for raw outputs.

Runs on a synthetic MNIST-like problem so it's self-contained; swap the
iterators for `mx.io.MNISTIter` on real data.

Usage:  python mnist_module.py [--epochs 4]
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, sym


def mlp_symbol(classes=10):
    data = sym.var("data")
    h = sym.Activation(sym.FullyConnected(data, num_hidden=64, name="fc1"),
                       act_type="relu")
    h = sym.Activation(sym.FullyConnected(h, num_hidden=32, name="fc2"),
                       act_type="relu")
    out = sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=classes, name="fc3"),
        sym.var("softmax_label"), name="softmax")
    return out


def make_iters(n=1024, in_dim=32, classes=10, batch=64, seed=0):
    rng = np.random.RandomState(seed)
    centers = rng.randn(classes, in_dim).astype("float32") * 2.0
    y = rng.randint(0, classes, n).astype("float32")
    x = centers[y.astype(int)] + rng.randn(n, in_dim).astype("float32")
    split = int(n * 0.8)
    train = mx.io.NDArrayIter(x[:split], y[:split], batch_size=batch,
                              shuffle=True, label_name="softmax_label")
    val = mx.io.NDArrayIter(x[split:], y[split:], batch_size=batch,
                            label_name="softmax_label")
    return train, val


def train(epochs=4, prefix=None, log=print):
    prefix = prefix or os.path.join(tempfile.gettempdir(), "mnist_module")
    train_iter, val_iter = make_iters()

    mod = mx.mod.Module(mlp_symbol(), context=mx.cpu(),
                        data_names=["data"],
                        label_names=["softmax_label"])

    # phase 1: train the first half, checkpointing every epoch
    half = max(1, epochs // 2)
    ckpt = mx.callback.do_checkpoint(prefix)
    mod.fit(train_iter, eval_data=val_iter, eval_metric="acc",
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            initializer=mx.init.Xavier(),
            num_epoch=half, epoch_end_callback=ckpt)

    # phase 2: RESUME from the checkpoint into a fresh module
    sym_loaded, arg_params, aux_params = mx.model.load_checkpoint(
        prefix, half)
    mod2 = mx.mod.Module(sym_loaded, context=mx.cpu(),
                         data_names=["data"],
                         label_names=["softmax_label"])
    train_iter.reset()
    mod2.fit(train_iter, eval_data=val_iter, eval_metric="acc",
             optimizer="sgd", optimizer_params={"learning_rate": 0.1},
             arg_params=arg_params, aux_params=aux_params,
             begin_epoch=half, num_epoch=epochs)

    # score + predict on the validation set
    val_iter.reset()
    score = mod2.score(val_iter, "acc")
    acc = dict(score)["accuracy"]
    val_iter.reset()
    preds = mod2.predict(val_iter)
    log("val accuracy %.3f, predictions %s" % (acc, preds.shape))
    return acc, preds


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=4)
    args = ap.parse_args()
    train(epochs=args.epochs)
