"""Denoising autoencoder trained end-to-end.

Role parity: reference `example/autoencoder/` (the stacked denoising
autoencoder demo: corrupt input, reconstruct, reconstruction MSE as the
metric). The reference's greedy layerwise PRETRAINING phase is omitted:
end-to-end training with modern initializers reaches the manifold
directly — the corrupt->encode->decode->MSE capability is the parity
surface here.

Usage:  python train_autoencoder.py [--epochs 8]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon, nd


def make_data(n=768, dim=64, rank=6, seed=0):
    """Low-rank structured data: the AE must discover the 6-d manifold."""
    rng = np.random.RandomState(seed)
    basis = rng.randn(rank, dim).astype("float32")
    codes = rng.randn(n, rank).astype("float32")
    x = np.tanh(codes @ basis)
    return x.astype("float32")


class DAE(gluon.Block):
    def __init__(self, dim, hidden, bottleneck, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.enc1 = gluon.nn.Dense(hidden, activation="relu")
            self.enc2 = gluon.nn.Dense(bottleneck)
            self.dec1 = gluon.nn.Dense(hidden, activation="relu")
            self.dec2 = gluon.nn.Dense(dim)

    def encode(self, x):
        return self.enc2(self.enc1(x))

    def forward(self, x):
        return self.dec2(self.dec1(self.encode(x)))


def train(epochs=8, noise=0.2, batch=64, log=print):
    x = make_data()
    net = DAE(x.shape[1], 32, 8)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(1)
    first = last = None
    for epoch in range(epochs):
        total, nb = 0.0, 0
        for s in range(0, len(x), batch):
            clean = x[s:s + batch]
            noisy = clean + rng.randn(*clean.shape).astype("float32") * noise
            xb, yb = nd.array(noisy), nd.array(clean)
            with ag.record():
                loss = loss_fn(net(xb), yb).mean()
            loss.backward()
            trainer.step(1)
            total += float(loss.asnumpy())
            nb += 1
        mse = total / nb
        if first is None:
            first = mse
        last = mse
        log("epoch %d: denoise MSE %.5f" % (epoch, mse))
    # reconstruction quality on clean inputs
    rec = net(nd.array(x)).asnumpy()
    rec_mse = float(((rec - x) ** 2).mean())
    code = net.encode(nd.array(x[:4])).asnumpy()
    log("clean reconstruction MSE %.5f, code shape %s"
        % (rec_mse, code.shape))
    return first, last, rec_mse


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=8)
    args = ap.parse_args()
    train(epochs=args.epochs)
