"""Matrix-factorization recommender on a synthetic low-rank rating matrix.

Role parity: reference `example/recommenders/demo1-MF.ipynb` /
`example/module/matrix_factorization*.py` (user/item embeddings, dot
product score, MSE). The embedding gradient is dense here (SparseEmbedding
is the dense-fallback alias — SURVEY §5.9); on TPU the full embedding
update is one fused scatter inside the jitted step.

Usage:  python matrix_fact.py [--steps 200]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon


class MFNet(gluon.Block):
    def __init__(self, num_users, num_items, factors=8, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.user = gluon.nn.Embedding(num_users, factors)
            self.item = gluon.nn.Embedding(num_items, factors)

    def forward(self, users, items):
        return (self.user(users) * self.item(items)).sum(axis=1)


def make_ratings(num_users=64, num_items=48, rank=4, seed=0):
    rng = np.random.RandomState(seed)
    u = rng.randn(num_users, rank) * 0.8
    v = rng.randn(num_items, rank) * 0.8
    return (u @ v.T).astype("float32"), rng


def train(steps=200, batch=256, factors=8, lr=0.1, log=print):
    mx.random.seed(0)
    ratings, rng = make_ratings()
    nu, ni = ratings.shape
    net = MFNet(nu, ni, factors)
    net.initialize(mx.init.Normal(0.1))
    net(mx.nd.array(np.zeros(2, "float32")),
        mx.nd.array(np.zeros(2, "float32")))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    l2 = gluon.loss.L2Loss()
    first = last = None
    for step in range(steps):
        us = rng.randint(0, nu, batch)
        its = rng.randint(0, ni, batch)
        r = mx.nd.array(ratings[us, its])
        with ag.record():
            pred = net(mx.nd.array(us.astype("float32")),
                       mx.nd.array(its.astype("float32")))
            loss = l2(pred, r).mean()
        loss.backward()
        trainer.step(batch)
        last = float(loss.asnumpy())
        first = last if first is None else first
        if step % 40 == 0:
            log("step %3d  mse %.4f" % (step, 2 * last))
    return net, ratings, first, last


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    net, ratings, first, last = train(args.steps)
    # full-matrix reconstruction error
    nu, ni = ratings.shape
    uu, ii = np.meshgrid(np.arange(nu), np.arange(ni), indexing="ij")
    pred = net(mx.nd.array(uu.ravel().astype("float32")),
               mx.nd.array(ii.ravel().astype("float32")))
    rmse = float(np.sqrt(np.mean(
        (pred.asnumpy() - ratings.ravel()) ** 2)))
    print("loss %.4f -> %.4f ; full-matrix RMSE %.4f (rating std %.3f)"
          % (first, last, rmse, ratings.std()))


if __name__ == "__main__":
    main()
