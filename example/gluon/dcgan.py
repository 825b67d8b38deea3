"""DCGAN on synthetic image data.

Role parity: reference `example/gluon/dcgan.py` (DCGAN with alternating
generator/discriminator SGD). Synthetic target distribution: images whose
lower half is bright and upper half is dark — easy to learn, easy to test.

Usage:  python dcgan.py [--steps 100]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon


def build_generator(ngf=16, nz=16):
    net = gluon.nn.Sequential()
    with net.name_scope():
        # z (B, nz, 1, 1) -> (B, 1, 16, 16)
        net.add(gluon.nn.Conv2DTranspose(ngf * 2, 4, strides=1, padding=0,
                                         use_bias=False),
                gluon.nn.BatchNorm(), gluon.nn.Activation("relu"),
                gluon.nn.Conv2DTranspose(ngf, 4, strides=2, padding=1,
                                         use_bias=False),
                gluon.nn.BatchNorm(), gluon.nn.Activation("relu"),
                gluon.nn.Conv2DTranspose(1, 4, strides=2, padding=1,
                                         use_bias=False),
                gluon.nn.Activation("tanh"))
    return net


def build_discriminator(ndf=16):
    net = gluon.nn.Sequential()
    with net.name_scope():
        net.add(gluon.nn.Conv2D(ndf, 4, strides=2, padding=1,
                                use_bias=False),
                gluon.nn.LeakyReLU(0.2),
                gluon.nn.Conv2D(ndf * 2, 4, strides=2, padding=1,
                                use_bias=False),
                gluon.nn.BatchNorm(), gluon.nn.LeakyReLU(0.2),
                gluon.nn.Conv2D(1, 4, strides=1, padding=0,
                                use_bias=False))
    return net


def real_batch(batch, rng):
    """Images in [-1, 1]: bright lower half, dark upper half + noise."""
    x = rng.randn(batch, 1, 16, 16).astype("float32") * 0.1
    x[:, :, 8:, :] += 0.8
    x[:, :, :8, :] -= 0.8
    return mx.nd.array(np.clip(x, -1, 1))


def train(steps=100, batch=32, nz=16, lr=2e-4, log=print):
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    gen, dis = build_generator(nz=nz), build_discriminator()
    gen.initialize(mx.init.Normal(0.02))
    dis.initialize(mx.init.Normal(0.02))
    z0 = mx.nd.array(rng.randn(batch, nz, 1, 1).astype("float32"))
    dis(gen(z0))  # resolve deferred shapes
    gt = gluon.Trainer(gen.collect_params(), "adam",
                       {"learning_rate": lr, "beta1": 0.5})
    dt = gluon.Trainer(dis.collect_params(), "adam",
                       {"learning_rate": lr, "beta1": 0.5})
    bce = gluon.loss.SigmoidBinaryCrossEntropyLoss()
    ones = mx.nd.ones((batch,))
    zeros = mx.nd.zeros((batch,))

    d_loss = g_loss = None
    for step in range(steps):
        z = mx.nd.array(rng.randn(batch, nz, 1, 1).astype("float32"))
        real = real_batch(batch, rng)
        # D step: real -> 1, fake -> 0
        with ag.record():
            fake = gen(z)
            l_d = (bce(dis(real).reshape((-1,)), ones) +
                   bce(dis(fake.detach()).reshape((-1,)), zeros)).mean()
        l_d.backward()
        dt.step(batch)
        # G step: fool D
        with ag.record():
            fake = gen(z)
            l_g = bce(dis(fake).reshape((-1,)), ones).mean()
        l_g.backward()
        gt.step(batch)
        d_loss, g_loss = float(l_d.asnumpy()), float(l_g.asnumpy())
        if step % 20 == 0:
            log("step %3d  d_loss %.4f  g_loss %.4f"
                % (step, d_loss, g_loss))
    return gen, dis, d_loss, g_loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100)
    args = ap.parse_args()
    gen, dis, d_loss, g_loss = train(args.steps)
    rng = np.random.RandomState(1)
    z = mx.nd.array(rng.randn(8, 16, 1, 1).astype("float32"))
    samples = gen(z).asnumpy()
    top = samples[:, :, :8, :].mean()
    bottom = samples[:, :, 8:, :].mean()
    print("final d_loss %.4f g_loss %.4f" % (d_loss, g_loss))
    print("generated structure: top mean %.3f, bottom mean %.3f "
          "(target: dark top, bright bottom)" % (top, bottom))


if __name__ == "__main__":
    main()
