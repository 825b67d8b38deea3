"""Single-shot detector training on synthetic scenes.

Role parity: reference `example/ssd/` (SSD training driver built on
_contrib_MultiBoxPrior / MultiBoxTarget / MultiBoxDetection). A compact
single-scale SSD: conv backbone -> (cls, loc) heads over per-pixel anchors,
target assignment by the MultiBoxTarget op, SmoothL1 + softmax CE loss,
decode + NMS by MultiBoxDetection at eval.

Usage:  python train_ssd.py [--steps 50] [--image 64]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon


class TinySSD(gluon.Block):
    """Backbone + single-scale multibox heads (A anchors per position)."""

    def __init__(self, num_classes=2, sizes=(0.3, 0.5), ratios=(1.0, 2.0),
                 **kwargs):
        super().__init__(**kwargs)
        self.num_classes = num_classes
        self.num_anchors = len(sizes) + len(ratios) - 1
        self._sizes, self._ratios = sizes, ratios
        with self.name_scope():
            self.backbone = gluon.nn.Sequential()
            for ch in (16, 32, 64):
                self.backbone.add(gluon.nn.Conv2D(ch, 3, padding=1),
                                  gluon.nn.BatchNorm(),
                                  gluon.nn.Activation("relu"),
                                  gluon.nn.MaxPool2D(2))
            self.cls_head = gluon.nn.Conv2D(
                self.num_anchors * (num_classes + 1), 3, padding=1)
            self.loc_head = gluon.nn.Conv2D(self.num_anchors * 4, 3,
                                            padding=1)

    def forward(self, x):
        feat = self.backbone(x)
        anchors = mx.nd.contrib.MultiBoxPrior(feat, sizes=self._sizes,
                                              ratios=self._ratios)
        B = x.shape[0]
        # heads -> (B, N_anchors, ...) layouts the MultiBox ops expect
        cls = self.cls_head(feat).transpose((0, 2, 3, 1)).reshape(
            (B, -1, self.num_classes + 1))
        loc = self.loc_head(feat).transpose((0, 2, 3, 1)).reshape((B, -1))
        return anchors, cls, loc


def synthetic_batch(batch, image, rng):
    """One box per image: a bright square on dark background, class 0."""
    x = rng.rand(batch, 3, image, image).astype("float32") * 0.1
    labels = np.zeros((batch, 1, 5), "float32")
    for b in range(batch):
        cx, cy = rng.rand(2) * 0.5 + 0.25
        s = 0.2 + rng.rand() * 0.15
        x1, y1 = max(cx - s / 2, 0), max(cy - s / 2, 0)
        x2, y2 = min(cx + s / 2, 1), min(cy + s / 2, 1)
        labels[b, 0] = [0, x1, y1, x2, y2]
        px = slice(int(y1 * image), max(int(y2 * image), int(y1 * image) + 1))
        py = slice(int(x1 * image), max(int(x2 * image), int(x1 * image) + 1))
        x[b, :, px, py] = 1.0
    return mx.nd.array(x), mx.nd.array(labels)


def train(steps=50, batch=8, image=64, lr=0.05, log=print):
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = TinySSD()
    net.initialize(mx.init.Xavier())
    xb, yb = synthetic_batch(batch, image, rng)
    net(xb)  # resolve deferred shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    smooth_l1 = gluon.loss.HuberLoss()

    first = last = None
    for step in range(steps):
        xb, yb = synthetic_batch(batch, image, rng)
        with ag.record():
            anchors, cls, loc = net(xb)
            bt, bm, ct = mx.nd.contrib.MultiBoxTarget(
                anchors, yb, cls.transpose((0, 2, 1)),
                negative_mining_ratio=3.0)
            cls_l = ce(cls.reshape((-1, cls.shape[-1])), ct.reshape((-1,)))
            loc_l = smooth_l1(loc * bm, bt * bm)
            loss = cls_l.mean() + loc_l.mean()
        loss.backward()
        trainer.step(batch)
        v = float(loss.asnumpy())
        first = v if first is None else first
        last = v
        if step % 10 == 0:
            log("step %3d  loss %.4f" % (step, v))
    return net, first, last


def detect(net, x, threshold=0.3):
    anchors, cls, loc = net(x)
    probs = mx.nd.softmax(cls, axis=-1).transpose((0, 2, 1))
    return mx.nd.contrib.MultiBoxDetection(probs, loc, anchors,
                                           threshold=threshold)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=64)
    args = ap.parse_args()
    net, first, last = train(args.steps, args.batch, args.image)
    print("loss: %.4f -> %.4f" % (first, last))
    rng = np.random.RandomState(1)
    xb, yb = synthetic_batch(2, args.image, rng)
    out = detect(net, xb).asnumpy()
    kept = out[0][out[0, :, 0] >= 0]
    print("detections (img 0): %d, best score %.3f"
          % (kept.shape[0], kept[:, 1].max() if kept.size else 0.0))
    print("gt box:", yb.asnumpy()[0, 0, 1:])
    if kept.size:
        print("top box:", kept[np.argmax(kept[:, 1]), 2:6])


if __name__ == "__main__":
    main()
