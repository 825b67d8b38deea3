"""Two-stage detector slice: RPN + Proposal + ROIAlign + classifier head.

Role parity: reference `example/rcnn/` (Faster R-CNN built on
_contrib_Proposal / _contrib_ROIAlign). Synthetic task: each image holds
one bright axis-aligned square (class 0) or a bright cross (class 1); the
RPN learns objectness + box regression over pixel-space anchors, Proposal
decodes + NMS's candidate boxes, ROIAlign pools their features, and a
small head classifies the pooled region.

RPN targets come from MultiBoxTarget with variances=(1,1,1,1) so the
encoding matches Proposal's unit-variance decode.

Usage:  python train_frcnn.py [--steps 60]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon

IMAGE = 32
STRIDE = 4
SCALES = (2, 3)
RATIOS = (1.0,)
A = len(SCALES) * len(RATIOS)


def pixel_anchors():
    """The exact anchor grid Proposal enumerates, normalized to [0, 1]
    (ratio-major/scale-minor, (a, h, w) flatten order)."""
    from mxnet_tpu.ops.proposal_ops import _gen_base_anchors
    import jax.numpy as jnp
    F = IMAGE // STRIDE
    base = np.asarray(_gen_base_anchors(STRIDE, RATIOS, SCALES,
                                        jnp.float32))
    sy = np.arange(F) * STRIDE
    sx = np.arange(F) * STRIDE
    out = np.zeros((A, F, F, 4), "float32")
    for a in range(A):
        for i, y in enumerate(sy):
            for j, x in enumerate(sx):
                out[a, i, j] = base[a] + [x, y, x, y]
    return out.reshape(1, -1, 4) / IMAGE


def synthetic_batch(batch, rng):
    x = rng.rand(batch, 1, IMAGE, IMAGE).astype("float32") * 0.1
    labels = np.zeros((batch, 1, 5), "float32")
    for b in range(batch):
        cls = rng.randint(0, 2)
        size = rng.randint(8, 14)
        cy, cx = rng.randint(size // 2 + 1, IMAGE - size // 2 - 1, 2)
        y1, y2 = cy - size // 2, cy + size // 2
        x1, x2 = cx - size // 2, cx + size // 2
        if cls == 0:
            x[b, 0, y1:y2, x1:x2] = 1.0          # filled square
        else:
            x[b, 0, cy - 1:cy + 1, x1:x2] = 1.0  # cross
            x[b, 0, y1:y2, cx - 1:cx + 1] = 1.0
        labels[b, 0] = [cls, x1 / IMAGE, y1 / IMAGE, x2 / IMAGE, y2 / IMAGE]
    return mx.nd.array(x), mx.nd.array(labels)


class FRCNN(gluon.Block):
    def __init__(self, num_classes=2, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.backbone = gluon.nn.Sequential()
            for ch in (16, 32):
                self.backbone.add(gluon.nn.Conv2D(ch, 3, padding=1),
                                  gluon.nn.Activation("relu"),
                                  gluon.nn.MaxPool2D(2))
            self.rpn_conv = gluon.nn.Conv2D(32, 3, padding=1,
                                            activation="relu")
            self.rpn_cls = gluon.nn.Conv2D(2 * A, 1)
            self.rpn_loc = gluon.nn.Conv2D(4 * A, 1)
            self.head = gluon.nn.Sequential()
            self.head.add(gluon.nn.Dense(32, activation="relu"),
                          gluon.nn.Dense(num_classes))

    def rpn(self, x):
        feat = self.backbone(x)
        r = self.rpn_conv(feat)
        return feat, self.rpn_cls(r), self.rpn_loc(r)

    def propose(self, cls_score, loc, post_nms=8):
        B = cls_score.shape[0]
        F = cls_score.shape[2]
        # softmax over the (bg, fg) pair per anchor
        s = cls_score.reshape((B, 2, A, F, F))
        probs = mx.nd.softmax(s, axis=1).reshape((B, 2 * A, F, F))
        im_info = mx.nd.array(np.tile([IMAGE, IMAGE, 1.0], (B, 1))
                              .astype("float32"))
        rois, scores = mx.nd.contrib.MultiProposal(
            probs, loc, im_info, rpn_pre_nms_top_n=32,
            rpn_post_nms_top_n=post_nms, threshold=0.7, rpn_min_size=4,
            scales=SCALES, ratios=RATIOS, feature_stride=STRIDE,
            output_score=True)
        return rois, scores

    def classify(self, feat, rois):
        pooled = mx.nd.contrib.ROIAlign(
            feat, rois, pooled_size=(4, 4), spatial_scale=1.0 / STRIDE)
        return self.head(pooled.reshape((pooled.shape[0], -1)))


def train(steps=60, batch=8, lr=0.02, log=print):
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = FRCNN()
    net.initialize(mx.init.Xavier())
    anchors = mx.nd.array(pixel_anchors())
    xb, yb = synthetic_batch(batch, rng)
    feat, c, l = net.rpn(xb)
    net.classify(feat, mx.nd.array(np.array([[0, 4, 4, 20, 20]],
                                            "float32")))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    huber = gluon.loss.HuberLoss()

    first = last = None
    for step in range(steps):
        xb, yb = synthetic_batch(batch, rng)
        with ag.record():
            feat, cls_score, loc = net.rpn(xb)
            B, _, F, _ = cls_score.shape
            # (a, h, w) flatten order to match the anchor grid
            cls_ahw = cls_score.reshape((B, 2, A, F, F)) \
                               .transpose((0, 1, 2, 3, 4)) \
                               .reshape((B, 2, -1))
            loc_ahw = loc.reshape((B, A, 4, F, F)) \
                         .transpose((0, 1, 3, 4, 2)).reshape((B, -1))
            bt, bm, ct = mx.nd.contrib.MultiBoxTarget(
                anchors, yb, cls_ahw, overlap_threshold=0.5,
                variances=(1.0, 1.0, 1.0, 1.0))
            obj = (ct > 0).astype("float32")  # class-agnostic objectness
            rpn_cls_l = ce(cls_ahw.transpose((0, 2, 1)).reshape((-1, 2)),
                           obj.reshape((-1,)))
            rpn_loc_l = huber(loc_ahw * bm, bt * bm)
            # head training on ground-truth boxes (pixel coords)
            gt_rois = mx.nd.concat(
                mx.nd.arange(B).reshape((B, 1)),
                yb[:, 0, 1:5] * IMAGE, dim=1)
            logits = net.classify(feat, gt_rois)
            head_l = ce(logits, yb[:, 0, 0])
            loss = rpn_cls_l.mean() + rpn_loc_l.mean() + head_l.mean()
        loss.backward()
        trainer.step(batch)
        last = float(loss.asnumpy())
        first = last if first is None else first
        if step % 10 == 0:
            log("step %3d  loss %.4f (rpn_cls %.3f loc %.3f head %.3f)"
                % (step, last, float(rpn_cls_l.mean().asnumpy()),
                   float(rpn_loc_l.mean().asnumpy()),
                   float(head_l.mean().asnumpy())))
    return net, first, last


def evaluate(net, n=8):
    """Proposal quality + classification accuracy on fresh scenes."""
    rng = np.random.RandomState(1)
    xb, yb = synthetic_batch(n, rng)
    feat, cls_score, loc = net.rpn(xb)
    rois, scores = net.propose(cls_score, loc)
    r = rois.asnumpy()
    gt = yb.asnumpy()[:, 0, 1:5] * IMAGE
    best_iou = []
    for b in range(n):
        mine = r[r[:, 0] == b][:, 1:]
        g = gt[b]
        ious = []
        for m in mine:
            ix = max(0, min(m[2], g[2]) - max(m[0], g[0]))
            iy = max(0, min(m[3], g[3]) - max(m[1], g[1]))
            inter = ix * iy
            u = ((m[2] - m[0]) * (m[3] - m[1]) +
                 (g[2] - g[0]) * (g[3] - g[1]) - inter)
            ious.append(inter / u if u > 0 else 0.0)
        best_iou.append(max(ious) if ious else 0.0)
    gt_rois = mx.nd.concat(
        mx.nd.arange(n).reshape((n, 1)),
        yb[:, 0, 1:5] * IMAGE, dim=1)
    logits = net.classify(feat, gt_rois).asnumpy()
    acc = (logits.argmax(1) == yb.asnumpy()[:, 0, 0]).mean()
    return float(np.mean(best_iou)), float(acc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    net, first, last = train(args.steps)
    print("loss: %.4f -> %.4f" % (first, last))
    miou, acc = evaluate(net)
    print("mean best-proposal IoU: %.3f   head accuracy: %.2f"
          % (miou, acc))


if __name__ == "__main__":
    main()
