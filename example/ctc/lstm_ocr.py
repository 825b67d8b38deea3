"""CTC sequence training: BiLSTM + CTC loss on synthetic OCR-style data.

Role parity: reference `example/ctc/lstm_ocr_train.py` (captcha OCR with
warp-CTC / mx.sym.ctc_loss). Synthetic task: each "image" is a sequence of
column vectors, each column one-hot-ish for a digit with noise; the label
is the digit string without blanks or repeats collapsed — exactly the CTC
alignment problem.

Usage:  python lstm_ocr.py [--steps 80]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag, gluon

NUM_CLASSES = 10  # digits; CTC blank is class NUM_CLASSES


def synthetic_batch(batch, seq_len, label_len, rng):
    """Each label digit is painted over a random span of columns."""
    x = rng.rand(batch, seq_len, NUM_CLASSES).astype("float32") * 0.3
    labels = np.zeros((batch, label_len), "float32")
    for b in range(batch):
        digits = rng.randint(0, NUM_CLASSES, label_len)
        labels[b] = digits
        # paint digits over consecutive spans
        bounds = np.sort(rng.choice(
            np.arange(1, seq_len), label_len - 1, replace=False))
        spans = np.split(np.arange(seq_len), bounds)
        for d, span in zip(digits, spans):
            x[b, span, d] += 2.0
    return mx.nd.array(x), mx.nd.array(labels)


class CTCNet(gluon.Block):
    def __init__(self, hidden=32, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.lstm = gluon.rnn.LSTM(hidden, bidirectional=True,
                                       layout="NTC")
            self.proj = gluon.nn.Dense(NUM_CLASSES + 1, flatten=False)

    def forward(self, x):
        return self.proj(self.lstm(x))  # (B, T, C+1)


def train(steps=80, batch=16, seq_len=20, label_len=4, lr=0.02,
          log=print):
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net = CTCNet()
    net.initialize(mx.init.Xavier())
    xb, yb = synthetic_batch(batch, seq_len, label_len, rng)
    net(xb)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    ctc = gluon.loss.CTCLoss(layout="NTC", label_layout="NT")
    first = last = None
    for step in range(steps):
        xb, yb = synthetic_batch(batch, seq_len, label_len, rng)
        with ag.record():
            logits = net(xb)
            loss = ctc(logits, yb).mean()
        loss.backward()
        trainer.step(batch)
        last = float(loss.asnumpy())
        first = last if first is None else first
        if step % 10 == 0:
            log("step %3d  ctc loss %.4f" % (step, last))
    return net, first, last


def greedy_decode(logits):
    """Best-path decode: argmax per frame, collapse repeats, drop blanks."""
    ids = np.argmax(logits, axis=-1)
    out = []
    for row in ids:
        prev = -1
        s = []
        for t in row:
            if t != prev and t != NUM_CLASSES:
                s.append(int(t))
            prev = t
        out.append(s)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=80)
    args = ap.parse_args()
    net, first, last = train(args.steps)
    print("ctc loss: %.4f -> %.4f" % (first, last))
    rng = np.random.RandomState(1)
    xb, yb = synthetic_batch(4, 20, 4, rng)
    decoded = greedy_decode(net(xb).asnumpy())
    correct = sum(d == list(map(int, y)) for d, y in
                  zip(decoded, yb.asnumpy()))
    print("exact-sequence accuracy: %d/4" % correct)
    print("sample: predicted", decoded[0], "label",
          [int(v) for v in yb.asnumpy()[0]])


if __name__ == "__main__":
    main()
