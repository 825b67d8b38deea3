"""Sparse linear classification on libsvm-format data.

Role parity: reference `example/sparse/linear_classification/train.py`:
a linear model whose weight is ROW-SPARSE, fed by libsvm-format sparse
features; every step pulls only the weight rows the batch touches from the
kvstore (`kv.row_sparse_pull(..., row_ids=batch_cols)`), computes the
sparse dot, and pushes a row-sparse gradient back.

TPU-native notes: the compute itself is a dense matmul over the batch's
CSR rows scattered into a dense block (XLA has no CSR kernels; a gather +
MXU matmul wins on this hardware for the classic KDD-style shapes), while
the STORAGE and the kvstore traffic stay row-sparse — which is the part
the reference example exists to demonstrate.

Usage:  python linear_classification.py [--epochs 5]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ndarray import sparse


def make_libsvm(path, n=512, feat=1000, active=12, seed=0):
    """Synthetic libsvm file: y in {0,1} from a sparse ground-truth w."""
    rng = np.random.RandomState(seed)
    w_true = np.zeros(feat, np.float32)
    support = rng.choice(feat, 40, replace=False)
    w_true[support] = rng.randn(40)
    with open(path, "w") as fh:
        for _ in range(n):
            cols = np.sort(rng.choice(feat, active, replace=False))
            vals = rng.rand(active).astype(np.float32) + 0.1
            y = 1 if float(vals @ w_true[cols]) > 0 else 0
            fh.write("%d %s\n" % (y, " ".join(
                "%d:%.4f" % (c, v) for c, v in zip(cols, vals))))
    return w_true


def load_libsvm(path, feat):
    """Parse libsvm rows into a CSR matrix + labels (the reference feeds
    this through LibSVMIter; parsing is the example's data code here)."""
    data, indices, indptr, labels = [], [], [0], []
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            labels.append(float(parts[0]))
            for tok in parts[1:]:
                c, v = tok.split(":")
                indices.append(int(c))
                data.append(float(v))
            indptr.append(len(indices))
    csr = sparse.csr_matrix(
        (np.asarray(data, np.float32), np.asarray(indices, np.int64),
         np.asarray(indptr, np.int64)), shape=(len(labels), feat))
    return csr, np.asarray(labels, np.float32)


def batches(csr, labels, batch_size):
    n = labels.shape[0]
    for s in range(0, n - batch_size + 1, batch_size):
        rows = csr[s:s + batch_size]
        # column ids this batch touches -> the row ids of the weight we
        # must pull (reference train.py sparse_row_id_fn)
        dense = rows.asnumpy()
        touched = np.nonzero(dense.any(axis=0))[0]
        yield dense, labels[s:s + batch_size], touched


def train(epochs=5, feat=1000, batch_size=64, lr=0.5, log=print):
    tmp = os.path.join("/tmp", "sparse_linear.libsvm")
    w_true = make_libsvm(tmp, feat=feat)
    csr, labels = load_libsvm(tmp, feat)

    # row-sparse weight lives in the kvstore, updated ON the store
    # (reference update_on_kvstore=True dist layout)
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=lr))
    weight = nd.zeros((feat, 1))
    bias = nd.zeros((1,))
    kv.init("w", weight)

    losses = []
    for epoch in range(epochs):
        total, count = 0.0, 0
        for x, y, touched in batches(csr, labels, batch_size):
            # pull ONLY the touched rows, row-sparse (reference
            # kvstore.row_sparse_pull on every forward)
            w_rs = sparse.row_sparse_array(
                (np.zeros((len(touched), 1), np.float32), touched),
                shape=(feat, 1))
            kv.row_sparse_pull("w", out=w_rs, row_ids=nd.array(touched))

            xb = nd.array(x)
            yb = nd.array(y)
            w_dense = nd.array(w_rs.asnumpy())
            w_dense.attach_grad()
            bias.attach_grad()
            with mx.autograd.record():
                logit = nd.dot(xb, w_dense) + bias
                p = nd.sigmoid(logit).reshape((batch_size,))
                eps = 1e-7
                loss = -(yb * nd.log(p + eps) +
                         (1 - yb) * nd.log(1 - p + eps)).mean()
            loss.backward()

            # push a ROW-SPARSE gradient: only touched rows move; the
            # store-side optimizer applies sgd (update_on_kvstore)
            g = w_dense.grad.asnumpy()
            g_rs = sparse.row_sparse_array(
                (g[touched], touched), shape=(feat, 1))
            kv.push("w", g_rs)
            kv.pull("w", out=weight)
            bias -= lr * bias.grad
            total += float(loss.asnumpy())
            count += 1
        losses.append(total / count)
        log("epoch %d: loss %.4f" % (epoch, losses[-1]))

    # final accuracy over the training set
    w_final = weight.asnumpy()
    logits = csr.asnumpy() @ w_final + bias.asnumpy()
    acc = float(((logits.ravel() > 0) == (labels > 0.5)).mean())
    log("train accuracy %.3f" % acc)
    return losses, acc, w_final, w_true


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    args = ap.parse_args()
    train(epochs=args.epochs)
