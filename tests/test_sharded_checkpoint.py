"""Sharded checkpoint/resume over the mesh trainer (SURVEY §5.4 TPU-native
path): save mid-training, keep training, restore, and verify the restored
trainer reproduces the exact same subsequent trajectory."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel


def _make_trainer(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"),
            gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    mesh = parallel.make_mesh()  # dp over all (8 virtual) devices
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-2}, mesh=mesh)


def _batches(n, seed):
    rng = np.random.RandomState(seed)
    return [(mx.nd.array(rng.rand(8, 8).astype("float32")),
             mx.nd.array(rng.randint(0, 4, (8,)).astype("float32")))
            for _ in range(n)]


def test_checkpoint_resume_reproduces_trajectory(tmp_path):
    t1 = _make_trainer()
    warm = _batches(3, seed=1)
    for x, y in warm:
        t1.step(x, y)
    ckpt = str(tmp_path / "ckpt")
    parallel.save_checkpoint(t1, ckpt)
    step_at_save = t1._t

    cont = _batches(3, seed=2)
    losses_a = [float(t1.step(x, y).asnumpy()) for x, y in cont]

    # fresh trainer, different init -> restore -> same trajectory
    t2 = _make_trainer(seed=99)
    parallel.restore_checkpoint(t2, ckpt)
    assert t2._t == step_at_save
    losses_b = [float(t2.step(x, y).asnumpy()) for x, y in cont]
    np.testing.assert_allclose(losses_a, losses_b, rtol=1e-5, atol=1e-6)


def test_checkpoint_preserves_shardings(tmp_path):
    t1 = _make_trainer()
    for x, y in _batches(2, seed=3):
        t1.step(x, y)
    ckpt = str(tmp_path / "ckpt2")
    parallel.save_checkpoint(t1, ckpt)
    t2 = _make_trainer(seed=5)
    parallel.restore_checkpoint(t2, ckpt)
    for a, b in zip(t1._values, t2._values):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        assert b.sharding.is_equivalent_to(a.sharding, a.ndim)
