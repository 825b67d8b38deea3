"""Device-fed training pipeline tests: DeviceFeed staging ring,
ShardedTrainer.step_stream chunked spans, DataLoader pin_memory pre-staging,
PrefetchingIter lifecycle, and CachedOp concurrent dispatch.

The overlap claims are proven structurally (monkeypatched staging funnel:
batches are staged ahead of consumption, zero consumer-side stage waits
after warmup) — the CPU oracle can't measure real H2D/compute overlap, and
no benchmark cell feeds ``step_stream`` on the chip yet.
"""
import threading
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, profiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import DeviceFeed, datafeed
from mxnet_tpu.resilience import chaos
from mxnet_tpu.resilience.chaos import FatalFault


def _mlp_trainer(seed=0, lr=0.05, optimizer="sgd"):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
        {"learning_rate": lr}, mesh=parallel.make_mesh(dp=8)), net


def _batches(n, batch=16, din=8, ncls=4, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.standard_normal((batch, din)).astype("float32"),
             rng.randint(0, ncls, batch).astype("float32"))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# DeviceFeed
# ---------------------------------------------------------------------------

def test_devicefeed_stages_list_source():
    mesh = parallel.make_mesh(dp=8)
    batches = _batches(5)
    with DeviceFeed(batches, mesh=mesh, depth=2, name="t.basic") as feed:
        out = list(feed)
    assert len(out) == 5
    for (xs, y), (hx, hy) in zip(out, batches):
        assert isinstance(xs, tuple) and len(xs) == 1
        np.testing.assert_array_equal(np.asarray(xs[0]), hx)
        np.testing.assert_array_equal(np.asarray(y), hy)
        # staged onto the dp-sharded layout step() uses
        assert xs[0].sharding.spec == parallel.PartitionSpec(("dp",))


def test_devicefeed_from_dataloader_and_ndarrayiter():
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.io.io import NDArrayIter

    mesh = parallel.make_mesh(dp=8)
    X = np.random.randn(32, 8).astype("float32")
    Y = np.arange(32).astype("float32")
    dl = DataLoader(ArrayDataset(mx.nd.array(X), mx.nd.array(Y)),
                    batch_size=8)
    with DeviceFeed(dl, mesh=mesh, name="t.dl") as feed:
        got = list(feed)
    assert len(got) == 4
    np.testing.assert_array_equal(np.asarray(got[0][0][0]), X[:8])

    it = NDArrayIter(X, Y, batch_size=8)
    with DeviceFeed(it, mesh=mesh, name="t.iter") as feed:
        got = list(feed)
    assert len(got) == 4
    np.testing.assert_array_equal(np.asarray(got[2][1]), Y[16:24])


def test_devicefeed_multi_input_batches():
    mesh = parallel.make_mesh(dp=8)
    rng = np.random.RandomState(0)
    src = [((rng.standard_normal((8, 4)).astype("float32"),
             rng.standard_normal((8, 2)).astype("float32")),
            rng.randint(0, 2, 8).astype("float32")) for _ in range(3)]
    with DeviceFeed(src, mesh=mesh, name="t.multi") as feed:
        out = list(feed)
    assert len(out) == 3 and len(out[0][0]) == 2
    np.testing.assert_array_equal(np.asarray(out[1][0][1]), src[1][0][1])


def test_devicefeed_staged_ahead_and_no_waits_after_warmup(monkeypatch):
    """The pipeline contract on the CPU oracle: with the ring prefilled,
    >= depth-1 batches are staged ahead of consumption and a
    slower-than-staging consumer never waits on the ring."""
    staged = []
    orig = datafeed._stage_put
    monkeypatch.setattr(datafeed, "_stage_put",
                        lambda v, s: (staged.append(1), orig(v, s))[1])
    mesh = parallel.make_mesh(dp=8)
    depth = 3
    feed = DeviceFeed(_batches(10, batch=8), mesh=mesh, depth=depth,
                      name="t.ahead")
    try:
        assert feed.prefill(timeout=30.0) == depth
        # ring full: depth batches staged (2 arrays each) before ANY consume
        assert len(staged) >= 2 * depth
        it = iter(feed)
        next(it)
        # >= depth-1 staged ahead of the single consumed batch
        deadline = time.monotonic() + 10.0
        while feed.stats()["depth_occupancy"] < depth - 1 \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        assert feed.stats()["depth_occupancy"] >= depth - 1
        for _ in it:
            time.sleep(0.005)  # consumer slower than in-memory staging
        st = feed.stats()
        assert st["batches"] == 10
        assert st["stage_waits"] == 0, st
        assert st["bytes_staged"] > 0
    finally:
        feed.close()


def test_devicefeed_source_error_propagates():
    def bad_source():
        yield (np.zeros((8, 8), "float32"), np.zeros(8, "float32"))
        raise ValueError("decode failed")

    mesh = parallel.make_mesh(dp=8)
    feed = DeviceFeed(bad_source(), mesh=mesh, name="t.err")
    it = iter(feed)
    next(it)
    with pytest.raises(ValueError, match="decode failed"):
        next(it)
    feed.close()


def test_devicefeed_reiterable_and_reset():
    from mxnet_tpu.io.io import NDArrayIter

    mesh = parallel.make_mesh(dp=8)
    # list source: plain re-iteration restarts from the top
    feed = DeviceFeed(_batches(4), mesh=mesh, name="t.reiter")
    assert sum(1 for _ in feed) == 4
    assert sum(1 for _ in feed) == 4
    feed.close()
    # DataIter source: reset() mid-epoch rewinds the underlying iterator
    X = np.random.randn(32, 8).astype("float32")
    it = NDArrayIter(X, np.arange(32).astype("float32"), batch_size=8)
    feed = DeviceFeed(it, mesh=mesh, depth=2, name="t.reset")
    next(iter(feed))
    feed.reset()
    assert sum(1 for _ in feed) == 4
    feed.close()


def test_devicefeed_profiler_rows():
    mesh = parallel.make_mesh(dp=8)
    feed = DeviceFeed(_batches(3), mesh=mesh, name="t.rows")
    list(feed)
    rows = profiler.get_aggregate_stats()
    assert rows["datafeed.t.rows.batches"]["calls"] == 3
    assert rows["datafeed.t.rows.bytes_staged"]["calls"] > 0
    assert "datafeed.t.rows.stage_wait_ms" in rows
    assert "datafeed.t.rows.depth_occupancy" in rows
    feed.close()
    # close() unregisters: a finished feed must not pin buffers via stats
    assert "datafeed.t.rows.batches" not in profiler.get_aggregate_stats()


def test_devicefeed_use_after_close_raises_fast():
    """A closed feed must fail fast on use (not strand the consumer in a
    full-timeout wait on a stager that exited without a sentinel);
    reset() re-arms it."""
    mesh = parallel.make_mesh(dp=8)
    feed = DeviceFeed(_batches(4), mesh=mesh, depth=2, name="t.closed")
    next(iter(feed))
    feed.close()
    with pytest.raises(RuntimeError, match="closed"):
        next(iter(feed))
    feed.reset()
    assert sum(1 for _ in feed) == 4
    # closed AFTER exhaustion must not silently revive either (a revived
    # feed would run unregistered from the stats registry)
    feed.close()
    with pytest.raises(RuntimeError, match="closed"):
        iter(feed)


def test_devicefeed_collected_feed_leaves_no_registry_entry():
    """A feed collected without close() self-discards its registry handle
    (uniquely-named feeds from loaders built in a loop must not grow the
    registry without bound)."""
    import gc

    mesh = parallel.make_mesh(dp=8)
    feed = DeviceFeed(_batches(2), mesh=mesh, depth=2, name="t.gcreg")
    list(feed)
    assert "t.gcreg" in parallel.feed_stats()
    del feed
    gc.collect()
    assert "t.gcreg" not in datafeed._registry._items


def test_devicefeed_namedtuple_batches_staged():
    """pin_memory structure mode must rebuild namedtuple batches
    positionally (the generic 1-arg tuple rebuild crashes them)."""
    from collections import namedtuple

    Batch = namedtuple("Batch", ["data", "label"])
    src = [Batch(np.random.randn(8, 4).astype("float32"),
                 np.arange(8).astype("float32")) for _ in range(2)]
    feed = DeviceFeed(src, mesh=None, output="batch", depth=2,
                      name="t.ntuple")
    out = list(feed)
    feed.close()
    assert len(out) == 2 and isinstance(out[0], Batch)
    assert isinstance(out[0].data, mx.nd.NDArray)
    np.testing.assert_array_equal(out[1].label.asnumpy(), src[1].label)


def test_devicefeed_gauge_in_serving_metrics():
    """The serving /metrics payload carries live feed stats (ModelServer
    registers the same ``datafeed`` gauge fn this exercises)."""
    from mxnet_tpu.serving import ServingMetrics

    m = ServingMetrics(name="t.datafeed")
    m.set_gauge_fn("datafeed", parallel.feed_stats)
    feed = DeviceFeed(_batches(2), mesh=parallel.make_mesh(dp=8),
                      name="t.metrics")
    list(feed)
    snap = m.snapshot()
    assert snap["datafeed"]["t.metrics"]["batches"] == 2
    feed.close()


def test_devicefeed_rejects_bad_args():
    with pytest.raises(ValueError):
        DeviceFeed([], depth=0)
    with pytest.raises(ValueError):
        DeviceFeed([], output="tensors")


# ---------------------------------------------------------------------------
# ShardedTrainer.step_stream
# ---------------------------------------------------------------------------

def test_step_stream_bitwise_matches_step_calls():
    """Acceptance: host-supplied batches through step_stream are
    bitwise-equal (losses AND final params) to the same batches through a
    sequence of step() calls."""
    batches = _batches(6, seed=11)
    st1, net1 = _mlp_trainer(seed=2)
    st2, net2 = _mlp_trainer(seed=2)
    for p1, p2 in zip(net1.collect_params().values(),
                      net2.collect_params().values()):
        p2.set_data(p1.data())
    losses1 = np.array([st1.step(mx.nd.array(x), mx.nd.array(y)).asnumpy()
                        for x, y in batches], "float32")
    feed = DeviceFeed(list(batches), mesh=st2.mesh, name="t.bitwise")
    losses2 = st2.step_stream(feed, chunk=4).asnumpy()  # spans of 4 + 2
    feed.close()
    np.testing.assert_array_equal(losses1, losses2.astype("float32"))
    for v1, v2 in zip(st1._values, st2._values):
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    assert st2._t == 6


def test_step_stream_conv_bn_matches_step_and_span():
    """Conv+BatchNorm coverage: step_stream's chunked spans are BITWISE the
    fused step_many program (aux stats carried across chunk boundaries
    included); vs a sequence of step() calls the losses stay bitwise and
    params match to float32 exactness — XLA fuses the conv backward
    differently in the single-step program vs the scan body (~1 ULP on a
    few conv weights), a program-shape property the existing step_many
    test acknowledges, not a streaming artifact."""
    np.random.seed(3)
    mx.random.seed(3)
    X = np.random.randn(6, 16, 3, 8, 8).astype("float32")
    Y = np.random.randint(0, 4, (6, 16)).astype("float32")

    def make_net():
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(8, 3, padding=1, in_channels=3),
                    nn.BatchNorm(in_channels=8),
                    nn.Activation("relu"),
                    nn.GlobalAvgPool2D(),
                    nn.Dense(4, in_units=8))
        net.initialize(mx.init.Xavier())
        return net

    net1, net2 = make_net(), make_net()
    for p1, p2 in zip(net1.collect_params().values(),
                      net2.collect_params().values()):
        p2.set_data(p1.data())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    mesh = parallel.make_mesh(dp=8)
    net3 = make_net()
    for p1, (p2, p3) in zip(net1.collect_params().values(),
                            zip(net2.collect_params().values(),
                                net3.collect_params().values())):
        p2.set_data(p1.data())
        p3.set_data(p1.data())
    st1 = parallel.ShardedTrainer(net1, loss_fn, "sgd",
                                  {"learning_rate": 0.05}, mesh=mesh)
    losses1 = np.array([st1.step(mx.nd.array(X[i]),
                                 mx.nd.array(Y[i])).asnumpy()
                        for i in range(6)], "float32")

    st2 = parallel.ShardedTrainer(net2, loss_fn, "sgd",
                                  {"learning_rate": 0.05}, mesh=mesh)
    feed = DeviceFeed([(X[i], Y[i]) for i in range(6)], mesh=mesh,
                      name="t.bitwise")
    losses2 = st2.step_stream(feed, chunk=4).asnumpy()  # spans of 4 + 2
    feed.close()

    st3 = parallel.ShardedTrainer(net3, loss_fn, "sgd",
                                  {"learning_rate": 0.05}, mesh=mesh)
    losses3 = st3.step_many(mx.nd.array(X), mx.nd.array(Y)).asnumpy()

    np.testing.assert_array_equal(losses1, losses2.astype("float32"))
    np.testing.assert_array_equal(losses3, losses2)
    # chunked stream == one fused span, bitwise (params, opt state, BN aux)
    for v2, v3 in zip(st2._values, st3._values):
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v3))
    # vs the single-step program: float32-exact (see docstring)
    for v1, v2 in zip(st1._values, st2._values):
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2),
                                   rtol=1e-6, atol=1e-7)
    assert st2._t == 6


def test_step_stream_steps_arg_and_autowrap():
    """steps= bounds consumption; a plain iterable source is auto-wrapped
    in a DeviceFeed on the trainer's mesh."""
    st, _ = _mlp_trainer()
    losses = st.step_stream(_batches(8), steps=5, chunk=2)
    assert losses.shape == (5,)
    assert st._t == 5
    assert np.isfinite(losses.asnumpy()).all()
    # steps=0 is a no-op returning an empty loss vector
    empty = st.step_stream(_batches(2), steps=0)
    assert empty.shape == (0,) and st._t == 5


def test_step_stream_staging_ahead_of_consumption(monkeypatch):
    """Acceptance (CPU CI alternative): the staging funnel proves batches
    are dispatched ahead of the consuming span — with a prefilled feed the
    consumer records ZERO stage waits, i.e. no per-step synchronous
    transfer sits between spans."""
    count = {"puts": 0}
    orig = datafeed._stage_put

    def counting_put(v, s):
        count["puts"] += 1
        return orig(v, s)

    monkeypatch.setattr(datafeed, "_stage_put", counting_put)
    st, _ = _mlp_trainer()
    n = 10
    # depth >= chunk: each span's batches are fully resident before the
    # span dispatches, so the consumer side never blocks on staging
    feed = DeviceFeed(_batches(n), mesh=st.mesh, depth=6, name="t.stream")
    feed.prefill(timeout=30.0)
    staged_before_any_step = count["puts"]
    assert staged_before_any_step >= 2 * (6 - 1)  # >= depth-1 batches ahead
    losses = st.step_stream(feed, chunk=5)
    feed.close()
    assert losses.shape == (n,)
    assert count["puts"] == 2 * n  # every batch staged exactly once
    assert feed.stats()["stage_waits"] == 0


@pytest.mark.chaos
def test_step_stream_chaos_fault_restore_and_replay():
    """The pre-mutation trainer.step contract, per chunk: a fault at a
    chunk boundary leaves trainer AND feed consistent — resuming the
    stream completes the run with params bitwise-equal to an
    uninterrupted one."""
    batches = _batches(6, seed=7)
    ref, _ = _mlp_trainer(seed=1)
    ref_losses = ref.step_stream(list(batches), chunk=2).asnumpy()

    st, _ = _mlp_trainer(seed=1)
    feed = DeviceFeed(list(batches), mesh=st.mesh, depth=6, name="t.chaos")
    chaos.arm("trainer.step", "fatal", at=2)
    try:
        with pytest.raises(FatalFault):
            st.step_stream(feed, chunk=2)
    finally:
        chaos.clear()
    # chunk 1 (2 steps) committed; the faulted chunk consumed nothing
    assert st._t == 2
    resumed = st.step_stream(feed, chunk=2).asnumpy()
    feed.close()
    assert resumed.shape == (4,)
    np.testing.assert_array_equal(ref_losses[2:], resumed)
    for v1, v2 in zip(ref._values, st._values):
        np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


@pytest.mark.chaos
def test_step_stream_chaos_fire_parity():
    """The trainer.step point fires exactly once per chunk of real work —
    a dry feed (natural end of the stream) must not consume a trigger, so
    a rule armed for the NEXT unit of work cannot discard a completed
    run's losses."""
    st, _ = _mlp_trainer()
    rule = chaos.arm("trainer.step", "fatal", at=4)
    try:
        losses = st.step_stream(_batches(6), chunk=2)
    finally:
        chaos.clear()
    assert losses.shape == (6,)
    assert rule.calls == 3  # 3 chunks ran; the dry tail fired nothing


@pytest.mark.slow
def test_step_stream_resnet_e2e():
    """End-to-end: ResNet-18 fed from a host DataLoader through the
    device-fed pipeline, uint8 batches preprocessed in-graph."""
    from mxnet_tpu.gluon.model_zoo import vision

    np.random.seed(0)
    mx.random.seed(0)
    net = vision.resnet18_v1()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, 32, 32)))
    mesh = parallel.make_mesh(dp=8)
    st = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.01}, mesh=mesh)
    batches = [(np.random.randn(8, 3, 32, 32).astype("float32"),
                np.random.randint(0, 1000, 8).astype("float32"))
               for _ in range(6)]
    feed = DeviceFeed(batches, mesh=mesh, depth=3, name="t.resnet")
    losses = st.step_stream(feed, chunk=3).asnumpy()
    feed.close()
    st.sync_back()
    assert losses.shape == (6,)
    assert np.isfinite(losses).all()
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all()


# ---------------------------------------------------------------------------
# DataLoader pin_memory
# ---------------------------------------------------------------------------

def test_dataloader_pin_memory_prestages(monkeypatch):
    """pin_memory=True routes batches through the DeviceFeed staging ring
    (not a silent no-op): leaves come back as device-backed NDArrays in the
    loader's structure and every array was dispatched via the funnel."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    count = {"puts": 0}
    orig = datafeed._stage_put

    def counting_put(v, s):
        count["puts"] += 1
        return orig(v, s)

    monkeypatch.setattr(datafeed, "_stage_put", counting_put)
    X = np.random.randn(24, 8).astype("float32")
    Y = np.arange(24).astype("float32")
    dl = DataLoader(ArrayDataset(mx.nd.array(X), mx.nd.array(Y)),
                    batch_size=8, pin_memory=True)
    seen = 0
    for x, y in dl:
        assert isinstance(x, mx.nd.NDArray) and isinstance(y, mx.nd.NDArray)
        assert isinstance(x._data, jax.Array)
        np.testing.assert_array_equal(x.asnumpy(), X[seen * 8:(seen + 1) * 8])
        seen += 1
    assert seen == 3
    assert count["puts"] == 6  # 3 batches x (data, label)
    # re-iterable: a fresh epoch builds a fresh ring
    assert sum(1 for _ in dl) == 3


def test_dataloader_pin_memory_anonymous_loader_completes():
    """Regression: `for batch in DataLoader(..., pin_memory=True)` — the
    loader object dies when its source generator exhausts INSIDE the
    stager thread; that teardown must not suppress the end-of-epoch
    sentinel (the consumer used to hang for the full feed timeout)."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    X = np.random.randn(24, 8).astype("float32")
    Y = np.arange(24).astype("float32")
    got = 0
    for x, y in DataLoader(ArrayDataset(mx.nd.array(X), mx.nd.array(Y)),
                           batch_size=8, pin_memory=True):
        got += 1
    assert got == 3


def test_devicefeed_abandoned_feed_stager_exits():
    """The stager holds no strong reference to its feed: dropping a feed
    mid-epoch without close() lets it be collected and the stager thread
    retire (no immortal worker pinning staged device buffers)."""
    import gc

    feed = DeviceFeed(_batches(8), mesh=parallel.make_mesh(dp=8), depth=2,
                      name="t.abandon")
    it = iter(feed)
    next(it)
    thread = feed._thread
    assert thread is not None and thread.is_alive()
    del feed, it
    gc.collect()
    deadline = time.monotonic() + 10.0
    while thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not thread.is_alive()


def test_dataloader_pin_memory_dict_batches_staged(monkeypatch):
    """A custom batchify returning a dict must be staged too (silently
    passing dicts through unstaged made pin_memory a no-op)."""
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    count = {"puts": 0}
    orig = datafeed._stage_put

    def counting_put(v, s):
        count["puts"] += 1
        return orig(v, s)

    monkeypatch.setattr(datafeed, "_stage_put", counting_put)
    X = np.random.randn(16, 8).astype("float32")
    Y = np.arange(16).astype("float32")

    def dict_batchify(samples):
        from mxnet_tpu.gluon.data.dataloader import default_batchify_fn
        x, y = default_batchify_fn(samples)
        return {"x": x, "y": y}

    dl = DataLoader(ArrayDataset(mx.nd.array(X), mx.nd.array(Y)),
                    batch_size=8, pin_memory=True,
                    batchify_fn=dict_batchify)
    for batch in dl:
        assert isinstance(batch["x"], mx.nd.NDArray)
    assert count["puts"] == 4  # 2 batches x 2 staged leaves


def test_dataloader_pin_memory_off_is_unchanged(monkeypatch):
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    monkeypatch.setattr(datafeed, "_stage_put",
                        lambda v, s: pytest.fail("staged without pin_memory"))
    X = np.random.randn(16, 8).astype("float32")
    dl = DataLoader(ArrayDataset(mx.nd.array(X), mx.nd.array(X[:, 0])),
                    batch_size=8)
    assert sum(1 for _ in dl) == 2


# ---------------------------------------------------------------------------
# io.PrefetchingIter lifecycle
# ---------------------------------------------------------------------------

def _nd_iter(n=32, batch=8):
    from mxnet_tpu.io.io import NDArrayIter
    X = np.random.randn(n, 8).astype("float32")
    return NDArrayIter(X, np.arange(n).astype("float32"), batch_size=batch)


def test_prefetching_iter_error_propagates_not_wedges():
    from mxnet_tpu.io.io import NDArrayIter, PrefetchingIter

    class Boom(NDArrayIter):
        def next(self):
            if self.cursor >= 16:
                raise ValueError("decode failed")
            return super().next()

    X = np.random.randn(32, 8).astype("float32")
    it = PrefetchingIter(Boom(X, np.arange(32).astype("float32"),
                              batch_size=8))
    got = 0
    with pytest.raises(ValueError, match="decode failed"):
        while True:
            it.next()
            got += 1
    assert got == 3  # cursor hits 16 after serving batches at -8, 0, 8
    # the handshake survived the raise: reset() must not deadlock and the
    # iterator must serve again
    it.reset()
    assert it.next() is not None
    it.close()


def test_prefetching_iter_reset_mid_epoch():
    from mxnet_tpu.io.io import PrefetchingIter

    it = PrefetchingIter(_nd_iter())
    it.next()
    it.next()
    it.reset()  # mid-epoch: must not deadlock, restarts from the top
    count = sum(1 for _ in it)
    assert count == 4
    it.close()


def test_prefetching_iter_multi_iter_error_keeps_good_batch():
    """A fault in ONE of several iterators must not clobber a non-failing
    iterator's already-fetched batch: only the errored slot refetches, so
    after a transient error the streams stay aligned and every good batch
    is served exactly once."""
    from mxnet_tpu.io.io import NDArrayIter, PrefetchingIter

    class TransientBoom(NDArrayIter):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self._raised = False

        def next(self):
            if self.cursor >= 8 and not self._raised:
                self._raised = True
                raise ValueError("transient decode fault")
            return super().next()

    X = np.arange(32 * 4, dtype="float32").reshape(32, 4)
    Y = np.arange(32, dtype="float32")
    it = PrefetchingIter([TransientBoom(X, Y, batch_size=8),
                          NDArrayIter(X, Y, batch_size=8)])
    good_starts, boom_starts = [], []
    while True:
        try:
            b = it.next()
        except StopIteration:
            break
        except ValueError:
            continue  # transient: consumer retries
        boom_starts.append(float(b.data[0].asnumpy()[0, 0]))
        good_starts.append(float(b.data[1].asnumpy()[0, 0]))
    assert good_starts == [0.0, 32.0, 64.0, 96.0]
    assert boom_starts == good_starts  # streams still pairwise aligned
    it.close()


def test_prefetching_iter_reiterable_after_exhaustion():
    from mxnet_tpu.io.io import PrefetchingIter

    it = PrefetchingIter(_nd_iter())
    assert sum(1 for _ in it) == 4
    it.reset()
    assert sum(1 for _ in it) == 4
    it.close()


# ---------------------------------------------------------------------------
# CachedOp concurrent dispatch
# ---------------------------------------------------------------------------

def test_cachedop_concurrent_dispatch_thread_safe():
    """Regression: the LRU cache and stats mutated with no lock while the
    serving engine dispatched from multiple HTTP threads — concurrent
    get/move_to_end/popitem corrupted the OrderedDict. Shape churn above
    capacity from 8 threads must stay correct, bounded, and consistent."""
    from mxnet_tpu.cached_op import CachedOp

    op = CachedOp(lambda a, b: a * 2 + b, capacity=4)
    errs = []
    start = threading.Barrier(8)

    def worker(k):
        try:
            start.wait(timeout=10)
            for i in range(40):
                n = 1 + (i + k) % 6  # 6 signatures churn a capacity-4 LRU
                a = mx.nd.array(np.full((n, 3), 1.0, "float32"))
                b = mx.nd.array(np.full((n, 3), float(k), "float32"))
                out = op(a, b).asnumpy()
                assert out.shape == (n, 3)
                assert np.allclose(out, 2.0 + k)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    st = op.cache_stats()
    assert st["size"] <= 4
    # duplicate compiles are tolerated, lost executables are dropped — the
    # ledger still balances: every dispatch was a hit or a miss
    assert st["hits"] + st["misses"] == 8 * 40
    assert st["misses"] >= 6  # at least one compile per signature
    assert st["evictions"] >= 1
