"""In-step training guardrails (mxnet_tpu.resilience.guardrails) tests.

Covers the ISSUE-3 acceptance criteria on the CPU oracle:
(a) a guarded clean run is BITWISE-identical to the unguarded trainer —
    the fused finite-check/where-select/×1.0 ops never perturb the math;
(b) an injected-NaN step is skipped branchlessly: params, optimizer state,
    and BatchNorm aux land bitwise-untouched while the skip counter and
    telemetry advance;
(c) the guarded step adds no blocking host sync beyond the loss handle the
    caller already reads (all readback funnels through guardrails._fetch,
    gated on Array.is_ready);
(d) the dynamic loss-scale schedule matches the reference LossScaler state
    machine (grow every window, halve on overflow, floor 1);
(e) watchdog deadline detection with a fake clock, no real sleeping;
(f) a NaN storm raises AnomalyFault and resumable_fit answers with
    restore-and-replay, ending converged with the full step count;
plus the satellites: fused AMP has_overflow (host-transfer count),
DataLoader error_policy="skip" (+ cap), chaos "nan" kind grammar, and the
observability surfaces (profiler rows, /metrics, degraded /healthz).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel
from mxnet_tpu.contrib.amp import LossScaler
from mxnet_tpu.gluon.data.dataloader import DataLoader, DataLoaderSkipLimit
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.resilience import (AnomalyDetector, AnomalyFault, GuardedStep,
                                  StepWatchdog, chaos, guardrails,
                                  resumable_fit)
from mxnet_tpu.resilience import resume as resume_mod

pytestmark = []


@pytest.fixture(autouse=True)
def _disarm_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _make_trainer(seed=0, optimizer="adam", with_bn=False):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"))
    if with_bn:
        net.add(gluon.nn.BatchNorm())
    net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, 8)))
    mesh = parallel.make_mesh()
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), optimizer,
        {"learning_rate": 1e-2}, mesh=mesh)


def _batches(n, seed):
    rng = np.random.RandomState(seed)
    return [(mx.nd.array(rng.rand(8, 8).astype("float32")),
             mx.nd.array(rng.randint(0, 4, (8,)).astype("float32")))
            for _ in range(n)]


def _values_of(t):
    return [np.asarray(v).copy() for v in t._values]


def _states_of(t):
    return [[np.asarray(s).copy() for s in st] for st in t._states]


# ---------------------------------------------------------------------------
# (a) clean-run bitwise equivalence
# ---------------------------------------------------------------------------

def test_guarded_clean_run_bitwise_equals_unguarded():
    batches = _batches(6, seed=3)
    ta = _make_trainer(seed=0)
    for x, y in batches:
        ta.step(x, y)

    tb = _make_trainer(seed=0)
    g = GuardedStep(tb)
    for x, y in batches:
        g.step(x, y)
    g.flush()

    assert g.skipped_steps == 0
    for va, vb in zip(ta._values, tb._values):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    for sa, sb in zip(ta._states, tb._states):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_guarded_clean_run_bitwise_with_batchnorm_aux():
    """Aux (BatchNorm running stats) rides the same guarded fold-back:
    every value the optimizer updates is bitwise the unguarded run's. The
    running statistics are held to one float32 ULP a step and no closer:
    the guarded step is another XLA program, and XLA:CPU is free to fuse
    and round ``momentum * old + (1 - momentum) * batch`` differently in
    the two (on this jax ``running_mean`` differs by one ULP from the
    second step on, while the weights, the gradients they come from and
    the optimizer state stay identical)."""
    batches = _batches(4, seed=5)
    ta = _make_trainer(seed=0, with_bn=True)
    for x, y in batches:
        ta.step(x, y)
    ta.sync_back()

    tb = _make_trainer(seed=0, with_bn=True)
    g = GuardedStep(tb)
    for x, y in batches:
        g.step(x, y)
    g.sync_back()

    trainable = set(ta._trainable_indices())
    assert len(trainable) < len(ta._values)     # the running stats are aux
    for i, (va, vb) in enumerate(zip(ta._values, tb._values)):
        if i in trainable:
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        else:
            np.testing.assert_array_max_ulp(np.asarray(va), np.asarray(vb),
                                            maxulp=len(batches))
    for sa, sb in zip(ta._states, tb._states):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (b) skip-step semantics under injected NaN
# ---------------------------------------------------------------------------

def test_injected_nan_step_is_skipped_bitwise():
    t = _make_trainer(seed=1)
    g = GuardedStep(t, detector=False)
    for x, y in _batches(2, seed=7):
        g.step(x, y)
    g.flush()
    vals_before = _values_of(t)
    states_before = _states_of(t)
    t_before = t._t

    chaos.arm("trainer.grads", "nan", first=1)
    loss = g.step(*_batches(1, seed=8)[0])
    g.flush()

    assert not np.isfinite(float(np.asarray(loss._data)))
    assert g.skipped_steps == 1
    assert t._t == t_before + 1  # the step was counted, just not applied
    for a, b in zip(vals_before, t._values):
        np.testing.assert_array_equal(a, np.asarray(b))
    for sa, sb in zip(states_before, t._states):
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a, np.asarray(b))
    # telemetry saw the skip
    tel = g.telemetry()
    assert tel["ok"] is False and tel["skips"] == 1
    # and the run continues cleanly after the poisoned batch
    loss2 = g.step(*_batches(1, seed=9)[0])
    g.flush()
    assert np.isfinite(float(np.asarray(loss2._data)))
    assert g.skipped_steps == 1


@pytest.mark.chaos
def test_every_injected_nan_step_skipped_none_leak():
    """Acceptance: 100% of injected-NaN steps are skipped; params stay
    finite; clean steps keep training."""
    chaos.arm("trainer.grads", "nan", every=3)
    t = _make_trainer(seed=2)
    g = GuardedStep(t, detector=False)
    for x, y in _batches(9, seed=11):
        g.step(x, y)
    g.flush()
    fired = chaos.stats()["trainer.grads"]["fires"]
    assert fired == 3
    assert g.skipped_steps == fired  # every poison skipped, only poisons
    for v in t._values:
        assert np.isfinite(np.asarray(v)).all()


def test_unguarded_trainer_absorbs_poison_motivation():
    """The problem the tentpole fixes: the raw trainer eats the NaN."""
    t = _make_trainer(seed=3)
    chaos.arm("trainer.grads", "nan", first=1)
    t.step(*_batches(1, seed=12)[0])
    assert not all(np.isfinite(np.asarray(v)).all() for v in t._values)


# ---------------------------------------------------------------------------
# (c) no added per-step host sync
# ---------------------------------------------------------------------------

def test_guarded_step_adds_no_blocking_host_sync(monkeypatch):
    """All guardrails readback goes through _fetch, and only for telemetry
    the device already finished (is_ready) — never a stall inserted into
    the dispatch pipeline; NDArray.asnumpy is never called by step()."""
    fetches = {"n": 0, "unready": 0}
    real_fetch = guardrails._fetch

    def counting_fetch(arr):
        fetches["n"] += 1
        if not guardrails._is_ready(arr):
            fetches["unready"] += 1
        return real_fetch(arr)

    monkeypatch.setattr(guardrails, "_fetch", counting_fetch)
    asnumpys = {"n": 0}
    real_asnumpy = NDArray.asnumpy
    monkeypatch.setattr(
        NDArray, "asnumpy",
        lambda self: (asnumpys.__setitem__("n", asnumpys["n"] + 1),
                      real_asnumpy(self))[1])

    t = _make_trainer(seed=4)
    g = GuardedStep(t)
    n_steps = 5
    for x, y in _batches(n_steps, seed=13):
        g.step(x, y)

    assert asnumpys["n"] == 0            # step() never forces an NDArray
    assert fetches["unready"] == 0       # never fetched un-finished work
    assert fetches["n"] <= n_steps       # one telemetry vector per step max


# ---------------------------------------------------------------------------
# (d) dynamic loss scaling
# ---------------------------------------------------------------------------

def test_scale_update_matches_reference_loss_scaler_schedule():
    """Drive the traced schedule and the reference host LossScaler with the
    same clean/overflow sequence — identical trajectories."""
    import jax.numpy as jnp

    seq = [True, True, False, True, True, True, True, False, False, True]
    ref = LossScaler(init_scale=256.0, scale_factor=2.0, scale_window=3)
    scale, good = jnp.float32(256.0), jnp.int32(0)
    for ok in seq:
        scale, good = guardrails.scale_update(scale, good, jnp.bool_(ok),
                                              jnp.float32(2.0), jnp.int32(3))
        ref.update_scale(overflow=not ok)
        assert float(scale) == ref.loss_scale
    assert float(scale) == 64.0  # sanity: the sequence actually moved it


def test_dynamic_scale_grows_and_halves_e2e():
    t = _make_trainer(seed=5)
    g = GuardedStep(t, dynamic_scale=True, init_scale=4.0, scale_window=2,
                    detector=False)
    for x, y in _batches(2, seed=14):
        g.step(x, y)
    g.flush()
    assert g.loss_scale == 8.0          # grew after 2 clean steps
    chaos.arm("trainer.grads", "nan", first=1)
    g.step(*_batches(1, seed=15)[0])
    g.flush()
    assert g.loss_scale == 4.0          # halved on overflow
    assert g.skipped_steps == 1


def test_dynamic_scale_clean_steps_bitwise_equal_unguarded():
    """Power-of-2 scale/unscale is exact in fp32: a dynamic-scale clean run
    still matches the unguarded trainer bitwise."""
    batches = _batches(4, seed=16)
    ta = _make_trainer(seed=0, optimizer="sgd")
    for x, y in batches:
        ta.step(x, y)
    tb = _make_trainer(seed=0, optimizer="sgd")
    g = GuardedStep(tb, dynamic_scale=True, init_scale=1024.0,
                    scale_window=1000, detector=False)
    for x, y in batches:
        g.step(x, y)
    g.flush()
    for va, vb in zip(ta._values, tb._values):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_clip_norm_bounds_update_and_counts():
    t = _make_trainer(seed=6)
    g = GuardedStep(t, clip_norm=1e-4, detector=False)
    for x, y in _batches(3, seed=17):
        g.step(x, y)
    g.flush()
    assert g.stats()["clipped"] == 3     # tiny threshold: every step clips
    assert g.telemetry()["grad_norm"] > 1e-4  # telemetry has the RAW norm


# ---------------------------------------------------------------------------
# (e) watchdog with a fake clock
# ---------------------------------------------------------------------------

def test_watchdog_flags_stall_then_recovery_fake_clock():
    clk = {"t": 0.0}
    stalls = []
    wd = StepWatchdog(deadline_ms=100, clock=lambda: clk["t"],
                      on_stall=lambda step, s: stalls.append((step, s)))
    ready = {"v": False}
    wd.watch(7, lambda: ready["v"])
    assert wd._scan() is None            # young: no verdict yet
    clk["t"] = 0.05
    assert wd._scan() is None and wd.stalls == 0
    clk["t"] = 0.2                       # past the 100ms deadline
    assert wd._scan() == "stall"
    assert wd.stalls == 1 and stalls == [(7, 0.2)]
    assert wd._scan() is None            # stall counted once, not per poll
    assert wd.stalled_active             # live degradation signal
    ready["v"] = True                    # device came back
    assert wd._scan() == "recovered"
    assert wd.recovered == 1 and not wd.stalled_active
    wd.close()


def test_watchdog_ok_step_never_stalls():
    clk = {"t": 0.0}
    wd = StepWatchdog(deadline_ms=100, clock=lambda: clk["t"])
    wd.watch(1, lambda: True)
    assert wd._scan() == "ok"
    clk["t"] = 99.0
    assert wd._scan() is None and wd.stalls == 0
    wd.close()


def test_watchdog_rejects_disabled_deadline():
    with pytest.raises(ValueError):
        StepWatchdog(deadline_ms=0)


def test_guarded_step_health_degrades_on_stall_and_storm():
    clk = {"t": 0.0}
    wd = StepWatchdog(deadline_ms=10, clock=lambda: clk["t"],
                      name="health_probe")
    t = _make_trainer(seed=7)
    g = GuardedStep(t, watchdog=wd, name="health_probe")
    assert g.health()["status"] == "ok"
    wd.watch(1, lambda: False)
    clk["t"] = 1.0
    wd._scan()
    h = g.health()
    assert h["status"] == "degraded" and any(
        "watchdog" in r for r in h["reasons"])
    assert guardrails.health()["status"] == "degraded"
    # serving /healthz keys off the same aggregate
    from mxnet_tpu.serving import ModelServer
    srv = ModelServer.__new__(ModelServer)  # no socket: just health()
    srv._draining = False
    srv.breaker = None
    assert srv.health()["status"] == "degraded"
    assert "guardrails" in srv.health()
    wd.close()
    g._watchdog = None
    g._detector.storm_active = True
    assert "nan_storm" in g.health()["reasons"]
    g._detector.storm_active = False
    assert guardrails.health()["status"] == "ok"


# ---------------------------------------------------------------------------
# (f) anomaly detection + restore-and-replay
# ---------------------------------------------------------------------------

def test_anomaly_detector_storm_and_spike_and_reset():
    det = AnomalyDetector(window=16, spike_factor=5.0, min_history=4,
                          storm_window=6, storm_skips=3)
    for i in range(6):
        assert det.feed(1.0 + 0.01 * i, 0.5, 1.0, 0, True) is None
    assert det.feed(50.0, 0.5, 1.0, 0, True) == "spike"
    assert det.spikes == 1 and not det.storm_active
    assert det.feed(float("nan"), float("nan"), 1.0, 1, False) is None
    assert det.feed(float("nan"), float("nan"), 1.0, 2, False) is None
    assert det.feed(float("nan"), float("nan"), 1.0, 3, False) == "storm"
    assert det.storms == 1 and det.storm_active
    det.reset()
    assert not det.storm_active
    assert det.feed(float("nan"), 0.0, 1.0, 4, False) is None  # window clear


def test_anomaly_detector_storm_unlatches_when_window_clears():
    """Regression: a monitoring-only GuardedStep (raise_on_storm=False)
    must not report degraded health forever after one transient storm —
    clean steps age the window and clear storm_active."""
    det = AnomalyDetector(storm_window=4, storm_skips=2, min_history=99)
    det.feed(float("nan"), 0.0, 1.0, 1, False)
    det.feed(float("nan"), 0.0, 1.0, 2, False)
    assert det.storm_active
    det.feed(1.0, 0.1, 1.0, 2, True)
    assert det.storm_active            # both skips still inside the window
    det.feed(1.0, 0.1, 1.0, 2, True)
    det.feed(1.0, 0.1, 1.0, 2, True)  # window now holds one skip: over
    assert not det.storm_active
    assert det.storms == 1             # the past storm stays counted


def test_restore_resets_detector_window():
    """Regression: restore-and-replay re-feeds the same steps; keeping the
    pre-restore skip window would double-count them into a spurious
    storm."""
    t = _make_trainer(seed=11)
    g = GuardedStep(t, detector=AnomalyDetector(storm_window=8,
                                                storm_skips=4))
    g._detector.feed(float("nan"), 0.0, 1.0, 1, False)
    g._detector.feed(float("nan"), 0.0, 1.0, 2, False)
    g._restore_extra(g._checkpoint_extra())
    assert sum(g._detector._recent_skips) == 0
    assert not g._detector.storm_active


def test_watchdog_rearms_after_close():
    """Regression: watch() after close() must restart a LIVE monitor, not
    a thread whose stop event is still set."""
    clk = {"t": 0.0}
    wd = StepWatchdog(deadline_ms=10, clock=lambda: clk["t"])
    wd.watch(1, lambda: True)
    assert wd._scan() == "ok"
    wd.close()
    wd.watch(2, lambda: False)
    assert not wd._stop.is_set()       # the re-armed thread can actually run
    clk["t"] = 1.0
    assert wd._scan() == "stall" and wd.stalls == 1
    wd.close()


@pytest.mark.chaos
def test_step_many_fires_trainer_grads_point():
    """step() and step_many() expose the same input-path injection point;
    one fire poisons the whole staged span."""
    t = _make_trainer(seed=12)
    chaos.arm("trainer.grads", "nan", first=1)
    rng = np.random.RandomState(24)
    xs = mx.nd.array(rng.rand(2, 8, 8).astype("float32"))
    ys = mx.nd.array(rng.randint(0, 4, (2, 8)).astype("float32"))
    t.step_many(xs, ys)
    assert chaos.stats()["trainer.grads"]["fires"] == 1
    assert not all(np.isfinite(np.asarray(v)).all() for v in t._values)


@pytest.mark.chaos
def test_nan_storm_raises_anomaly_fault():
    chaos.arm("trainer.grads", "nan", every=1)
    t = _make_trainer(seed=8)
    g = GuardedStep(t, detector=AnomalyDetector(storm_window=4,
                                                storm_skips=2))
    with pytest.raises(AnomalyFault, match="NaN storm"):
        for x, y in _batches(4, seed=18):
            g.step(x, y)


@pytest.mark.chaos
def test_resumable_fit_recovers_from_nan_storm_e2e(tmp_path):
    """Acceptance: a NaN burst dense enough to be a storm triggers
    AnomalyFault -> restore-and-replay; the run completes all steps with
    finite losses (the burst is replayed clean) and finite params."""
    chaos.arm("trainer.grads", "nan", first=3)
    t = _make_trainer(seed=0)
    g = GuardedStep(t, detector=AnomalyDetector(storm_window=6,
                                                storm_skips=3))
    before = resume_mod.resume_stats()
    losses = resumable_fit(g, _batches(8, seed=19), str(tmp_path / "s"),
                           ckpt_every=5, seed=123)
    after = resume_mod.resume_stats()
    assert after["restores"] >= before["restores"] + 1
    assert g._t == 8
    assert all(l is not None and np.isfinite(l) for l in losses)
    for v in t._values:
        assert np.isfinite(np.asarray(v)).all()


def test_close_unregisters_and_clears_stall():
    """Regression: a closed/abandoned GuardedStep must not degrade health
    forever nor stay pinned in the stats registry."""
    clk = {"t": 0.0}
    wd = StepWatchdog(deadline_ms=10, clock=lambda: clk["t"],
                      name="close_probe")
    t = _make_trainer(seed=13)
    g = GuardedStep(t, watchdog=wd, name="close_probe")
    wd.watch(1, lambda: False)
    clk["t"] = 1.0
    wd._scan()
    assert guardrails.health()["status"] == "degraded"
    g.close()
    assert not wd.stalled_active                    # stall cleared
    assert guardrails.health()["status"] == "ok"    # and unregistered
    assert "close_probe" not in guardrails.all_stats()


def test_checkpoint_restores_across_wrapper_change(tmp_path):
    """Regression: a checkpoint saved by a plain trainer restores into a
    GuardedStep (guard state stays fresh), and a guarded checkpoint
    restores into a plain trainer (guard state discarded)."""
    t = _make_trainer(seed=14)
    t.step(*_batches(1, seed=25)[0])
    parallel.save_checkpoint(t, str(tmp_path / "plain"))

    g = GuardedStep(_make_trainer(seed=15), dynamic_scale=True,
                    init_scale=32.0, detector=False)
    parallel.restore_checkpoint(g, str(tmp_path / "plain"))
    assert g._t == 1 and g.loss_scale == 32.0       # fresh guard state
    for a, b in zip(t._values, g._values):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    g.step(*_batches(1, seed=26)[0])                # and it still steps
    g.flush()

    g2 = GuardedStep(_make_trainer(seed=16), detector=False)
    g2.step(*_batches(1, seed=27)[0])
    parallel.save_checkpoint(g2, str(tmp_path / "guarded"))
    t2 = _make_trainer(seed=17)
    parallel.restore_checkpoint(t2, str(tmp_path / "guarded"))
    assert t2._t == 1
    for a, b in zip(g2._values, t2._values):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_roundtrips_guard_state(tmp_path):
    t = _make_trainer(seed=9)
    g = GuardedStep(t, dynamic_scale=True, init_scale=8.0, scale_window=3,
                    detector=False)
    for x, y in _batches(2, seed=20):
        g.step(x, y)
    g.flush()
    parallel.save_checkpoint(g, str(tmp_path / "ck"))
    scale_saved = g.loss_scale
    for x, y in _batches(2, seed=21):  # move the scale past the window
        g.step(x, y)
    g.flush()
    assert g.loss_scale != scale_saved
    parallel.restore_checkpoint(g, str(tmp_path / "ck"))
    assert g._t == 2 and g.loss_scale == scale_saved
    # and the restored guard state feeds the next compiled step cleanly
    g.step(*_batches(1, seed=22)[0])
    g.flush()
    assert g.skipped_steps == 0


# ---------------------------------------------------------------------------
# satellite: fused AMP has_overflow (host-transfer regression)
# ---------------------------------------------------------------------------

def _params_with_grads(seed=0):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    with mx.autograd.record():
        out = net(mx.nd.ones((2, 8)))
        loss = out.sum()
    loss.backward()
    return list(net.collect_params().values())


def test_amp_has_overflow_fused_no_per_grad_asnumpy(monkeypatch):
    """Regression: has_overflow must not do a blocking asnumpy() per
    gradient — the reduction is device-side, one scalar readback."""
    params = _params_with_grads()
    calls = {"n": 0}
    real = NDArray.asnumpy
    monkeypatch.setattr(
        NDArray, "asnumpy",
        lambda self: (calls.__setitem__("n", calls["n"] + 1), real(self))[1])
    scaler = LossScaler()
    assert scaler.has_overflow(params) is False
    assert calls["n"] == 0

    # poison one gradient -> detected, still zero asnumpy host pulls
    g = params[0].list_grad()[0]
    bad = np.asarray(g._data).copy()
    bad.flat[0] = np.inf
    g._data = __import__("jax").numpy.asarray(bad)
    assert scaler.has_overflow(params) is True
    assert calls["n"] == 0


def test_amp_has_overflow_empty_and_null_grads():
    scaler = LossScaler()
    assert scaler.has_overflow([]) is False
    params = _params_with_grads()
    for p in params:
        p.grad_req = "null"
    assert scaler.has_overflow(params) is False


def test_amp_update_scale_unchanged_semantics():
    s = LossScaler(init_scale=16.0, scale_factor=2.0, scale_window=2)
    s.update_scale(True)
    assert s.loss_scale == 8.0
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 16.0


# ---------------------------------------------------------------------------
# satellite: DataLoader bad-sample policy
# ---------------------------------------------------------------------------

class _FlakyDataset:
    """Raises on marked indices; the rest return (x, label)."""

    def __init__(self, n=32, bad=()):
        self._n = n
        self._bad = set(bad)

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        if i in self._bad:
            raise ValueError("corrupt record %d" % i)
        return (np.full((3,), float(i), "float32"), np.float32(i))


@pytest.mark.parametrize("num_workers", [0, 2])
def test_dataloader_skip_policy_drops_bad_samples(num_workers):
    bad = {3, 10, 11}
    dl = DataLoader(_FlakyDataset(16, bad), batch_size=4,
                    error_policy="skip", num_workers=num_workers)
    seen = []
    for batch in dl:
        x = batch[0].asnumpy()
        seen.extend(int(v) for v in x[:, 0])
    assert sorted(seen) == sorted(set(range(16)) - bad)


def test_dataloader_raise_policy_is_default_and_propagates():
    dl = DataLoader(_FlakyDataset(8, bad={1}), batch_size=4)
    with pytest.raises(ValueError, match="corrupt record 1"):
        list(dl)


@pytest.mark.parametrize("num_workers", [0, 2])
def test_dataloader_skip_cap_fails_loudly(num_workers):
    ds = _FlakyDataset(16, bad=set(range(16)))  # data-wide corruption
    dl = DataLoader(ds, batch_size=4, error_policy="skip", max_skips=5,
                    num_workers=num_workers)
    with pytest.raises(DataLoaderSkipLimit, match="MXNET_DATALOADER"):
        list(dl)


def test_dataloader_skip_counter_reaches_profiler():
    from mxnet_tpu import profiler
    base = profiler.get_aggregate_stats().get(
        "guardrails.dataloader.skipped", {"calls": 0})["calls"]
    dl = DataLoader(_FlakyDataset(8, bad={0, 5}), batch_size=4,
                    error_policy="skip")
    assert len(list(dl)) == 2
    now = profiler.get_aggregate_stats()["guardrails.dataloader.skipped"]
    assert now["calls"] == base + 2


def test_dataloader_skip_policy_whole_batch_gone_still_iterates():
    dl = DataLoader(_FlakyDataset(8, bad={0, 1, 2, 3}), batch_size=4,
                    error_policy="skip")
    batches = list(dl)
    assert len(batches) == 1  # first batch vanished entirely, no None leaked
    assert batches[0][0].shape == (4, 3)


def test_dataloader_rejects_unknown_policy():
    with pytest.raises(ValueError, match="error_policy"):
        DataLoader(_FlakyDataset(4), batch_size=2, error_policy="ignore")


def test_dataloader_skip_policy_bad_batchify():
    """A sample that fetches fine but can't batchify (non-numeric payload)
    is attributed per-sample and dropped too."""
    class _GarbageDataset(_FlakyDataset):
        def __getitem__(self, i):
            if i == 2:
                return ("corrupt-blob", np.float32(i))  # unconvertible
            return super().__getitem__(i)

    def strict_batchify(samples):
        xs = np.stack([np.asarray(s[0], "float32") for s in samples])
        ys = np.asarray([s[1] for s in samples], "float32")
        return nd.array(xs), nd.array(ys)

    dl = DataLoader(_GarbageDataset(8), batch_size=4, error_policy="skip",
                    batchify_fn=strict_batchify)
    seen = []
    for x, y in dl:
        seen.extend(int(v) for v in y.asnumpy())
    assert sorted(seen) == [0, 1, 3, 4, 5, 6, 7]


# ---------------------------------------------------------------------------
# satellite: chaos "nan" kind
# ---------------------------------------------------------------------------

def test_chaos_nan_kind_grammar_and_counters():
    rules = chaos.arm_from_env("trainer.grads:nan:every=2")
    assert len(rules) == 1 and rules[0].kind == "nan"
    assert [chaos.poisoned("trainer.grads") for _ in range(4)] == \
        [False, True, False, True]
    st = chaos.stats()["trainer.grads"]
    assert st["calls"] == 4 and st["fires"] == 2


def test_chaos_nan_never_raises_and_point_returns_marker():
    chaos.arm("p.nan", "nan", first=1)
    assert chaos.point("p.nan") == "nan"  # no exception
    assert chaos.point("p.nan") is None


def test_poison_nonfinite_floats_and_int_fallback():
    import jax.numpy as jnp
    xs, y = guardrails.poison_nonfinite(
        (jnp.ones((2, 2)), jnp.ones((2,), jnp.int32)), jnp.ones((2,)))
    assert np.isnan(np.asarray(xs[0])).all()
    assert np.asarray(xs[1]).dtype == np.int32  # ints can't carry NaN
    assert not np.isnan(np.asarray(y)).any()    # an input took the poison
    xs2, y2 = guardrails.poison_nonfinite(
        (jnp.ones((2,), jnp.int32),), jnp.ones((2,)))
    assert np.isnan(np.asarray(y2)).all()       # all-int inputs: label pays


# ---------------------------------------------------------------------------
# observability: profiler aggregate rows
# ---------------------------------------------------------------------------

def test_guardrails_counters_reach_profiler_aggregate():
    from mxnet_tpu import profiler
    t = _make_trainer(seed=10)
    g = GuardedStep(t, name="agg_probe_guard", detector=False)
    chaos.arm("trainer.grads", "nan", first=1)
    for x, y in _batches(2, seed=23):
        g.step(x, y)
    g.flush()
    stats = profiler.get_aggregate_stats()
    assert stats["resilience.guardrails.agg_probe_guard.steps"]["calls"] == 2
    assert stats["resilience.guardrails.agg_probe_guard.skips"]["calls"] == 1
    table = profiler.dumps()
    assert "resilience.guardrails.agg_probe_guard.skips" in table
