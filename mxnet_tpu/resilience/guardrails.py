"""In-step training guardrails: compiled numerical-fault tolerance.

Role parity: the reference guarded training numerics from the HOST — AMP's
``LossScaler.has_overflow`` pulled every gradient to numpy each step
(reference ``contrib/amp/loss_scaler.py``) and ``Module.fit`` skipped the
update after the fact. On TPU a host round-trip per gradient per step is
the difference between compute-bound and launch-bound, so the guard moves
*inside* the compiled SPMD step built by ``parallel/trainer.py``:

- **branchless skip** — ONE fused all-finite reduction over loss + grads;
  the optimizer output is committed with ``jnp.where(ok, new, old)`` on
  every parameter, optimizer-state, and BatchNorm-aux leaf, so a poisoned
  batch costs one skipped step, never a corrupted run;
- **dynamic loss scaling** as traced carried state (grow every
  ``scale_window`` clean steps, halve on overflow, floor 1.0) — power-of-2
  scale/unscale is exact in fp32, so enabling it does not perturb clean
  steps;
- **global-norm gradient clipping** fused into the same program;
- **telemetry** (loss, grad global-norm, live scale, cumulative skips,
  ok-flag) returned as one stacked device scalar vector, fetched only when
  the device says it is ready (``jax.Array.is_ready``) — the guarded step
  adds ZERO blocking host syncs beyond the loss handle the caller already
  reads — and fed to an :class:`AnomalyDetector` whose NaN-storm verdict
  raises :class:`AnomalyFault`, which ``resumable_fit`` catches like any
  injected fault and answers with restore-and-replay;
- a :class:`StepWatchdog` thread that flags steps whose results are not
  ready within a deadline (hung collective, wedged runtime) without ever
  blocking on them.

Counters export through the shared ``_stats.py`` provider hook as
``resilience.guardrails.<name>.*`` rows (profiler aggregate table, serving
``/metrics``), and :func:`health` degrades the serving ``/healthz`` while
a watchdog stall or NaN storm is live.

Checkpoint integration: :class:`GuardedStep` duck-types the trainer
surface ``resumable_fit``/``parallel.checkpoint`` consume (``step``,
``_t``, ``_values``, ``_states``, ``_params``) and contributes its guard
state (scale, clean-step counter, skip counter) to the checkpoint tree via
the ``_checkpoint_extra`` hook, so restore-and-replay reproduces the loss
-scale trajectory bitwise.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from ..observability import attribution as _attr
from ..observability import tracer as _trace
from . import chaos as _chaos
from ._stats import Registry, export_rows
from .chaos import Fault

__all__ = ["GuardedStep", "AnomalyDetector", "StepWatchdog", "AnomalyFault",
           "all_finite", "global_norm", "scale_update", "poison_nonfinite",
           "health", "all_stats"]


class AnomalyFault(Fault):
    """Raised by :class:`GuardedStep` when its :class:`AnomalyDetector`
    calls a NaN storm — a run of skipped steps dense enough that waiting
    for the next clean batch is hopeless. A :class:`~.chaos.Fault`
    subclass, so ``resumable_fit``'s default ``catch=`` answers it with
    restore-and-replay."""


# ---------------------------------------------------------------------------
# traced building blocks (pure; unit-testable without a trainer)
# ---------------------------------------------------------------------------

def all_finite(arrays):
    """One fused device-side all-finite reduction over ``arrays`` (jax
    arrays of any shapes/dtypes). Returns a scalar bool ON DEVICE — the
    caller decides if/when to pay the host transfer for it."""
    ok = jnp.bool_(True)
    for a in arrays:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(a)))
    return ok


def global_norm(arrays):
    """sqrt(sum of squared L2 norms), accumulated in fp32 regardless of the
    gradient dtype (bf16 squares overflow at ~3e38 scale sums otherwise)."""
    total = jnp.float32(0.0)
    for a in arrays:
        total = total + jnp.sum(jnp.square(a.astype(jnp.float32)))
    return jnp.sqrt(total)


def scale_update(scale, good_steps, ok, scale_factor, scale_window):
    """Traced dynamic-loss-scale schedule (the reference
    ``LossScaler.update_scale`` as pure jax): on overflow halve (by
    ``scale_factor``, floor 1.0) and reset the clean-step counter; after
    ``scale_window`` consecutive clean steps grow by ``scale_factor`` and
    reset the counter. Returns ``(new_scale, new_good_steps)``."""
    good2 = jnp.where(ok, good_steps + 1, 0)
    grow = good2 >= scale_window
    scale2 = jnp.where(ok,
                       jnp.where(grow, scale * scale_factor, scale),
                       jnp.maximum(scale / scale_factor, 1.0))
    good2 = jnp.where(grow, 0, good2)
    return scale2, good2


def poison_nonfinite(xs, y):
    """The payload of the ``nan`` chaos kind: replace every floating model
    input with NaNs (labels too, when no input is floating — integer token
    streams can't carry a NaN but their loss can). Mirrors a corrupt
    host batch / flipped HBM bits reaching the compiled step."""
    out, hit = [], False
    for x in xs:
        if jnp.issubdtype(x.dtype, jnp.floating):
            out.append(jnp.full_like(x, jnp.nan))
            hit = True
        else:
            out.append(x)
    if not hit and jnp.issubdtype(y.dtype, jnp.floating):
        y = jnp.full_like(y, jnp.nan)
    return tuple(out), y


def _fetch(arr):
    """All guardrails host readback funnels through here (tests monkeypatch
    it to prove the no-added-sync contract). Only ever called on arrays
    that reported ``is_ready()`` — a copy of finished bytes, not a stall."""
    return np.asarray(arr)


def _is_ready(arr):
    try:
        return bool(arr.is_ready())
    except AttributeError:  # older jax: no readiness probe — treat as done
        return True


# ---------------------------------------------------------------------------
# host-side monitors
# ---------------------------------------------------------------------------

class AnomalyDetector:
    """Rolling-window monitor over per-step telemetry.

    Two verdicts:

    - **NaN storm**: ≥ ``storm_skips`` skipped steps within the last
      ``storm_window`` fed steps → ``storm_active`` latches (and
      ``on_anomaly("storm", ...)`` fires once per storm). A storm means
      the data/hardware is persistently poisoned; the right answer is
      restore-and-replay, not more skipping.
    - **loss spike**: a finite loss > ``spike_factor`` × the rolling median
      of the last ``window`` finite losses (after ``min_history`` fills) —
      counted and reported, not fatal by itself.
    """

    def __init__(self, window=50, spike_factor=10.0, min_history=8,
                 storm_window=None, storm_skips=None, on_anomaly=None):
        from .. import config as _config
        if storm_window is None:
            storm_window = _config.get("MXNET_GUARDRAILS_STORM_WINDOW")
        if storm_skips is None:
            storm_skips = _config.get("MXNET_GUARDRAILS_STORM_SKIPS")
        self.storm_window = int(storm_window)
        self.storm_skips = int(storm_skips)
        self.spike_factor = float(spike_factor)
        self.min_history = int(min_history)
        self._losses = deque(maxlen=int(window))
        self._recent_skips = deque(maxlen=self.storm_window)
        self._on_anomaly = on_anomaly
        self.spikes = 0
        self.storms = 0
        self.storm_active = False

    def feed(self, loss, gnorm, scale, skips, ok):
        """One step's telemetry, host floats. Returns the verdict string
        (``"storm"`` / ``"spike"``) or None."""
        self._recent_skips.append(0 if ok else 1)
        if not ok:
            if (not self.storm_active
                    and sum(self._recent_skips) >= self.storm_skips):
                self.storm_active = True
                self.storms += 1
                if self._on_anomaly is not None:
                    self._on_anomaly("storm", loss, gnorm)
                return "storm"
            return None
        # clean steps age the window; once the skip density drops below the
        # threshold the storm is over — a monitoring-only GuardedStep
        # (raise_on_storm=False) must not report degraded health forever
        if self.storm_active and sum(self._recent_skips) < self.storm_skips:
            self.storm_active = False
        verdict = None
        if np.isfinite(loss):
            if len(self._losses) >= self.min_history:
                med = float(np.median(self._losses))
                if loss > self.spike_factor * max(abs(med), 1e-12):
                    self.spikes += 1
                    verdict = "spike"
                    if self._on_anomaly is not None:
                        self._on_anomaly("spike", loss, gnorm)
            self._losses.append(float(loss))
        return verdict

    def reset(self):
        """Forget the rolling windows (called after a restore-and-replay:
        the replayed trajectory must not inherit the storm that killed its
        predecessor)."""
        self._losses.clear()
        self._recent_skips.clear()
        self.storm_active = False


class StepWatchdog:
    """Deadline monitor for in-flight steps. ``watch(step, ready_fn)``
    registers the newest dispatched step; a daemon thread polls
    ``ready_fn`` (non-blocking, e.g. ``telemetry.is_ready``) and flags a
    *stall* — counter + ``on_stall(step, elapsed_s)`` — when the deadline
    passes first. Never blocks on device results; recovery (the result
    turning ready after all) is recorded too, so ``stalled_active``
    distinguishes "currently wedged" from "was slow once".

    ``clock`` is injectable; tests drive :meth:`_scan` directly with a fake
    clock and no thread."""

    def __init__(self, deadline_ms, poll_ms=50.0, clock=time.monotonic,
                 on_stall=None, name="default"):
        if deadline_ms <= 0:
            raise ValueError("deadline_ms must be > 0 (use no watchdog to "
                             "disable)")
        self.deadline_ms = float(deadline_ms)
        self.poll_ms = float(poll_ms)
        self.name = name
        self._clock = clock
        self._on_stall = on_stall
        self._lock = threading.Lock()
        self._current = None  # (step, started_at, ready_fn, stalled_flag[])
        self._thread = None
        self._stop = threading.Event()
        self.stalls = 0
        self.recovered = 0
        self.watched = 0

    def watch(self, step, ready_fn):
        with self._lock:
            self._current = (int(step), self._clock(), ready_fn, [False])
            self.watched += 1
        if self._thread is None:
            # re-arm after close(): the stop event must be cleared or the
            # fresh thread's first wait() returns True and it dies silently
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True,
                name="guardrails-watchdog-%s" % self.name)
            self._thread.start()

    def _scan(self):
        """One poll: resolve or age the watched step. Returns ``"stall"`` /
        ``"recovered"`` / ``"ok"`` / None (nothing watched)."""
        with self._lock:
            cur = self._current
        if cur is None:
            return None
        step, t0, ready_fn, stalled = cur
        if ready_fn():
            with self._lock:
                if self._current is cur:
                    self._current = None
            if stalled[0]:
                self.recovered += 1
                return "recovered"
            return "ok"
        elapsed = self._clock() - t0
        if elapsed * 1e3 > self.deadline_ms and not stalled[0]:
            stalled[0] = True
            self.stalls += 1
            # a wedged device is exactly when no one had a trace running:
            # dump the flight ring NOW, while the process can still write
            _attr.flight_note("watchdog_stall", watchdog=self.name,
                              step=step, elapsed_s=elapsed,
                              deadline_ms=self.deadline_ms)
            _attr.flight_dump("watchdog_stall")
            if self._on_stall is not None:
                self._on_stall(step, elapsed)
            return "stall"
        return None

    @property
    def stalled_active(self):
        """A watched step is past its deadline and still not ready."""
        with self._lock:
            cur = self._current
        return bool(cur is not None and cur[3][0] and not cur[2]())

    def _run(self):
        while not self._stop.wait(self.poll_ms / 1e3):
            self._scan()

    def close(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(2.0)
            self._thread = None
        with self._lock:
            # a stalled entry must not outlive the monitor: health() would
            # report a closed watchdog as degraded forever
            self._current = None


# ---------------------------------------------------------------------------
# the guarded step
# ---------------------------------------------------------------------------

class GuardedStep:
    """Fuse numerical guardrails into a :class:`ShardedTrainer`'s step.

    Wraps a built trainer and replaces ``step()`` with a jitted program
    that adds the all-finite skip, dynamic loss scaling, and global-norm
    clipping INSIDE the compiled step, plus host-side telemetry draining
    into an :class:`AnomalyDetector` and an optional :class:`StepWatchdog`.

    Duck-types the surface ``resumable_fit`` and ``parallel.checkpoint``
    use, so ``resumable_fit(GuardedStep(trainer), batches, ...)`` gets
    skip + scale + restore-and-replay together — the guard state rides in
    the checkpoint via ``_checkpoint_extra``.

    With defaults (no clipping, static scale 1.0) a clean run is
    **bitwise-identical** to the unguarded trainer: the extra program ops
    (finite reduction, ``where`` selects, ×1.0) never perturb the update
    math. Dynamic scaling multiplies loss and gradients by powers of two —
    exact in fp32 — so clean-step numerics still match.

    Parameters default from the ``MXNET_GUARDRAILS_*`` env knobs
    (``config.py``); pass explicit values to override. ``detector=False``
    / ``deadline_ms=0`` disable the respective monitor.
    """

    def __init__(self, trainer, clip_norm=None, dynamic_scale=None,
                 init_scale=None, scale_factor=None, scale_window=None,
                 detector=None, raise_on_storm=True, deadline_ms=None,
                 watchdog=None, name="trainer"):
        from .. import config as _config
        self._trainer = trainer
        if clip_norm is None:
            clip_norm = _config.get("MXNET_GUARDRAILS_CLIP_NORM")
        self._clip_norm = float(clip_norm) if clip_norm else None
        if dynamic_scale is None:
            dynamic_scale = bool(_config.get("MXNET_GUARDRAILS_DYNAMIC_SCALE"))
        self._dynamic = bool(dynamic_scale)
        if init_scale is None:
            init_scale = (_config.get("MXNET_GUARDRAILS_INIT_SCALE")
                          if self._dynamic else 1.0)
        if scale_factor is None:
            scale_factor = _config.get("MXNET_GUARDRAILS_SCALE_FACTOR")
        if scale_window is None:
            scale_window = _config.get("MXNET_GUARDRAILS_SCALE_WINDOW")
        self._scale_factor = float(scale_factor)
        self._scale_window = int(scale_window)
        if detector is None:
            detector = AnomalyDetector()
        self._detector = detector or None
        self._raise_on_storm = bool(raise_on_storm)
        if watchdog is None:
            if deadline_ms is None:
                deadline_ms = _config.get("MXNET_GUARDRAILS_DEADLINE_MS")
            if deadline_ms and deadline_ms > 0:
                watchdog = StepWatchdog(deadline_ms, name=name)
        self._watchdog = watchdog or None
        self.name = name
        # traced guard state: (loss_scale f32, clean-step counter i32,
        # cumulative skip counter i32), replicated over the mesh so the
        # jitted step sees one consistent copy per device
        from ..parallel.mesh import replicated
        rep = replicated(trainer._mesh)
        self._gstate = (jax.device_put(jnp.float32(init_scale), rep),
                        jax.device_put(jnp.int32(0), rep),
                        jax.device_put(jnp.int32(0), rep))
        self._gstep_fn = None
        self._pending = deque()   # (step_no, telemetry handle)
        # host mirrors, updated only from READY telemetry — stats() and
        # health() never touch the device
        self._steps = 0
        self._skips = 0
        self._clipped = 0
        self._last = {"loss": float("nan"), "grad_norm": float("nan"),
                      "loss_scale": float(init_scale), "skips": 0, "ok": True}
        _registry.add(self)

    # -- trainer duck-type surface (checkpoint/resume write through these) --

    @property
    def trainer(self):
        return self._trainer

    @property
    def mesh(self):
        return self._trainer.mesh

    @property
    def plan(self):
        return getattr(self._trainer, "plan", None)

    @property
    def _plan(self):
        # checkpoint.save records the placement through the wrapper, and
        # restore's re-plan accounting compares against it
        return getattr(self._trainer, "_plan", None)

    @property
    def _mesh(self):
        return self._trainer._mesh

    @property
    def _params(self):
        return self._trainer._params

    @property
    def _values(self):
        return self._trainer._values

    @_values.setter
    def _values(self, v):
        self._trainer._values = v

    @property
    def _states(self):
        return self._trainer._states

    @_states.setter
    def _states(self, s):
        self._trainer._states = s

    @property
    def _t(self):
        return self._trainer._t

    @_t.setter
    def _t(self, t):
        self._trainer._t = t

    @property
    def learning_rate(self):
        return self._trainer.learning_rate

    def set_learning_rate(self, lr):
        self._trainer.set_learning_rate(lr)

    def sync_back(self):
        self._trainer.sync_back()

    def forward(self, data):
        return self._trainer.forward(data)

    # -- checkpoint hook: guard state rides in the checkpoint tree ---------

    def _checkpoint_extra(self):
        return {"guard_scale": self._gstate[0],
                "guard_good": self._gstate[1],
                "guard_skips": self._gstate[2]}

    def _restore_extra(self, extra):
        from ..parallel.mesh import replicated
        rep = replicated(self._trainer._mesh)
        self._gstate = (
            jax.device_put(jnp.float32(np.asarray(extra["guard_scale"])),
                           rep),
            jax.device_put(jnp.int32(np.asarray(extra["guard_good"])), rep),
            jax.device_put(jnp.int32(np.asarray(extra["guard_skips"])), rep))
        self._skips = int(np.asarray(extra["guard_skips"]))
        self._last["loss_scale"] = float(np.asarray(extra["guard_scale"]))
        self._last["skips"] = self._skips
        self._pending.clear()  # pre-restore telemetry is another timeline
        if self._detector is not None:
            # the replay re-feeds the same steps: keeping the pre-restore
            # window would double-count their skips into a spurious storm
            self._detector.reset()

    # -- the traced step ----------------------------------------------------

    def _guarded_one_step(self, key, param_vals, states, gstate, t, lr,
                          x_args, y):
        from ..ndarray.ndarray import NDArray
        tr = self._trainer
        trainable = tr._trainable_indices()
        if tr._preprocess is not None:
            x_args = tuple(tr._preprocess(x) for x in x_args)
        scale, good, skips = gstate

        def lfn(tv):
            pv = list(param_vals)
            for i, v in zip(trainable, tv):
                pv[i] = v
            outs, aux = tr._pure(key, pv, *x_args)
            l = tr._loss(NDArray(outs[0]), NDArray(y))
            lv = l._data if isinstance(l, NDArray) else l
            mean_loss = jnp.mean(lv)
            # scale the LOSS (one scalar multiply) instead of every grad:
            # backprop linearity hands back pre-scaled grads for free
            scaled = (mean_loss.astype(jnp.float32) * scale
                      if self._dynamic else mean_loss)
            return scaled, (mean_loss, aux)

        with tr._mesh_scope():
            (_, (loss_val, aux)), grads = jax.value_and_grad(
                lfn, has_aux=True)([param_vals[i] for i in trainable])
        if self._dynamic:
            inv = jnp.float32(1.0) / scale  # exact for power-of-2 scales
            grads = [g * inv.astype(g.dtype) for g in grads]

        # ONE fused all-finite verdict over loss + every gradient — the
        # device-side replacement for has_overflow's per-grad asnumpy()
        ok = all_finite([loss_val] + grads)
        gnorm = global_norm(grads) if grads else jnp.float32(0.0)
        if self._clip_norm is not None:
            # min(1, clip/norm): a clean sub-threshold step multiplies by
            # exactly 1.0; a NaN norm yields a NaN factor, but those steps
            # are skipped by `ok` anyway
            factor = jnp.minimum(jnp.float32(1.0),
                                 self._clip_norm / (gnorm + 1e-12))
            grads = [g * factor.astype(g.dtype) for g in grads]

        new_vals = list(param_vals)
        new_states = list(states)
        for i, g in zip(trainable, grads):
            w = param_vals[i]
            w2, s2 = tr._update(w, g.astype(w.dtype), states[i], t, lr)
            # branchless commit: a skipped step selects the OLD leaf — no
            # host round-trip, no recompiled alternate program
            new_vals[i] = jnp.where(ok, w2, w)
            new_states[i] = tuple(jnp.where(ok, a, b)
                                  for a, b in zip(s2, states[i]))
        # aux (BatchNorm moving stats) fold-back, guarded the same way:
        # a skipped step must leave running stats bitwise-untouched too
        handle_to_idx = {}
        for pi, p in enumerate(tr._params):
            for d in p._data:
                handle_to_idx[id(d)] = pi
        aux_out = []
        for h, v in zip(tr._pure.aux_handles, aux):
            pi = handle_to_idx.get(id(h))
            if pi is not None:
                new_vals[pi] = jnp.where(
                    ok, v.astype(new_vals[pi].dtype), new_vals[pi])
                aux_out.append(new_vals[pi])
            else:
                aux_out.append(v)

        if self._dynamic:
            scale2, good2 = scale_update(scale, good, ok,
                                         jnp.float32(self._scale_factor),
                                         jnp.int32(self._scale_window))
        else:
            scale2, good2 = scale, good
        skips2 = skips + jnp.where(ok, jnp.int32(0), jnp.int32(1))
        telem = jnp.stack([loss_val.astype(jnp.float32), gnorm,
                           scale2.astype(jnp.float32),
                           skips2.astype(jnp.float32),
                           ok.astype(jnp.float32)])
        return (loss_val, new_vals, new_states, (scale2, good2, skips2),
                aux_out, telem)

    def _build(self):
        def gstep(key, param_vals, states, gstate, t, lr, *batch):
            x_args, y = batch[:-1], batch[-1]
            return self._guarded_one_step(key, param_vals, states, gstate,
                                          t, lr, x_args, y)

        self._gstep_fn = jax.jit(gstep, donate_argnums=(1, 2, 3))

    # -- host-side step -----------------------------------------------------

    def step(self, data, label, lr=None):
        """Drop-in for ``ShardedTrainer.step`` — same staging, same RNG
        stream (one ``next_key`` per step), same chaos contract
        (``trainer.step`` fires before any state mutates), plus the
        ``trainer.grads`` poison point on the staged batch. Returns the
        (possibly non-finite, on a skipped step) scalar loss handle without
        forcing it to host."""
        from ..ndarray.ndarray import NDArray
        from ..parallel.mesh import batch_sharding
        from .. import random as _random
        _chaos.point("trainer.step")
        tr = self._trainer
        if self._gstep_fn is None:
            self._build()
        if isinstance(data, list):
            raise TypeError(
                "GuardedStep.step: pass a TUPLE for multi-input models or "
                "a single stacked array — a list is ambiguous")
        xs = data if isinstance(data, tuple) else (data,)
        bs = batch_sharding(tr._mesh, tr._batch_axes)
        xs = tuple(jax.device_put(
            x._data if isinstance(x, NDArray) else jnp.asarray(x), bs)
            for x in xs)
        y = label._data if isinstance(label, NDArray) else jnp.asarray(label)
        y = jax.device_put(y, bs)
        if _chaos.poisoned("trainer.grads"):
            xs, y = poison_nonfinite(xs, y)
        tr._t += 1
        key = _random.next_key()
        (loss_val, tr._values, tr._states, self._gstate, aux,
         telem) = self._gstep_fn(
            key, tr._values, tr._states, self._gstate, tr._t,
            lr if lr is not None else tr._lr, *xs, y)
        if hasattr(tr, "_await_plan"):
            # multi-axis plans: the guarded step's collectives ride the
            # same watchdog bound as the bare trainer's
            tr._await_plan((loss_val, tr._values, tr._states))
        for h, v in zip(tr._pure.aux_handles, aux):
            h._data = v
        self._steps += 1
        self._pending.append((tr._t, telem))
        if self._watchdog is not None:
            self._watchdog.watch(tr._t, telem.is_ready
                                 if hasattr(telem, "is_ready")
                                 else (lambda: True))
        self._drain(block=False)
        return NDArray(loss_val)

    def _drain(self, block=False):
        """Feed READY telemetry to the detector and host mirrors. With
        ``block=False`` (the per-step path) a not-yet-ready entry ends the
        drain — zero added host syncs; ``block=True`` (:meth:`flush`)
        waits everything out."""
        storm = None
        while self._pending:
            step_no, telem = self._pending[0]
            if not block and not _is_ready(telem):
                break
            vals = _fetch(telem)
            self._pending.popleft()
            loss, gnorm, scale, skips, okf = (float(v) for v in vals)
            ok = okf >= 0.5
            self._last = {"loss": loss, "grad_norm": gnorm,
                          "loss_scale": scale, "skips": int(skips),
                          "ok": ok}
            if not ok:
                # skipped step as a timeline instant: a NaN burst shows up
                # exactly where it happened in the step sequence
                _trace.instant("guardrails.skip", guarded=self.name,
                               step=step_no, loss=loss, loss_scale=scale)
                _attr.flight_note("guard_skip", guarded=self.name,
                                  step=step_no, loss=loss,
                                  loss_scale=scale)
            else:
                _attr.flight_note("step", guarded=self.name,
                                  step=step_no, loss=loss,
                                  grad_norm=gnorm)
            self._skips = int(skips)
            if (ok and self._clip_norm is not None
                    and np.isfinite(gnorm) and gnorm > self._clip_norm):
                self._clipped += 1
            if self._detector is not None:
                verdict = self._detector.feed(loss, gnorm, scale,
                                              int(skips), ok)
                if verdict == "storm":
                    storm = (step_no, loss)
        if storm is not None and self._raise_on_storm:
            self._detector.reset()
            _trace.instant("guardrails.anomaly", guarded=self.name,
                           step=storm[0], loss=storm[1], kind="nan_storm")
            # post-mortem timeline BEFORE the raise: whoever catches the
            # fault (resumable_fit restore-and-replay) gets the last K
            # step records on disk even if the process dies next
            _attr.flight_note("anomaly", guarded=self.name,
                              step=storm[0], loss=storm[1],
                              storm="nan_storm")
            _attr.flight_dump("anomaly_fault")
            raise AnomalyFault(
                "NaN storm: >= %d skipped steps in the last %d (at step "
                "%d) — restore-and-replay" % (self._detector.storm_skips,
                                              self._detector.storm_window,
                                              storm[0]))

    def flush(self):
        """Block until all pending telemetry is drained (end of epoch /
        before reading :meth:`telemetry`)."""
        self._drain(block=True)

    def telemetry(self):
        """Latest drained per-step scalars:
        ``{loss, grad_norm, loss_scale, skips, ok}`` (host floats)."""
        self._drain(block=False)
        return dict(self._last)

    @property
    def loss_scale(self):
        """Current loss scale as drained from telemetry (host mirror)."""
        return self._last["loss_scale"]

    @property
    def skipped_steps(self):
        return self._skips

    def stats(self):
        rows = {"steps": self._steps, "skips": self._skips,
                "clipped": self._clipped,
                "loss_scale": int(self._last["loss_scale"])}
        if self._detector is not None:
            rows["spikes"] = self._detector.spikes
            rows["storms"] = self._detector.storms
        if self._watchdog is not None:
            rows["watchdog_stalls"] = self._watchdog.stalls
        return rows

    def health(self):
        """``ok`` | ``degraded`` (+ reasons) — feeds :func:`health` and the
        serving ``/healthz``."""
        reasons = []
        if self._watchdog is not None and self._watchdog.stalled_active:
            reasons.append("watchdog: step %s ms deadline exceeded"
                           % int(self._watchdog.deadline_ms))
        if self._detector is not None and self._detector.storm_active:
            reasons.append("nan_storm")
        if reasons:
            return {"status": "degraded", "reasons": reasons,
                    "skips": self._skips}
        return {"status": "ok"}

    def close(self):
        """Retire this guarded step: stop the watchdog (clearing any live
        stall) and drop it from the stats/health registry — a finished or
        abandoned training job must neither degrade ``/healthz`` nor pin
        its parameters in memory through the registry's strong ref."""
        if self._watchdog is not None:
            self._watchdog.close()
        _registry.discard(self)


# ---------------------------------------------------------------------------
# registry + process-level views (profiler rows, /metrics, /healthz)
# ---------------------------------------------------------------------------

_registry = Registry()


def all_stats():
    """``{name: stats}`` over registered :class:`GuardedStep` instances."""
    return _registry.map(lambda g: g.stats())


def health():
    """Aggregate guardrails health: ``degraded`` while any registered
    guarded step has a live watchdog stall or NaN storm."""
    bad = {name: h for name, h in
           _registry.map(lambda g: g.health()).items()
           if h["status"] != "ok"}
    if bad:
        return {"status": "degraded", "guarded": bad}
    return {"status": "ok"}


def _profiler_rows():
    rows = {}
    for name, st in all_stats().items():
        for k, v in st.items():
            rows["resilience.guardrails.%s.%s" % (name, k)] = (v, 0.0)
    return rows


export_rows(_profiler_rows)
