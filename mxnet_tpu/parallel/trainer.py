"""ShardedTrainer: the TPU-native multi-chip training step.

Role parity: this replaces the reference's entire distributed update stack —
DataParallelExecutorGroup batch slicing (`module/executor_group.py:282`),
KVStore push/pull gradient sync (`src/kvstore/kvstore_dist.h`,
`kvstore_nccl.h`), and server-side optimizer (`kvstore_dist_server.h:346`) —
with ONE jitted SPMD program over a named mesh (SURVEY §5.8): forward,
backward, gradient allreduce (inserted by XLA's SPMD partitioner because the
batch is dp-sharded while params are replicated/TP-sharded), and the
optimizer update, all fused, with parameter buffers donated in place.
"""
from __future__ import annotations

import contextlib
import itertools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import pcache as _pcache
from .. import random as _random
from ..ndarray.ndarray import NDArray
from ..observability import tracer as _trace
from ..resilience import chaos as _chaos
from .functional import functionalize, functional_optimizer, shard_params
from .mesh import make_mesh, batch_sharding, mesh_scope, replicated

__all__ = ["ShardedTrainer"]

# distinct stats name per auto-wrapped step_stream feed (the datafeed
# registry is latest-wins per name; concurrent trainers must not evict
# each other's telemetry)
_stream_seq = itertools.count()


def _owned_on(v, device):
    """An owning single-device copy of ``v``: device_put alone is zero-copy
    when source and target share a device, and handing out a buffer that the
    trainer's donated step also holds would let the donation delete it."""
    return jnp.array(jax.device_put(v, device), copy=True)


def _note_bytes(sp, *arrays):
    """The ``bytes`` of a span that placed ``arrays`` (a recording one)."""
    if sp.ctx is not None:
        sp.set(bytes=sum(a.nbytes for a in arrays))


@contextlib.contextmanager
def _launching():
    compiled = _pcache.stats()["compile_s"]
    with _trace.span("trainer.launch") as sp:
        yield
        sp.set(first=_pcache.stats()["compile_s"] != compiled)


def _launch_span():
    """``trainer.launch``: the call of the compiled step. ``first`` says
    that the call compiled (jax reported a backend compile across it):
    that one holds the trace, the lowering and the compile or cache load,
    which the ``jax.*`` events inside it name; a steady one is the
    runtime's enqueue."""
    return _launching() if _trace.tracer._enabled else _trace._NULL_SPAN


class ShardedTrainer:
    """Data/tensor-parallel trainer over a jax.sharding.Mesh.

    Usage::

        mesh = parallel.make_mesh(dp=4, tp=2)
        trainer = parallel.ShardedTrainer(net, loss_fn, 'sgd',
                                          {'learning_rate': 0.1}, mesh=mesh,
                                          param_rules=[('dense.*weight',
                                                        PartitionSpec(None, 'tp'))])
        for x, y in batches:
            loss = trainer.step(x, y)
        trainer.sync_back()   # write updated values into the Block's params

    Gradient sync happens *inside* the compiled step via XLA collectives
    over ICI — there are no kvstore processes (SURVEY §2.4 north star).
    """

    def __init__(self, block, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_rules=None, batch_axes=("dp",),
                 dtype=None, preprocess=None, plan=None):
        """``preprocess``: optional callable applied to each model input
        INSIDE the compiled step (e.g. uint8 NHWC → normalized bf16 NCHW).
        Host ships raw uint8 over the link (4× fewer bytes than f32); the
        cast/normalize/transpose fuse into the step on device — the
        TPU-native input pipeline (reference normalized on host CPU,
        src/io/iter_normalize.h).

        ``plan``: a :class:`~mxnet_tpu.parallel.planner.ShardingPlan` —
        the mesh, the batch axes, and the naming-convention param rules
        all derive from it (explicit ``mesh`` still wins if given).
        Caller ``param_rules`` are PREPENDED: rule matching is
        first-match-wins, so an explicit rule overrides the plan's
        convention for the params it names (e.g. a tp spec on a
        ``stack_*`` param) and the plan's rules back-fill the rest. The
        jitted step is then compiled against the resulting shardings,
        checkpoints record the plan, and multi-axis placements get
        their fused-step result waits bounded by the collective
        watchdog."""
        # the part of set-up that grows with the model and the mesh:
        # functionalize, shard, the per-parameter copy / cast / placement
        with _trace.span("trainer.build") as sp:
            self._build(block, loss_fn, optimizer, optimizer_params, mesh,
                        param_rules, batch_axes, dtype, preprocess, plan)
            _note_bytes(sp, *self._values,
                        *(x for state in self._states for x in state))
            sp.set(params=len(self._params), devices=self._mesh.devices.size)

    def _build(self, block, loss_fn, optimizer, optimizer_params, mesh,
               param_rules, batch_axes, dtype, preprocess, plan):
        self._block = block
        self._loss = loss_fn
        self._preprocess = preprocess
        self._plan = plan
        if plan is not None:
            if mesh is None:
                mesh = plan.mesh()
            batch_axes = plan.data_axes
            param_rules = list(param_rules or []) + list(plan.param_rules())
        self._mesh = mesh if mesh is not None else make_mesh()
        optimizer_params = dict(optimizer_params or {})
        self._lr = optimizer_params.get("learning_rate", 0.01)
        self._pure, self._params = functionalize(block, train=True)
        self._pure_eval, _ = functionalize(block, train=False)
        init_state, self._update = functional_optimizer(optimizer,
                                                        **optimizer_params)
        self._batch_axes = tuple(batch_axes)

        # place parameters on the mesh
        self._shardings = shard_params(self._params, self._mesh, param_rules)
        self._values = []
        for p, s in zip(self._params, self._shardings):
            src = p.data()._data
            v = src.astype(dtype) if dtype is not None else src
            if v is src:
                # own the buffer BEFORE placing (astype is a no-op alias
                # when the dtype already matches): device_put is
                # zero-copy for the shard landing on the source device,
                # and the donated step deleting a buffer the Block's
                # eager param still references would kill eager forwards
                # (and any second trainer built from the same Block)
                # after one step — the sync_back/_owned_on hazard, at
                # init
                v = jnp.array(v, copy=True)
            self._values.append(jax.device_put(v, s))
        self._states = [tuple(jax.device_put(x, s) for x in init_state(v))
                        for v, s in zip(self._values, self._shardings)]
        self._t = 0
        self._step_fn = None
        self._step_many_fn = None
        self._aux_handles = []

    @property
    def mesh(self):
        return self._mesh

    @property
    def param_values(self):
        """``{parameter name: jax.Array}`` — the live values on the mesh
        (donated to the next step: read, don't keep)."""
        return {p.name: v for p, v in zip(self._params, self._values)}

    @property
    def plan(self):
        """The :class:`~mxnet_tpu.parallel.planner.ShardingPlan` this
        trainer was built from, or ``None`` (mesh given directly)."""
        return self._plan

    def _await_plan(self, outputs):
        """Multi-axis plans (pp/ep/sp > 1): bound the wait for the fused
        step's collectives — a hung pipeline stage or MoE all_to_all
        raises :class:`~mxnet_tpu.resilience.elastic.CollectiveTimeout`
        instead of wedging the job forever. Free (async semantics
        untouched) unless ``MXNET_ELASTIC_COLLECTIVE_DEADLINE_MS`` is
        armed; the results are already committed to the trainer, so the
        state stays consistent for the re-forming restart either way."""
        if self._plan is not None and self._plan.multi_axis:
            from ..resilience.elastic import guard_wait
            guard_wait(outputs, op="trainer.dispatch")

    def _mesh_scope(self):
        """Entered around every traced forward/backward of this trainer,
        so ops that must shard themselves (``ops/nn.py`` flash attention)
        see the mesh and the batch axes the step is compiled for."""
        return mesh_scope(self._mesh, self._batch_axes)

    def _trainable_indices(self):
        return [i for i, p in enumerate(self._params)
                if getattr(p, "grad_req", "write") != "null"]

    def _one_step(self, key, param_vals, states, t, lr, x_args, y):
        """Traced single step: fwd, bwd (trainable params only), optimizer
        update, and aux (BatchNorm moving stats) folded back into the
        carried parameter values so stats accumulate across steps."""
        pure = self._pure
        loss_block = self._loss
        update = self._update
        trainable = self._trainable_indices()
        if self._preprocess is not None:
            x_args = tuple(self._preprocess(x) for x in x_args)

        def lfn(tv):
            pv = list(param_vals)
            for i, v in zip(trainable, tv):
                pv[i] = v
            outs, aux = pure(key, pv, *x_args)
            out = outs[0]
            l = loss_block(NDArray(out), NDArray(y))
            lv = l._data if isinstance(l, NDArray) else l
            return jnp.mean(lv), (outs, aux)

        with self._mesh_scope():
            (loss_val, (_, aux)), grads = jax.value_and_grad(
                lfn, has_aux=True)([param_vals[i] for i in trainable])
        new_vals = list(param_vals)
        new_states = list(states)
        with jax.named_scope("optimizer"):
            for i, g in zip(trainable, grads):
                w = param_vals[i]
                w2, s2 = update(w, g.astype(w.dtype), states[i], t, lr)
                new_vals[i] = w2
                new_states[i] = s2
        # aux state (running mean/var) becomes the carried value of its
        # parameter slot — grad_req='null' params are never touched by the
        # optimizer (a wd>0 zero-grad "update" would decay running stats)
        handle_to_idx = {}
        for pi, p in enumerate(self._params):
            for d in p._data:
                handle_to_idx[id(d)] = pi
        for h, v in zip(pure.aux_handles, aux):
            pi = handle_to_idx.get(id(h))
            if pi is not None:
                new_vals[pi] = v.astype(new_vals[pi].dtype)
        return loss_val, new_vals, new_states, aux

    def _build_step(self):
        def step(key, param_vals, states, t, lr, *batch):
            x_args, y = batch[:-1], batch[-1]
            return self._one_step(key, param_vals, states, t, lr, x_args, y)

        self._step_fn = jax.jit(step, donate_argnums=(1, 2))

    def _build_step_many(self):
        def many(key, param_vals, states, t0, lr, *xs_ys):
            def body(carry, xy):
                key, pv, st, t = carry
                key, sub = jax.random.split(key)
                loss, pv2, st2, _aux = self._one_step(
                    sub, pv, st, t, lr, xy[:-1], xy[-1])
                return (key, pv2, st2, t + 1), loss

            (key, pv, st, t), losses = jax.lax.scan(
                body, (key, list(param_vals), list(states), t0),
                tuple(xs_ys))
            return losses, pv, st

        self._step_many_fn = jax.jit(many, donate_argnums=(1, 2))

    def step(self, data, label, lr=None):
        """One fused fwd+bwd+allreduce+update step. ``data`` is a single
        array, or a TUPLE of model inputs (e.g. BERT's tokens+segments) —
        a tuple means multi-input; lists are rejected as ambiguous. Each
        input is batch-sharded over the dp axes. Returns the (replicated)
        scalar loss as a host float-convertible array."""
        with _trace.span("trainer.step", t=self._t + 1,
                         step_num=self._t + 1):
            return self._step_impl(data, label, lr)

    def _step_impl(self, data, label, lr):
        # injection point BEFORE any state mutates: a fault leaves the
        # trainer consistent, so restore-and-replay (resilience.resume)
        # resumes from exactly the pre-step state
        _chaos.point("trainer.step")
        if self._step_fn is None:
            self._build_step()
        with _trace.span("trainer.place") as sp:
            xs, y = self._place_batch(data, label)
            _note_bytes(sp, y, *xs)
        # numerical-fault injection on the step INPUT path (chaos kind
        # "nan"): models a corrupt batch reaching the compiled step. The
        # unguarded trainer will absorb the poison into its parameters —
        # wrap with resilience.guardrails.GuardedStep to skip it instead.
        # Fired BEFORE _t advances: a raising kind armed here must honor
        # the same pre-mutation contract as trainer.step above.
        if _chaos.poisoned("trainer.grads"):
            from ..resilience.guardrails import poison_nonfinite
            xs, y = poison_nonfinite(xs, y)
        self._t += 1
        key = _random.next_key()
        with _launch_span():
            loss_val, self._values, self._states, aux = self._step_fn(
                key, self._values, self._states, self._t,
                lr if lr is not None else self._lr, *xs, y)
        self._await_plan((loss_val, self._values, self._states))
        # functional aux-state writeback (BatchNorm moving stats)
        for h, v in zip(self._pure.aux_handles, aux):
            h._data = v
        return NDArray(loss_val)

    def _place_batch(self, data, label):
        """One step's inputs and label on the mesh, batch-sharded."""
        if isinstance(data, list):
            raise TypeError(
                "ShardedTrainer.step: pass a TUPLE for multi-input models "
                "or a single stacked array — a list is ambiguous")
        xs = data if isinstance(data, tuple) else (data,)
        bs = batch_sharding(self._mesh, self._batch_axes)
        xs = tuple(jax.device_put(
            x._data if isinstance(x, NDArray) else jnp.asarray(x), bs)
            for x in xs)
        y = label._data if isinstance(label, NDArray) else jnp.asarray(label)
        return xs, jax.device_put(y, bs)

    def lower_step(self, data, label):
        """The :meth:`step` program for this batch as a
        ``jax.stages.Lowered`` — ``.compile()`` it to read what the
        compiler made of the step (``as_text()``: kernels, collectives,
        dtypes; ``memory_analysis()``: bytes per device). Runs nothing
        and leaves the trainer's state and RNG stream untouched."""
        if self._step_fn is None:
            self._build_step()
        xs, y = self._place_batch(data, label)
        return self._step_fn.lower(
            jax.random.PRNGKey(0), self._values, self._states, self._t + 1,
            self._lr, *xs, y)

    def step_many(self, data, label, lr=None):
        """Run ``data.shape[0]`` fused training steps in ONE compiled
        program (`lax.scan` over the leading steps axis). This amortizes
        per-dispatch host/runtime latency — the TPU-idiomatic training loop
        shape — and keeps params, optimizer state, and BatchNorm running
        stats on-device across the whole span. Returns the per-step losses
        as an NDArray of shape (n_steps,).

        data:  (n_steps, batch, ...) — or a TUPLE of such arrays for
        multi-input models (lists are rejected as ambiguous); label:
        (n_steps, batch, ...).
        """
        with _trace.span("trainer.step_many", t0=self._t + 1):
            return self._step_many_impl(data, label, lr)

    def _step_many_impl(self, data, label, lr):
        _chaos.point("trainer.step")  # same pre-mutation contract as step()
        if self._step_many_fn is None:
            self._build_step_many()
        if isinstance(data, list):
            raise TypeError(
                "ShardedTrainer.step_many: pass a TUPLE for multi-input "
                "models or a single (n_steps, batch, ...) array — a list "
                "is ambiguous")
        data_list = data if isinstance(data, tuple) else (data,)
        with _trace.span("trainer.place") as sp:
            xs, ys = self._place_span(
                tuple(x._data if isinstance(x, NDArray) else jnp.asarray(x)
                      for x in data_list),
                label._data if isinstance(label, NDArray)
                else jnp.asarray(label))
            _note_bytes(sp, ys, *xs)
        n_steps = xs[0].shape[0]
        # same input-path injection as step(): one fire poisons the whole
        # staged span (this call IS one input staging)
        if _chaos.poisoned("trainer.grads"):
            from ..resilience.guardrails import poison_nonfinite
            xs, ys = poison_nonfinite(xs, ys)
        key = _random.next_key()
        # t is 1-based inside updates (matches step(): first call sees t=1)
        with _launch_span():
            losses, self._values, self._states = self._step_many_fn(
                key, self._values, self._states, self._t + 1,
                lr if lr is not None else self._lr, *xs, ys)
        # _t commits WITH the values (the dispatch already consumed the
        # donated state): a CollectiveTimeout out of the guarded wait
        # below must leave counter and params consistent for the
        # emergency checkpoint the re-forming exit path writes
        self._t += n_steps
        self._await_plan((losses, self._values, self._states))
        # aux values (BatchNorm running stats) live in the carried values;
        # sync_back() lands them in the Block's handles. Doing it here per
        # call would add ~2 host roundtrips per BN layer per span.
        return NDArray(losses)

    def _place_span(self, xs, ys):
        """Place already-stacked ``(n_steps, batch, ...)`` inputs/labels on
        the mesh in the span layout ``_step_many_fn`` consumes: dim 0 =
        steps (unsharded), dim 1 = batch sharded over ALL batch axes
        jointly (matches ``batch_sharding`` used by step()). The single
        definition of the span sharding convention — step_many and
        step_stream both route through it."""
        spec = PartitionSpec(None, self._batch_axes)
        xs = tuple(jax.device_put(x, NamedSharding(self._mesh, spec))
                   for x in xs)
        ys = jax.device_put(ys, NamedSharding(
            self._mesh,
            PartitionSpec(None, self._batch_axes) if ys.ndim >= 2
            else PartitionSpec(None)))
        return xs, ys

    def _stack_span(self, xs_list, ys_list):
        """Stack per-step staged device batches into the span layout.
        Device-side only: the inputs are already resident (DeviceFeed
        staged them), so this is a concat + reshard in HBM, never an H2D
        transfer."""
        n_inputs = len(xs_list[0])
        return self._place_span(
            tuple(jnp.stack([row[i] for row in xs_list])
                  for i in range(n_inputs)),
            jnp.stack(ys_list))

    def step_stream(self, feed, steps=None, chunk=None, lr=None,
                    preemption=None):
        """Run training steps off a :class:`~.datafeed.DeviceFeed` (or any
        batch source, auto-wrapped) in chunked fused spans: chunk N runs as
        ONE compiled ``lax.scan`` program (the :meth:`step_many` function,
        params/opt-state donated across chunks) while the feed's stager
        thread keeps chunk N+1's batches flowing onto the device — the H2D
        staging that :meth:`step` pays serially and :meth:`step_many` pays
        up front for the whole span overlaps with compute instead.

        Parameters
        ----------
        feed : DeviceFeed or iterable
            Source of ``(data, label)`` batches. A non-DeviceFeed source is
            wrapped in one on this trainer's mesh/batch axes (and closed on
            return); pass an explicit ``DeviceFeed`` to control depth or to
            keep the feed alive across calls (restore-and-replay resumes
            consuming where the fault stopped it).
        steps : int, optional
            Max steps to run (default: until the feed is exhausted).
        chunk : int, optional
            Steps per compiled span (default ``MXNET_DATAFEED_CHUNK``). A
            short tail compiles one extra span program for its length.
        lr : float, optional
            Learning-rate override, as in :meth:`step`.
        preemption : PreemptionHandler, optional
            Polled at every chunk boundary (the step-stream's consistency
            points). A delivered eviction notice raises
            :class:`~mxnet_tpu.resilience.elastic.Preempted` BEFORE the
            next chunk consumes from the feed, with all completed chunks
            committed to ``_t`` — the caller emergency-checkpoints and
            ``feed.flush()`` releases the staged-ahead batches (replay
            re-reads them from the source after restart).

        Returns the per-step losses as an NDArray of shape ``(n_run,)``.
        Fires the same pre-mutation ``trainer.step`` chaos point as
        :meth:`step`/:meth:`step_many` once per chunk BEFORE consuming from
        the feed, so a fault leaves both the trainer and the feed
        consistent for restore-and-replay; the ``trainer.grads`` poison
        point fires per staged span. BatchNorm aux stats land in the Block
        on :meth:`sync_back`, as with :meth:`step_many`.
        """
        from .datafeed import DeviceFeed
        if chunk is None:
            from .. import config as _config
            chunk = _config.get("MXNET_DATAFEED_CHUNK")
        chunk = int(chunk)
        if chunk < 1:
            raise ValueError("chunk must be >= 1, got %r" % (chunk,))
        if steps is not None and steps < 0:
            raise ValueError("steps must be >= 0, got %r" % (steps,))
        if self._step_many_fn is None:
            self._build_step_many()
        owned = not isinstance(feed, DeviceFeed)
        if owned:
            feed = DeviceFeed(feed, mesh=self._mesh,
                              batch_axes=self._batch_axes,
                              name="step_stream.%d" % next(_stream_seq))
        try:
            it = iter(feed)
            losses_out = []
            remaining = None if steps is None else int(steps)
            chunk_idx = 0
            while remaining is None or remaining > 0:
                if preemption is not None and preemption.triggered():
                    from ..resilience.elastic import Preempted
                    raise Preempted(step=self._t)
                # the chunk span covers feed consumption (where stage
                # waits appear as nested datafeed.consumer_wait spans),
                # span stacking, and the fused dispatch — one timeline box
                # per compiled lax.scan program. Cancelled (not recorded)
                # when the feed turns out to be dry.
                with _trace.span("trainer.chunk", feed=feed.name,
                                 chunk=chunk_idx, t0=self._t + 1) as sp:
                    # peek ONE batch first so a dry feed never fires the
                    # chaos point (exactly one fire per chunk of real
                    # work, matching step()/step_many() parity), then fire
                    # BEFORE any state mutates — and hand the peeked batch
                    # back on a fault so the replay loses nothing
                    try:
                        first = next(it)
                    except StopIteration:
                        sp.cancel()
                        break
                    try:
                        _chaos.point("trainer.step")
                    except BaseException:
                        feed._unget(first)
                        raise
                    take = (chunk if remaining is None
                            else min(chunk, remaining))
                    xs_list, ys_list = [first[0]], [first[1]]
                    while len(xs_list) < take:
                        try:
                            xs, y = next(it)
                        except StopIteration:
                            break
                        xs_list.append(xs)
                        ys_list.append(y)
                    n = len(xs_list)
                    sp.set(steps=n)
                    with _trace.span("trainer.place") as place:
                        xs, ys = self._stack_span(xs_list, ys_list)
                        _note_bytes(place, ys, *xs)
                    if _chaos.poisoned("trainer.grads"):
                        from ..resilience.guardrails import poison_nonfinite
                        xs, ys = poison_nonfinite(xs, ys)
                    key = _random.next_key()
                    with _launch_span():
                        losses, self._values, self._states = \
                            self._step_many_fn(
                                key, self._values, self._states,
                                self._t + 1,
                                lr if lr is not None else self._lr, *xs, ys)
                    # counter commits with the values (see step_many)
                    self._t += n
                    self._await_plan((losses, self._values, self._states))
                    losses_out.append(losses)
                    if remaining is not None:
                        remaining -= n
                chunk_idx += 1
        finally:
            if owned:
                feed.close()
        if not losses_out:
            return NDArray(jnp.zeros((0,), jnp.float32))
        if len(losses_out) == 1:
            return NDArray(losses_out[0])
        return NDArray(jnp.concatenate(losses_out))

    def forward(self, data):
        """Sharded inference forward (no grad, no update)."""
        x = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        x = jax.device_put(x, batch_sharding(self._mesh, self._batch_axes))
        if self._preprocess is not None:
            x = self._preprocess(x)
        key = _random.next_key()
        with self._mesh_scope():
            (out, *_), _aux = self._pure_eval(key, self._values, x)
        return NDArray(out)

    def sync_back(self):
        """Write the trainer's (possibly sharded) values back into the
        Block's Parameters — gathers shards first, then lands each ctx copy
        on its own device (owned, so the next donating step can't delete
        what the Block now references) and eager forwards keep working."""
        for p, v in zip(self._params, self._values):
            full = jax.device_put(v, replicated(self._mesh))
            for d in p._data:
                d._data = _owned_on(full, d.ctx.jax_device)

    @property
    def learning_rate(self):
        return self._lr

    def set_learning_rate(self, lr):
        self._lr = lr
