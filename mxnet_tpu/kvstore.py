"""KVStore: the gradient-aggregation / parameter-sync surface.

Parity surface: reference ``python/mxnet/kvstore.py`` +
``src/kvstore/`` (N12-N15 in SURVEY §2.1): `KVStore::Create` modes
`local`/`device`/`nccl`/`dist_sync`/`dist_async`/`dist_device_sync`
(`src/kvstore/kvstore.cc:40`), Init/Push/Pull/PushPull/set_updater
(`include/mxnet/kvstore.h:105-438`).

TPU-native design (SURVEY §5.8): there are no server processes and no key
sharding — a single-process store aggregates across local device copies
(role of `CommDevice` `src/kvstore/comm.h:451`), and the distributed mode
``dist_tpu_sync`` [aliases: dist_sync, dist_device_sync, nccl] rides XLA
collectives: `rank`/`num_workers` come from `jax.process_index/count`, and
cross-host reduction happens *inside* the compiled training step (see
mxnet_tpu.parallel) — the eager push/pull path here uses a psum over the
global mesh when multiple processes are attached. `dist_async` is
anti-idiomatic on TPU and raises (SURVEY §2.4).
"""
from __future__ import annotations

import pickle

import numpy as _np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import ndarray as _nd
from .ndarray.ndarray import NDArray
from .resilience import chaos as _chaos
from .resilience import retry as _retry

__all__ = ["KVStore", "create"]


def _key_str(key):
    return str(key)


class KVStore:
    """Single-interface store over local devices / TPU mesh."""

    def __init__(self, kind="local", retry_policy=None):
        self._kind = kind
        self._store = {}
        self._updater = None
        self._optimizer = None
        self._compression_params = None
        self._residuals = {}
        self._is_dist = kind.startswith("dist") or kind == "nccl"
        # transient faults on push/pull (a flaky collective, an injected
        # chaos fault) are absorbed by the env-configured "retry.kvstore"
        # policy (own name: uncontended counters, attributable /metrics
        # rows); pass retry_policy=False to disable
        if retry_policy is None:
            retry_policy = _retry.named_policy("retry.kvstore")
        self._retry = retry_policy or None

    # ---- identity ---------------------------------------------------------
    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        return jax.process_index() if self._is_dist else 0

    @property
    def num_workers(self):
        return jax.process_count() if self._is_dist else 1

    # ---- init/push/pull ---------------------------------------------------
    def init(self, key, value):
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            # the store owns its buffer (reference: server/comm buffers are
            # separate allocations) — aliasing the caller's weight would let
            # a donated optimizer update delete the caller's array
            self._store[k] = NDArray(jnp.array(v._data, copy=True),
                                     ctx=v._ctx)
            # re-initializing a key starts a fresh compression history
            for rk in [rk for rk in self._residuals if rk[0] == k]:
                del self._residuals[rk]

    def _reduce(self, values):
        """Sum gradients across device copies (reference CommDevice::Reduce
        `src/kvstore/comm.h:451`). On TPU the copies live on one chip or a
        mesh; the eager sum lowers to XLA adds / ICI transfers."""
        if len(values) == 1:
            out = values[0]._data
        else:
            dev0 = values[0]._data.devices() if hasattr(values[0]._data, "devices") else None
            acc = values[0]._data
            for v in values[1:]:
                vv = v._data
                acc = acc + (jax.device_put(vv, next(iter(dev0)))
                             if dev0 and vv.devices() != values[0]._data.devices()
                             else vv)
            out = acc
        if self._is_dist and jax.process_count() > 1:
            # a peer lost mid-allreduce blocks here forever, not loudly:
            # the elastic collective watchdog turns the wedge into a
            # CollectiveTimeout abort (off unless
            # MXNET_ELASTIC_COLLECTIVE_DEADLINE_MS is set)
            from .resilience.elastic import guard_collective
            out = guard_collective(_cross_process_allreduce, out,
                                   op="kvstore.allreduce")
        return out

    def push(self, key, value, priority=0):
        if self._retry is not None:
            return self._retry.call(self._push_once, key, value, priority)
        return self._push_once(key, value, priority)

    def _push_once(self, key, value, priority=0):
        # chaos point at entry, BEFORE compression/update mutate anything:
        # a retried injected fault can never double-consume error-feedback
        # residuals or double-apply the updater
        _chaos.point("kvstore.push")
        keys, values = _key_value(key, value)
        grouped = {}
        for k, v in zip(keys, values):
            grouped.setdefault(k, []).append(v)
        for k, vals in grouped.items():
            if k not in self._store:
                # check before compression: a failed push must not consume
                # or leak error-feedback residual state
                raise MXNetError("key %s has not been initialized" % k)
            if self._compression_params:
                vals = [NDArray(self._compress(k, i, v._data), ctx=v._ctx)
                        for i, v in enumerate(vals)]
            reduced = self._reduce(vals)
            if self._updater is not None:
                gw = NDArray(reduced)
                self._updater(_key_int(k), gw, self._store[k])
            else:
                # replace, not accumulate (reference kvstore_local.h:
                # `local = merged`); owned copy — with one pushed value
                # _reduce returns the caller's buffer, and a later donated
                # update on the caller's array would delete the stored value
                self._store[k]._data = jnp.array(reduced, copy=True)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        if self._retry is not None:
            return self._retry.call(self._pull_once, key, out, priority,
                                    ignore_sparse)
        return self._pull_once(key, out, priority, ignore_sparse)

    def _pull_once(self, key, out=None, priority=0, ignore_sparse=True):
        _chaos.point("kvstore.pull")
        keys, outs = _key_value(key, out)
        for k, o in zip(keys, outs):
            if k not in self._store:
                raise MXNetError("key %s has not been initialized" % k)
            src = self._store[k]
            # per-out copy: device_put is zero-copy between CPU devices and
            # onto the same chip, and handing the same buffer to several
            # outs (or leaving an out aliasing the store) breaks buffer
            # donation downstream
            val = jnp.array(src._data, copy=True)
            if o.ctx != src.ctx:
                val = jax.device_put(val, o.ctx.jax_device)
            o._data = val.astype(o._data.dtype) if o._data.dtype != val.dtype else val

    def pushpull(self, key, value, out=None, priority=0):
        """Fused allreduce (reference KVStore::PushPull
        `include/mxnet/kvstore.h:236`). On TPU this is the natural single
        collective; push+pull decomposition is the legacy path."""
        self.push(key, value, priority)
        self.pull(key, out if out is not None else value, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the rows named by ``row_ids`` into a row_sparse
        output (reference KVStore::PullRowSparse `kvstore_local.h:359`:
        row ids are deduplicated+sorted, values gathered server-side so
        only the touched rows travel)."""
        if row_ids is None or out is None:
            return self.pull(key, out=out, priority=priority)
        import numpy as _onp
        from .ndarray.sparse import RowSparseNDArray
        keys, outs = _key_value(key, out)
        # a list is per-key ONLY when it lines up with the key list and
        # holds array-likes; a plain [0, 2] row-id list for a single key
        # must stay one id-set (it would otherwise zip away rows)
        if isinstance(row_ids, (list, tuple)) and \
                len(row_ids) == len(keys) and \
                all(hasattr(r, "__len__") or hasattr(r, "shape")
                    for r in row_ids):
            rids = list(row_ids)
        else:
            rids = [row_ids] * len(keys)
        for k, o, rid in zip(keys, outs, rids):
            if k not in self._store:
                raise MXNetError("key %s has not been initialized" % k)
            if not isinstance(o, RowSparseNDArray):
                # dense out keeps the full-value pull semantics (reference
                # dense fallback path); only row_sparse outs row-filter
                self.pull(k, out=o, priority=priority)
                continue
            src = self._store[k]
            idx = _onp.unique(_onp.asarray(
                rid.asnumpy() if hasattr(rid, "asnumpy") else rid
            ).astype(_onp.int64).ravel())
            if idx.size and (idx[0] < 0 or idx[-1] >= src.shape[0]):
                # jax gather would CLAMP out-of-range ids — silently wrong
                raise MXNetError(
                    "row_sparse_pull: row id out of range for key %s "
                    "(shape %s, ids [%d, %d])"
                    % (k, src.shape, int(idx[0]), int(idx[-1])))
            out_dtype = o.dtype
            vals = src._data[jnp.asarray(idx)].astype(out_dtype)
            o._values = jnp.asarray(vals)
            o._idx = jnp.asarray(idx)
            o._dense_cache = None
            o._shape_ = tuple(src.shape)
            o._dtype_ = _onp.dtype(out_dtype)

    def broadcast(self, key, value, out=None, priority=0):
        self.init(key, value)
        if out is not None:
            self.pull(key, out=out, priority=priority)

    # ---- optimizer --------------------------------------------------------
    def set_updater(self, updater):
        """reference `kvstore.py` set_updater — local mode runs the
        optimizer inside the store (update_on_kvstore)."""
        self._updater = updater

    def set_optimizer(self, optimizer):
        from . import optimizer as opt_mod
        self._optimizer = optimizer
        self._updater = opt_mod.get_updater(optimizer)

    def is_capable(self, capability):
        if capability.lower() == "optimizer":
            return not self._is_dist or True
        return False

    # ---- compression ------------------------------------------------------
    def set_gradient_compression(self, compression_params):
        """reference N15 `src/kvstore/gradient_compression.{h,cc}` (2-bit
        threshold quantization with error feedback on dist push).

        TPU-native: ICI usually makes compression unnecessary (SURVEY
        §2.4), but the mechanism is real here, applied per pushed copy in
        ``push``:

        - ``{'type': '2bit', 'threshold': t}`` — reference semantics:
          each element quantizes to {-t, 0, +t}; the quantization error is
          kept as a per-(key, copy) residual added to the next push.
        - ``{'type': 'int8'}`` — symmetric per-tensor int8 (scale =
          max|x|/127) with the same error feedback; the dequantized int8
          payload is what crosses devices.
        """
        if compression_params is not None:
            ctype = compression_params.get("type")
            if ctype not in ("2bit", "int8", "none", None):
                raise MXNetError("unsupported gradient compression type %r"
                                 % (ctype,))
            if ctype == "2bit":
                t = float(compression_params.get("threshold", 0.5))
                if t <= 0:
                    # reference gradient_compression.cc SetParams rejects
                    # non-positive thresholds too
                    raise MXNetError(
                        "2bit compression threshold must be > 0, got %r"
                        % (t,))
        self._compression_params = compression_params
        self._residuals = {}

    def _compress(self, k, slot, v):
        """Quantize one pushed copy with error feedback; returns the
        dequantized payload (what the wire would carry)."""
        params = self._compression_params
        ctype = params.get("type")
        if ctype in (None, "none"):
            return v
        res = self._residuals.get((k, slot))
        x = v if res is None else v + res
        if ctype == "2bit":
            t = jnp.asarray(float(params.get("threshold", 0.5)), v.dtype)
            deq = jnp.where(x >= t, t, jnp.where(x <= -t, -t,
                                                 jnp.zeros_like(x)))
        else:  # int8
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
            q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
            deq = q.astype(v.dtype) * scale.astype(v.dtype)
        self._residuals[(k, slot)] = x - deq
        return deq

    # ---- distributed control ----------------------------------------------
    def barrier(self):
        if self._is_dist and jax.process_count() > 1:
            from jax.experimental import multihost_utils
            from .resilience.elastic import guard_collective
            # same watchdog as the allreduce: a barrier whose peer died is
            # the canonical silent wedge
            guard_collective(multihost_utils.sync_global_devices,
                             "kvstore_barrier", op="kvstore.barrier")

    def _barrier(self):
        self.barrier()

    def send_command_to_servers(self, head, body):
        pass  # no server processes on TPU (SURVEY §5.8)

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for distributed training"
        with open(fname, "wb") as fout:
            fout.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for distributed training"
        with open(fname, "rb") as fin:
            self._updater.set_states(fin.read())

    @property
    def num_dead_node(self):
        return 0


_allreduce_cache = {}


def _cross_process_allreduce(x):
    """True allreduce across processes: each process contributes its local
    value on one device of a global 1-D mesh and a jitted `psum` rides the
    interconnect (ICI/DCN on TPU pods, gloo-style on the CPU backend) —
    O(size) per link, unlike allgather-then-sum which moves O(N*size) to
    every host. Replaces the reference PS push/aggregate round
    (`src/kvstore/kvstore_dist_server.h:337`) with one collective."""
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    nproc = jax.process_count()
    key = ("mesh", nproc)
    if key not in _allreduce_cache:
        # one device per process so each host contributes exactly one shard
        devs = [[d for d in jax.devices() if d.process_index == p][0]
                for p in range(nproc)]
        _allreduce_cache[key] = Mesh(_np.array(devs), ("p",))
    mesh = _allreduce_cache[key]

    fkey = ("fn", nproc)
    if fkey not in _allreduce_cache:
        def _psum(v):
            return jax.lax.psum(v, "p")
        _allreduce_cache[fkey] = jax.jit(
            shard_map(_psum, mesh=mesh, in_specs=P("p"), out_specs=P()))
    fn = _allreduce_cache[fkey]

    local = _np.asarray(x)[None]  # leading axis = this process's shard
    glob = multihost_utils.host_local_array_to_global_array(local, mesh, P("p"))
    summed = fn(glob)  # (1, *x.shape), replicated
    return jnp.asarray(_np.asarray(
        multihost_utils.global_array_to_host_local_array(summed, mesh, P()))[0])


def _key_int(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


def _key_value(key, value):
    single = not isinstance(key, (list, tuple))
    if single:
        if isinstance(value, (list, tuple)):
            return [_key_str(key)] * len(value), list(value)
        return [_key_str(key)], [value]
    keys, values = [], []
    for k, v in zip(key, value):
        if isinstance(v, (list, tuple)):
            keys.extend([_key_str(k)] * len(v))
            values.extend(v)
        else:
            keys.append(_key_str(k))
            values.append(v)
    return keys, values


def create(name="local"):
    """Factory (reference `KVStore::Create` `src/kvstore/kvstore.cc:40`)."""
    if not isinstance(name, str):
        raise TypeError("name must be a string")
    name = name.lower()
    if name in ("local", "local_update_cpu", "local_allreduce_cpu",
                "local_allreduce_device", "device"):
        return KVStore(name)
    if name in ("dist_tpu_sync", "dist_sync", "dist_device_sync", "nccl",
                "dist"):
        return KVStore("dist_tpu_sync")
    if name == "dist_async":
        raise MXNetError(
            "dist_async is unsupported on TPU: asynchronous parameter-server "
            "updates are anti-idiomatic for an ICI mesh (SURVEY §2.4); use "
            "dist_tpu_sync")
    raise MXNetError("unknown KVStore type %s" % name)
