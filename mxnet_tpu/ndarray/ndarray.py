"""NDArray: a mutable handle over an immutable ``jax.Array``.

Role parity: reference ``include/mxnet/ndarray.h:82`` (NDArray with Chunk =
Storage handle + engine var) and ``python/mxnet/ndarray/ndarray.py``.

TPU-native design: the reference needs a Chunk/engine-var pair because eager
GPU kernels require host-side dependency ordering and manual memory pools.
On TPU, a ``jax.Array`` already *is* an asynchronously-produced, refcounted
device buffer managed by PJRT — so NDArray collapses to a thin mutable cell:

  - mutation (``x[:]=``, ``+=``, ``out=``) rebinds ``_data`` to a new
    functional value — the moral equivalent of the reference's var version
    bump (`include/mxnet/engine.h:57`);
  - ``wait_to_read`` = ``block_until_ready`` (reference
    `include/mxnet/ndarray.h:368` WaitToRead → Engine::WaitForVar);
  - cross-device copy = ``jax.device_put`` (reference
    `src/ndarray/ndarray.cc:1142` CopyFromToImpl);
  - the handle can transparently hold a jax tracer, which is what makes the
    whole eager API traceable under jit (CachedOp) with zero extra code.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import dtype_np, numeric_types, integer_types
from ..context import Context, current_context
from ..observability import tracer as _trace
from .. import _tape

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "eye", "concat", "stack", "save", "load", "waitall",
           "from_numpy", "from_dlpack", "to_dlpack_for_read"]


def _is_tracer(v):
    return isinstance(v, jax.core.Tracer)


def _wait_span(data):
    """``ndarray.wait`` around a read that is about to block the host on
    the device: only with the tracer on, for a concrete ``jax.Array`` that
    is not ready yet. A ready value, a tracer or a host array records
    nothing, so eager code does not flood the ring."""
    if _trace.tracer._enabled and isinstance(data, jax.Array) \
            and not _is_tracer(data) and not data.is_ready():
        return _trace.span("ndarray.wait", bytes=data.nbytes)
    return _trace._NULL_SPAN


class NDArray:
    """Multi-dimensional array on a device context."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_ag_node", "_stype",
                 "__weakref__")

    def __init__(self, data, ctx=None, dtype=None, stype="default"):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array) and not _is_tracer(data):
            data = _np.asarray(data, dtype=dtype_np(dtype) if dtype else None)
            dev = (ctx or current_context()).jax_device
            data = jax.device_put(data, dev)
        elif dtype is not None and data.dtype != dtype_np(dtype):
            data = data.astype(dtype_np(dtype))
        self._data = data
        self._ctx = ctx
        self._grad = None
        self._grad_req = "write"
        self._ag_node = None
        self._stype = stype

    # ---- basic properties -------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(_np.prod(self.shape)) if self.shape else 1

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def stype(self):
        return self._stype

    @property
    def ctx(self) -> Context:
        if self._ctx is not None:
            return self._ctx
        if _is_tracer(self._data):
            return current_context()
        dev = self._data.devices() if hasattr(self._data, "devices") else None
        if dev:
            d = next(iter(dev))
            if d.platform == "cpu":
                return Context("cpu", d.id)
            # the id Context.jax_device resolves back to this device
            accel = [x for x in jax.local_devices() if x.platform != "cpu"]
            return Context("tpu", accel.index(d))
        return current_context()

    context = ctx

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return self.transpose()

    # ---- host interop -----------------------------------------------------
    def asnumpy(self) -> _np.ndarray:
        """Blocking copy to host (reference NDArray::SyncCopyToCPU)."""
        if _trace.tracer._enabled:      # off: this one test and no more
            with _wait_span(self._data):
                return _np.asarray(self._data)
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("ambiguous truth value of multi-element NDArray")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def wait_to_read(self):
        if not _is_tracer(self._data):
            with _wait_span(self._data):
                jax.block_until_ready(self._data)

    wait_to_write = wait_to_read

    # ---- device movement --------------------------------------------------
    def as_in_context(self, ctx) -> "NDArray":
        if ctx == self.ctx:
            return self
        return self.copyto(ctx)

    as_in_ctx = as_in_context

    def copyto(self, other):
        from ..ops import registry as _r
        if isinstance(other, Context):
            dev = other.jax_device
            new = NDArray(jax.device_put(self._data, dev), ctx=other)
            return new
        if isinstance(other, NDArray):
            val = self._data
            if other.ctx != self.ctx and not _is_tracer(val):
                val = jax.device_put(val, other.ctx.jax_device)
            other._data = val.astype(other.dtype) if other.dtype != self.dtype else val
            if not other._is_leaf:
                other._ag_node = self._ag_node
            return other
        raise TypeError("copyto expects NDArray or Context")

    def copy(self):
        return NDArray(self._data, ctx=self._ctx)

    def astype(self, dtype, copy=True):
        nd = dtype_np(dtype)
        if not copy and nd == self.dtype:
            return self
        from . import _op_proxy
        return _op_proxy.cast(self, dtype=nd)

    def tostype(self, stype):
        """Sparse storage conversion — API parity; dense fallback on TPU
        (reference cast_storage `src/operator/tensor/cast_storage.cc`)."""
        from .sparse import _to_stype
        return _to_stype(self, stype)

    # ---- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Mark as differentiable leaf (reference
        `python/mxnet/ndarray/ndarray.py` attach_grad →
        Imperative::MarkVariables `src/imperative/imperative.cc:123`)."""
        self._grad = zeros(self.shape, dtype=self.dtype, ctx=self._ctx)
        self._grad_req = grad_req
        self._ag_node = (_tape.Leaf(self), 0)
        return self

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        _tape.backward([self], [out_grad] if out_grad is not None else None,
                       retain_graph=retain_graph, train_mode=train_mode)

    # ---- mutation ---------------------------------------------------------
    @property
    def _is_leaf(self):
        """True when this handle is a marked autograd variable (attach_grad).
        Mutation must NOT unmark it: the Leaf node reads the handle's current
        value at backward time — matching MXNet, where a variable stays a
        variable across in-place optimizer updates (engine var version bumps,
        `include/mxnet/engine.h:57`)."""
        node = self._ag_node
        return (node is not None and isinstance(node[0], _tape.Leaf)
                and node[0].handle is self)

    def _set_data(self, val):
        self._data = val
        if not self._is_leaf:
            self._ag_node = None

    def __setitem__(self, key, value):
        from . import _op_proxy
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value))
        if key is None or key == slice(None) or key is Ellipsis:
            if isinstance(v, (int, float)):
                self._set_data(jnp.full(self.shape, v, dtype=self.dtype))
            else:
                v = jnp.asarray(v, dtype=self.dtype)
                self._set_data(jnp.broadcast_to(v, self.shape))
            return
        key = _canonical_index(key)
        self._set_data(self._data.at[key].set(v))

    def __getitem__(self, key):
        from . import _op_proxy
        if isinstance(key, NDArray):
            key = key._data
        key = _canonical_index(key)
        return _op_proxy._index(self, key=key)

    # ---- operators --------------------------------------------------------
    def _binop(self, other, name, reverse=False):
        from . import _op_proxy
        fn = getattr(_op_proxy, name)
        if isinstance(other, NDArray):
            return fn(other, self) if reverse else fn(self, other)
        if isinstance(other, numeric_types):
            return fn(other, self) if reverse else fn(self, other)
        other = array(other, ctx=self._ctx)
        return fn(other, self) if reverse else fn(self, other)

    def __add__(self, o):
        return self._binop(o, "add")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "subtract")

    def __rsub__(self, o):
        return self._binop(o, "subtract", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "multiply")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "divide")

    def __rtruediv__(self, o):
        return self._binop(o, "divide", reverse=True)

    def __mod__(self, o):
        return self._binop(o, "mod")

    def __rmod__(self, o):
        return self._binop(o, "mod", reverse=True)

    def __pow__(self, o):
        return self._binop(o, "power")

    def __rpow__(self, o):
        return self._binop(o, "power", reverse=True)

    def __matmul__(self, o):
        return self._binop(o, "matmul")

    def __neg__(self):
        return self._binop(-1, "multiply")

    def __abs__(self):
        from . import _op_proxy
        return _op_proxy.abs(self)

    def __eq__(self, o):
        return self._binop(o, "equal")

    def __ne__(self, o):
        return self._binop(o, "not_equal")

    def __lt__(self, o):
        return self._binop(o, "lesser")

    def __le__(self, o):
        return self._binop(o, "lesser_equal")

    def __gt__(self, o):
        return self._binop(o, "greater")

    def __ge__(self, o):
        return self._binop(o, "greater_equal")

    def __hash__(self):
        return id(self)

    # in-place: rebind _data (engine-var version bump equivalent)
    def _inplace(self, other, name):
        res = self._binop(other, name)
        self._data = res._data
        if not self._is_leaf:
            self._ag_node = res._ag_node
        return self

    def __iadd__(self, o):
        return self._inplace(o, "add")

    def __isub__(self, o):
        return self._inplace(o, "subtract")

    def __imul__(self, o):
        return self._inplace(o, "multiply")

    def __itruediv__(self, o):
        return self._inplace(o, "divide")

    # ---- shape ops (delegate to op namespace) -----------------------------
    def reshape(self, *shape, **kwargs):
        from . import _op_proxy
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        return _op_proxy.reshape(self, shape=shape)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        from . import _op_proxy
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return _op_proxy.transpose(self, axes=axes if axes else None)

    def swapaxes(self, a1, a2):
        from . import _op_proxy
        return _op_proxy.swapaxes(self, dim1=a1, dim2=a2)

    def expand_dims(self, axis):
        from . import _op_proxy
        return _op_proxy.expand_dims(self, axis=axis)

    def squeeze(self, axis=None):
        from . import _op_proxy
        return _op_proxy.squeeze(self, axis=axis)

    def flatten(self):
        from . import _op_proxy
        return _op_proxy.Flatten(self)

    def broadcast_to(self, shape):
        from . import _op_proxy
        return _op_proxy.broadcast_to(self, shape=tuple(shape))

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def slice_axis(self, axis, begin, end):
        from . import _op_proxy
        return _op_proxy.slice_axis(self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        from . import _op_proxy
        return _op_proxy.take(self, indices, axis=axis, mode=mode)

    def tile(self, reps):
        from . import _op_proxy
        return _op_proxy.tile(self, reps=reps)

    def repeat(self, repeats, axis=None):
        from . import _op_proxy
        return _op_proxy.repeat(self, repeats=repeats, axis=axis)

    def pick(self, index, axis=-1, keepdims=False):
        from . import _op_proxy
        return _op_proxy.pick(self, index, axis=axis, keepdims=keepdims)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import _op_proxy
        return _op_proxy.one_hot(self, depth=depth, on_value=on_value,
                                 off_value=off_value)

    # ---- reductions -------------------------------------------------------
    def _reduce(self, name, axis=None, keepdims=False):
        from . import _op_proxy
        return getattr(_op_proxy, name)(self, axis=axis, keepdims=keepdims)

    def sum(self, axis=None, keepdims=False, **kw):
        return self._reduce("sum", axis, keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        return self._reduce("mean", axis, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce("max", axis, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce("min", axis, keepdims)

    def prod(self, axis=None, keepdims=False, **kw):
        return self._reduce("prod", axis, keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        from . import _op_proxy
        return _op_proxy.norm(self, ord=ord, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        from . import _op_proxy
        return _op_proxy.argmax(self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        from . import _op_proxy
        return _op_proxy.argmin(self, axis=axis, keepdims=keepdims)

    def clip(self, a_min, a_max):
        from . import _op_proxy
        return _op_proxy.clip(self, a_min=a_min, a_max=a_max)

    def abs(self):
        from . import _op_proxy
        return _op_proxy.abs(self)

    def sqrt(self):
        from . import _op_proxy
        return _op_proxy.sqrt(self)

    def square(self):
        from . import _op_proxy
        return _op_proxy.square(self)

    def exp(self):
        from . import _op_proxy
        return _op_proxy.exp(self)

    def log(self):
        from . import _op_proxy
        return _op_proxy.log(self)

    def relu(self):
        from . import _op_proxy
        return _op_proxy.relu(self)

    def sigmoid(self):
        from . import _op_proxy
        return _op_proxy.sigmoid(self)

    def tanh(self):
        from . import _op_proxy
        return _op_proxy.tanh(self)

    def softmax(self, axis=-1):
        from . import _op_proxy
        return _op_proxy.softmax(self, axis=axis)

    def zeros_like(self):
        return zeros(self.shape, dtype=self.dtype, ctx=self._ctx)

    def ones_like(self):
        return ones(self.shape, dtype=self.dtype, ctx=self._ctx)

    def asnumpy_or_tracer(self):
        return self._data

    def as_np_ndarray(self):
        from ..numpy import ndarray as np_nd
        out = np_nd(self._data, ctx=self._ctx)
        out._ag_node = self._ag_node
        return out

    def as_nd_ndarray(self):
        return self

    def __repr__(self):
        if _is_tracer(self._data):
            return "\n<NDArray traced %s @%s>" % (self.shape, "trace")
        return "\n%s\n<NDArray %s @%s>" % (
            _np.asarray(self._data), "x".join(map(str, self.shape)), self.ctx)

    # ---- numpy protocol ---------------------------------------------------
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a


def _canonical_index(key):
    """Convert NDArray-containing index tuples into jax-compatible keys."""
    if isinstance(key, NDArray):
        return key._data
    if isinstance(key, tuple):
        return tuple(k._data if isinstance(k, NDArray) else k for k in key)
    return key


# ---- creation -------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    if isinstance(source_array, NDArray):
        out = NDArray(source_array._data, ctx=ctx)
        if dtype is not None:
            out = out.astype(dtype)
        return out
    arr = _np.asarray(source_array, dtype=dtype_np(dtype) if dtype else None)
    if arr.dtype == _np.float64 and dtype is None:
        arr = arr.astype(_np.float32)
    return NDArray(arr, ctx=ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    dev = (ctx or current_context()).jax_device
    with jax.default_device(dev):
        v = jnp.zeros(shape, dtype=dtype_np(dtype))
    return NDArray(v, ctx=ctx)


def ones(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    dev = (ctx or current_context()).jax_device
    with jax.default_device(dev):
        v = jnp.ones(shape, dtype=dtype_np(dtype))
    return NDArray(v, ctx=ctx)


def full(shape, val, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    dev = (ctx or current_context()).jax_device
    with jax.default_device(dev):
        v = jnp.full(shape, val, dtype=dtype_np(dtype))
    return NDArray(v, ctx=ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    v = jnp.arange(start, stop, step, dtype=dtype_np(dtype))
    if repeat > 1:
        v = jnp.repeat(v, repeat)
    return NDArray(v, ctx=ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None):
    v = jnp.eye(N, M if M else N, k=k, dtype=dtype_np(dtype))
    return NDArray(v, ctx=ctx)


def concat(*arrays, dim=1):
    from . import _op_proxy
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return _op_proxy.concat(*arrays, dim=dim)


def stack(*arrays, axis=0):
    from . import _op_proxy
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = tuple(arrays[0])
    return _op_proxy.stack(*arrays, axis=axis)


def from_numpy(a, zero_copy=False):
    return array(a)


def from_dlpack(cap):
    return NDArray(jnp.from_dlpack(cap))


def to_dlpack_for_read(arr):
    return arr._data.__dlpack__()


to_dlpack_for_write = to_dlpack_for_read


def waitall():
    """Parity with mx.nd.waitall (Engine::WaitForAll)."""
    last = jax.device_put(0.0) + 0
    with _wait_span(last):
        last.block_until_ready()


# ---- serialization (reference NDArray::Save/Load, mx.nd.save/load) --------

def save(fname, data):
    """Save list or dict of NDArrays in the reference's binary list container
    (reference `src/ndarray/ndarray.cc:1826` NDArray::Save) — files written
    here load in the reference and vice versa. See `serialization.py`."""
    from . import serialization
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        serialization.save_ndarrays(fname, list(data))
    elif isinstance(data, dict):
        keys = list(data.keys())
        serialization.save_ndarrays(fname, [data[k] for k in keys], keys)
    else:
        raise TypeError("save expects NDArray, list, or dict")


def load(fname):
    """Load NDArrays saved by `save` or by the reference (binary container);
    .npz files from older checkpoints of this framework still load."""
    import numpy as np
    import os
    from . import serialization
    path = fname if os.path.exists(fname) else fname + ".npz"
    if serialization.is_mxnet_binary(path):
        arrays, names = serialization.load_ndarrays(path)
        if names:
            return {k: array(a, dtype=a.dtype) for k, a in zip(names, arrays)}
        return [array(a, dtype=a.dtype) for a in arrays]
    with np.load(path, allow_pickle=False) as z:
        keys = list(z.keys())
        if "__mx_list__" in keys:
            n = int(z["__mx_list__"])
            arrs = [z["arr_%d" % i] for i in range(n)]
            return [array(a, dtype=a.dtype) for a in arrs]
        out = {}
        for k in keys:
            a = z[k]
            out[k] = array(a, dtype=a.dtype)
        return out
