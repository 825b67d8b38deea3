"""Gluon Parameter / ParameterDict.

Parity surface: reference ``python/mxnet/gluon/parameter.py`` (Parameter with
deferred init, per-context copies, grad_req; ParameterDict with prefix
scoping, get/initialize/save/load). The TPU-native difference: device copies
are ``jax.Array``s and data-parallel replication is usually replaced by a
*sharded* single array (see mxnet_tpu.parallel) — the per-context list API
is kept for MXNet compatibility and single-host multi-device eager use.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict

import numpy as _np

from .. import initializer as init_mod
from ..base import MXNetError, dtype_np
from ..context import Context, current_context, cpu
from ..ndarray import ndarray as _nd
from ..ndarray.ndarray import NDArray

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types", "swapped_in"]


@contextlib.contextmanager
def swapped_in(params, values):
    """While the ``with`` block runs, every context copy of ``params[i]``
    reads as ``values[i]`` (raw arrays or NDArrays — tracers, while a
    program that takes the parameters as ARGUMENTS is being traced).
    A parameter a traced program merely closes over is baked into the
    executable as a constant: one copy of the weights per program, in
    host memory while it compiles, in the serialized artifact and in
    device memory. Not re-entrant across threads: the handles are shared
    state, as in the reference's CachedOp parameter binding."""
    saved = []
    try:
        for p, v in zip(params, values):
            v = v._data if isinstance(v, NDArray) else v
            for d in p._data:
                saved.append((d, d._data))
                d._data = v
        yield
    finally:
        for d, old in reversed(saved):
            d._data = old

tensor_types = (NDArray,)


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape is known (reference
    `python/mxnet/gluon/parameter.py:38`)."""


def _shape_complete(shape):
    return shape is not None and all(s > 0 for s in shape)


class Parameter:
    """A trainable parameter: holds one NDArray copy per context.

    reference `python/mxnet/gluon/parameter.py:49` — same lifecycle:
    construct (maybe with unknown dims as 0) → initialize() → (deferred until
    shape known) → data()/grad().
    """

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self._var = None
        self._data = None
        self._grad = None
        self._ctx_list = None
        self._deferred_init = ()
        self.name = name
        self._shape = tuple(shape) if shape is not None else (
            None if shape is None else tuple(shape))
        if isinstance(shape, int):
            self._shape = (shape,)
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        if not differentiable:
            grad_req = "null"
        self._grad_req = None
        self.grad_req = grad_req
        self._stype = stype
        self._grad_stype = grad_stype
        # set by mxnet_tpu.parallel when the model is sharded over a mesh
        self.sharding = None

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (
            self.name, self._shape, self.dtype)

    # ---- grad_req ---------------------------------------------------------
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise ValueError("invalid grad_req %r" % req)
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data is not None:
                for d in self._data:
                    d._grad = None
                    d._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown_ok = all(s1 in (0, s2) for s1, s2 in
                         zip(self._shape, tuple(new_shape)))
        if len(self._shape) != len(tuple(new_shape)) or not unknown_ok:
            raise AssertionError(
                "expected shape %s is incompatible with given shape %s for "
                "parameter %s" % (self._shape, tuple(new_shape), self.name))
        self._shape = tuple(new_shape)

    # ---- init -------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """reference `gluon/parameter.py` Parameter.initialize."""
        if default_init is None:
            default_init = init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if not _shape_complete(self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(
                "Cannot initialize Parameter %s because it has invalid "
                "shape %s; set allow_deferred_init or complete the shape"
                % (self.name, self._shape))
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        if not _shape_complete(self._shape):
            raise DeferredInitializationError(
                "deferred init of %s failed: shape %s still unknown"
                % (self.name, self._shape))
        if data is None:
            host = _np.zeros(self._shape, dtype=dtype_np(self.dtype))
            host_nd = _nd.array(host, ctx=cpu(), dtype=self.dtype)
            initializer = init if init is not None else default_init
            if isinstance(initializer, str):
                initializer = init_mod.create(initializer)
            initializer(init_mod.InitDesc(self.name), host_nd)
            data = host_nd
        self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        import jax
        import jax.numpy as jnp

        self._ctx_list = list(ctx_list)
        dt = dtype_np(self.dtype)
        # Each context copy must OWN its buffer: device_put between CPU
        # devices (and onto the same TPU chip) is zero-copy, so without the
        # explicit copy all ctx copies would alias one buffer — and the
        # optimizer kernels donate parameter buffers, which would delete
        # every sibling copy on the first update.
        self._data = []
        for c in self._ctx_list:
            val = jnp.array(data._data, copy=True)
            val = jax.device_put(val, c.jax_device)
            if val.dtype != _np.dtype(dt):
                val = val.astype(dt)
            self._data.append(NDArray(val, ctx=c))
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        for d in self._data:
            d.attach_grad(self._grad_req)
        self._grad = [d.grad for d in self._data]

    # ---- accessors --------------------------------------------------------
    def _check_init(self):
        if self._data is None:
            if self._deferred_init:
                raise DeferredInitializationError(
                    "Parameter %s deferred initialization not complete"
                    % self.name)
            raise RuntimeError(
                "Parameter %s has not been initialized. Call .initialize() "
                "first" % self.name)

    def _dev_idx(self, ctx):
        if ctx is None:
            if len(self._data) == 1:
                return 0
            ctx = current_context()
        for i, c in enumerate(self._ctx_list):
            if c == ctx:
                return i
        raise RuntimeError(
            "Parameter %s not initialized on context %s (has %s)"
            % (self.name, ctx, self._ctx_list))

    def data(self, ctx=None):
        self._check_init()
        return self._data[self._dev_idx(ctx)]

    def list_data(self):
        self._check_init()
        return list(self._data)

    def grad(self, ctx=None):
        self._check_init()
        if self._grad is None:
            raise RuntimeError("Parameter %s grad_req='null'" % self.name)
        return self._data[self._dev_idx(ctx)].grad

    def list_grad(self):
        self._check_init()
        if self._grad is None:
            raise RuntimeError("Parameter %s grad_req='null'" % self.name)
        return [d.grad for d in self._data]

    def list_ctx(self):
        if self._data is None and self._deferred_init:
            return self._deferred_init[1]
        self._check_init()
        return list(self._ctx_list)

    def zero_grad(self):
        if self._grad is None:
            return
        for d in self._data:
            if d.grad is not None:
                d.grad[:] = 0

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            if self._deferred_init:
                init, ctx, default_init, _ = self._deferred_init
                self._deferred_init = (init, ctx, default_init,
                                       data if isinstance(data, NDArray)
                                       else _nd.array(data))
                return
            raise RuntimeError("set_data on uninitialized Parameter %s"
                               % self.name)
        for d in self._data:
            val = data._data if isinstance(data, NDArray) else data
            import jax
            import jax.numpy as jnp
            # copy=True: the new buffer must not alias the source — the
            # optimizer kernels donate parameter buffers in place
            val = jnp.array(val, copy=True)
            d._data = jax.device_put(val, d.ctx.jax_device).astype(d.dtype)

    def row_sparse_data(self, row_id):
        # sparse storage is API-complete dense fallback on TPU (SURVEY §2.1)
        return self.data()

    def list_row_sparse_data(self, row_id):
        return self.list_data()

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = self._reduce()
            self._init_impl(data, ctx)
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)

    def _reduce(self):
        """Average copies across contexts (reference Parameter._reduce)."""
        self._check_init()
        if len(self._data) == 1:
            return NDArray(self._data[0]._data, ctx=cpu())
        acc = sum(d.asnumpy() for d in self._data) / len(self._data)
        return _nd.array(acc, ctx=cpu(), dtype=self.dtype)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is None:
            return
        with_autograd = [d for d in self._data]
        self._data = [NDArray(d._data.astype(dtype_np(dtype)), ctx=c)
                      for d, c in zip(with_autograd, self._ctx_list)]
        if self._grad_req != "null":
            self._init_grad()

    def var(self):
        """Symbol variable for this parameter (Module/Symbol interop)."""
        if self._var is None:
            from ..symbol import var
            self._var = var(self.name, shape=self._shape, dtype=self.dtype,
                            lr_mult=self.lr_mult, wd_mult=self.wd_mult,
                            init=self.init)
        return self._var


class Constant(Parameter):
    """Non-updating parameter holding a constant (reference
    `gluon/parameter.py` Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _nd.array(value)
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(self_i, _, arr):
                arr[:] = value

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit(),
                         differentiable=False)


class ParameterDict:
    """Ordered, prefix-scoped dictionary of Parameters (reference
    `python/mxnet/gluon/parameter.py:558`)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __repr__(self):
        s = "\n".join(repr(v) for v in self.values())
        return "%s(\n%s\n)" % (self._prefix or "Parameters", s)

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __contains__(self, key):
        return key in self._params

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        """Get or create a Parameter named prefix+name."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if hasattr(param, k) and getattr(param, k) is not None:
                    existing = getattr(param, k)
                    if k == "shape" and v is not None and existing is not None:
                        # merge partially-known shapes
                        v = tuple(v)
                        if len(v) == len(existing):
                            merged = tuple(a if a != 0 else b
                                           for a, b in zip(existing, v))
                            param._shape = tuple(
                                a if a != 0 else b for a, b in zip(v, existing))
                            continue
                    continue
                setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError("no constant named %s" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError("duplicate parameter name %s" % k)
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = init_mod.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def list_ctx(self):
        s = []
        for v in self.values():
            for c in v.list_ctx():
                if c not in s:
                    s.append(c)
        return s

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            weight = param._reduce()
            if not param.name.startswith(strip_prefix):
                raise ValueError("Prefix %s is to be stripped but parameter "
                                 "%s does not start with it"
                                 % (strip_prefix, param.name))
            arg_dict[param.name[len(strip_prefix):]] = weight
        _nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arg_dict = _nd.load(filename)
        if not isinstance(arg_dict, dict):
            raise ValueError("expected dict-of-arrays file")
        arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in arg_dict:
                    raise AssertionError(
                        "Parameter %s missing in file %s" % (name, filename))
        for name, v in arg_dict.items():
            if name not in self._params:
                if not ignore_extra:
                    raise AssertionError(
                        "Parameter %s in file %s is not in ParameterDict"
                        % (name, filename))
                continue
            load_param_from_array(self._params[name], v, ctx)


def load_param_from_array(param, arr, ctx=None):
    """Adopt a loaded array into a Parameter: take its shape, initialize if
    needed, set the data (shared by ParameterDict.load, Block.load_parameters
    and SymbolBlock.imports)."""
    param.shape = arr.shape
    if param._data is None and not param._deferred_init:
        param.initialize(ctx=ctx or [current_context()])
    if param._data is not None or param._deferred_init:
        param.set_data(arr)
        if param._deferred_init:
            param._finish_deferred_init()
