"""Custom operators defined in Python.

Parity surface: reference ``python/mxnet/operator.py`` — ``CustomOp`` (:435,
imperative compute with ``assign`` honoring write/add/null req),
``CustomOpProp`` (:488, shape/type/arg declarations), ``register`` (:711),
invoked as ``mx.nd.Custom(..., op_type=name)`` / ``mx.sym.Custom(...)``
(``src/operator/custom/custom-inl.h:52`` runs them via engine callbacks).

TPU-native design: the user's numpy-level CustomOp runs on the HOST via
``jax.pure_callback`` — so a Custom node works inside jitted/hybridized
programs (XLA inserts the device<->host transfers where the reference
bounced through engine async callbacks). The backward pass is wired with
``jax.custom_vjp`` calling ``CustomOp.backward`` through a second
callback, so autograd/tape replay differentiates through custom nodes.

For device-speed custom kernels, skip the host bounce and register a JAX
or Pallas function directly as a first-class op with
``mxnet_tpu.operator.register_op`` (the TPU analogue of the reference's
lib_api.h dlopen path): the function becomes available in the nd/symbol
namespaces, is jit-fused by XLA, and differentiates via jax.vjp (or an
attached ``jax.custom_vjp``).
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

import jax
import jax.numpy as jnp

from . import _tape
from .ops.registry import register as register_op  # re-export; see docstring

__all__ = ["CustomOp", "CustomOpProp", "register", "get", "register_op"]

_REGISTRY = {}


class CustomOp:
    """Base class for Python custom operators (reference operator.py:435)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        # default: no gradient written (in_grad stays zero)
        pass

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` honoring the request type."""
        if req == "null":
            return
        from .ndarray.ndarray import NDArray, array
        src_nd = src if isinstance(src, NDArray) else array(_np.asarray(src))
        if req == "add":
            dst._data = dst._data + src_nd._data.astype(dst._data.dtype)
        else:  # write / inplace
            dst._data = src_nd._data.astype(dst._data.dtype)


class CustomOpProp:
    """Declarations for a custom operator (reference operator.py:488)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


def register(reg_name):
    """Register a CustomOpProp subclass under ``op_type=reg_name``
    (reference operator.py:711). Re-registering a name replaces the
    previous prop (notebook iteration)."""
    def do_register(prop_cls):
        _REGISTRY[reg_name] = prop_cls
        for cache in (_CALLABLE_CACHE, _ARG_NAMES_CACHE):
            for key in [k for k in cache if k[0] == reg_name]:
                del cache[key]
        return prop_cls
    return do_register


def get(reg_name):
    return _REGISTRY.get(reg_name)


def _make_prop(op_type, prop_kwargs):
    prop_cls = _REGISTRY.get(op_type)
    if prop_cls is None:
        raise ValueError(
            "Custom op type %r is not registered; decorate its CustomOpProp "
            "with @mx.operator.register(%r)" % (op_type, op_type))
    return prop_cls(**prop_kwargs)


def _shapes_dtypes(prop, in_vals):
    in_shapes = [list(v.shape) for v in in_vals]
    ret = prop.infer_shape(in_shapes)
    if len(ret) == 2:
        _, out_shapes = ret
    else:
        _, out_shapes, _ = ret
    in_types = [_np.dtype(v.dtype) for v in in_vals]
    tret = prop.infer_type(in_types)
    out_types = tret[1]
    return ([tuple(s) for s in out_shapes],
            [_np.dtype(t) for t in out_types])


def _wrap_host(np_arrays):
    from .ndarray.ndarray import array
    return [array(_np.asarray(a), dtype=_np.asarray(a).dtype)
            for a in np_arrays]


def _settled(results):
    """Wait for an eagerly dispatched host callback before the caller
    dispatches anything else (no-op on tracers, i.e. under jit). The
    user's op works on NDArrays, so the callback itself dispatches jax
    work; a device runs its computations in order, so if the main thread
    has meanwhile queued more work behind the computation that hosts the
    callback, the callback's own dispatch queues behind that work and
    the two wait on each other for good."""
    return jax.block_until_ready(results)


def _zeros_nd(specs):
    from .ndarray.ndarray import NDArray
    return [NDArray(jnp.zeros(s, d)) for s, d in specs]


# forward-call operator instances waiting for their backward, keyed by a
# call id that flows through the jax program as data — matches the
# reference's per-invoke op state (OpStatePtr) held by the autograd node.
# Bounded FIFO so primal-only calls can't leak instances.
_OP_STATES = OrderedDict()
_OP_STATE_CAP = 4096
_op_state_counter = [0]

# bounded FIFO: per-step-varying prop kwargs (e.g. a stringified lr) must
# not grow memory without bound over a long training run
_CALLABLE_CACHE = OrderedDict()
_CALLABLE_CACHE_CAP = 512


def _kwargs_key(prop_kwargs):
    return tuple(sorted((k, repr(v)) for k, v in prop_kwargs.items()))


def _custom_callable(op_type, prop_kwargs, is_train):
    """Build (and cache) the custom_vjp-wrapped jax function for one
    (op_type, prop kwargs, train-mode) configuration."""
    key = (op_type, _kwargs_key(prop_kwargs), is_train)
    hit = _CALLABLE_CACHE.get(key)
    if hit is not None:
        return hit
    prop = _make_prop(op_type, prop_kwargs)
    n_args = len(prop.list_arguments())
    n_aux = len(prop.list_auxiliary_states())
    n_out = len(prop.list_outputs())

    def _new_op(arrays):
        return prop.create_operator(None, [a.shape for a in arrays[:n_args]],
                                    [a.dtype for a in arrays[:n_args]])

    def host_forward(*np_arrays):
        op = _new_op(np_arrays)
        nds = _wrap_host(np_arrays)
        in_data, aux = nds[:n_args], nds[n_args:]
        out_shapes, out_types = _shapes_dtypes(prop, np_arrays[:n_args])
        out_data = _zeros_nd(list(zip(out_shapes, out_types)))
        op.forward(is_train=is_train, req=["write"] * n_out,
                   in_data=in_data, out_data=out_data, aux=aux)
        # retain the instance for its matching backward (state stashed on
        # self in forward must be visible in backward, reference semantics)
        _op_state_counter[0] += 1
        call_id = _op_state_counter[0]
        _OP_STATES[call_id] = op
        while len(_OP_STATES) > _OP_STATE_CAP:
            _OP_STATES.popitem(last=False)
        return (_np.int64(call_id),) + tuple(
            _np.asarray(o.asnumpy(), dtype=t)
            for o, t in zip(out_data, out_types))

    def host_backward(call_id, *np_arrays):
        grads = np_arrays[:n_out]
        rest = np_arrays[n_out:]
        ins, outs = rest[:n_args + n_aux], rest[n_args + n_aux:]
        op = _OP_STATES.pop(int(call_id), None)
        if op is None:  # evicted or replayed: fall back to a fresh instance
            op = _new_op(ins)
        nds = _wrap_host(ins)
        in_data, aux = nds[:n_args], nds[n_args:]
        out_data = _wrap_host(outs)
        out_grad = _wrap_host(grads)
        in_grad = _zeros_nd([(a.shape, a.dtype) for a in ins[:n_args]])
        op.backward(req=["write"] * n_args, out_grad=out_grad,
                    in_data=in_data, out_data=out_data, in_grad=in_grad,
                    aux=aux)
        return tuple(_np.asarray(g.asnumpy(), dtype=a.dtype)
                     for g, a in zip(in_grad, ins[:n_args]))

    def _fwd_callback(*tensor_vals):
        out_shapes, out_types = _shapes_dtypes(prop, tensor_vals[:n_args])
        specs = (jax.ShapeDtypeStruct((), _np.int64),) + tuple(
            jax.ShapeDtypeStruct(s, t)
            for s, t in zip(out_shapes, out_types))
        res = _settled(jax.pure_callback(host_forward, specs, *tensor_vals,
                                         vmap_method="sequential"))
        return res[0], tuple(res[1:])

    @jax.custom_vjp
    def run(*tensor_vals):
        _, outs = _fwd_callback(*tensor_vals)
        return outs

    def run_fwd(*tensor_vals):
        call_id, outs = _fwd_callback(*tensor_vals)
        return outs, (call_id, tensor_vals, outs)

    def run_bwd(res, gouts):
        call_id, tensor_vals, outs = res
        in_specs = tuple(jax.ShapeDtypeStruct(v.shape, v.dtype)
                         for v in tensor_vals[:n_args])
        grads = _settled(jax.pure_callback(
            host_backward, in_specs, call_id, *gouts, *tensor_vals, *outs,
            vmap_method="sequential"))
        if not isinstance(grads, tuple):
            grads = (grads,)
        # aux states receive no gradient
        return tuple(grads) + tuple(
            jnp.zeros(v.shape, v.dtype) for v in tensor_vals[n_args:])

    run.defvjp(run_fwd, run_bwd)
    _CALLABLE_CACHE[key] = (run, n_out, prop)
    while len(_CALLABLE_CACHE) > _CALLABLE_CACHE_CAP:
        _CALLABLE_CACHE.popitem(last=False)
    return run, n_out, prop


def _custom_fn(*tensor_vals, op_type, __is_train__=None, **prop_kwargs):
    """The registered ``Custom`` op (reference
    `src/operator/custom/custom.cc` NNVM_REGISTER_OP(Custom))."""
    if __is_train__ is None:
        # direct fn call (symbol executor path) — binder didn't run
        __is_train__ = _tape.is_training()
    run, n_out, _ = _custom_callable(op_type, prop_kwargs, bool(__is_train__))
    out = run(*tensor_vals)
    return out if n_out > 1 else out[0]


register_op(name="Custom", aliases=("_npx_Custom", "_npi_Custom"),
            state_binders={"__is_train__": _tape.is_training})(_custom_fn)


_ARG_NAMES_CACHE = OrderedDict()


def _arg_names(op_type, prop_kwargs):
    """Declared tensor-input order for one (op_type, kwargs) config —
    cached so eager calls don't rebuild the prop every invoke."""
    key = (op_type, _kwargs_key(prop_kwargs))
    names = _ARG_NAMES_CACHE.get(key)
    if names is None:
        prop = _make_prop(op_type, prop_kwargs)
        names = prop.list_arguments() + prop.list_auxiliary_states()
        _ARG_NAMES_CACHE[key] = names
        while len(_ARG_NAMES_CACHE) > _CALLABLE_CACHE_CAP:
            _ARG_NAMES_CACHE.popitem(last=False)
    return names


def normalize_custom_args(args, kwargs):
    """Reorder mxnet-style keyword tensor inputs (``Custom(data=x,
    label=y, op_type='softmax')``) into the positional order declared by
    the prop's list_arguments + list_auxiliary_states. Returns
    (tensors, call_kwargs)."""
    kwargs = dict(kwargs)
    op_type = kwargs.pop("op_type", None)
    if op_type is None:
        raise ValueError("Custom requires op_type=")
    name = kwargs.pop("name", None)
    from .ndarray.ndarray import NDArray
    from .symbol.symbol import Symbol
    tensor_kwargs = {k: v for k, v in kwargs.items()
                     if isinstance(v, (NDArray, Symbol))}
    # non-tensor kwargs parameterize the prop; the reference passes them
    # through the C boundary as strings, so props parse str values
    prop_kwargs = {k: v if isinstance(v, str) else str(v)
                   for k, v in kwargs.items() if k not in tensor_kwargs}
    names = _arg_names(op_type, prop_kwargs)
    tensors = list(args)
    for n in names[len(tensors):]:
        if n in tensor_kwargs:
            tensors.append(tensor_kwargs.pop(n))
    if tensor_kwargs:
        raise ValueError("unknown tensor inputs %s for custom op %r "
                         "(declared: %s)"
                         % (sorted(tensor_kwargs), op_type, names))
    call_kwargs = dict(prop_kwargs, op_type=op_type)
    if name is not None:
        call_kwargs["name"] = name
    return tensors, call_kwargs
