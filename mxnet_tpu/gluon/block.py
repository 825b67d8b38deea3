"""Gluon Block / HybridBlock.

Parity surface: reference ``python/mxnet/gluon/block.py`` — ``Block`` (:228,
imperative container with auto-registered children/params),
``HybridBlock`` (:838, `hybridize()` :1039 builds a CachedOp :932 and
replays it :979), parameter save/load, `export`.

TPU-native design: `hybridize()` wraps the block's forward in
``mxnet_tpu.cached_op.CachedOp`` — one ``jax.jit`` trace per input
signature, parameters passed as explicit program inputs so XLA sees a
closed functional program (and gradients flow to parameters through the
single recorded tape node, exactly like the reference records one
``_CachedOp`` node). There is no symbolic tracing language: the eager
NDArray API itself is traceable.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
from jax.core import Tracer as _Tracer

from ..base import PROGRAM_SCOPES
from ..context import current_context
from ..ndarray.ndarray import NDArray
from ..observability import tracer as _trace
from .parameter import (DeferredInitializationError, Parameter,
                        ParameterDict, swapped_in)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "recomputed"]

_naming = threading.local()
_initializing = threading.local()   # .on: inside Block.initialize


class _BlockScope:
    """Name manager for automatic prefixing (reference `gluon/block.py:33`)."""

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def current():
        return getattr(_naming, "scope", None)

    @staticmethod
    def create(prefix, params, hint):
        current = _BlockScope.current()
        if current is None:
            if prefix is None:
                prefix = _namegen(hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = _BlockScope.current()
        _naming.scope = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _naming.scope = self._old_scope


_global_counter = {}


def _namegen(hint):
    count = _global_counter.get(hint, 0)
    _global_counter[hint] = count + 1
    return "%s%d" % (hint, count)


def _flatten(args):
    """Flatten nested list/tuple of NDArrays into a flat list + treedef."""
    if isinstance(args, NDArray):
        return [args], int(0)
    if isinstance(args, (list, tuple)):
        flat, fmts = [], []
        for a in args:
            f, fmt = _flatten(a)
            flat.extend(f)
            fmts.append(fmt)
        return flat, fmts
    return [args], None


def _regroup(flat, fmt):
    if fmt is None:
        return flat[0], flat[1:]
    if isinstance(fmt, int):
        return flat[0], flat[1:]
    out = []
    for f in fmt:
        res, flat = _regroup(flat, f)
        out.append(res)
    return out, flat


class Block:
    """Base building block (reference `gluon/block.py:228`)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Auto-register children and parameters (reference block.py:254)."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)) and \
                    not isinstance(existing, type(value)):
                raise TypeError("Changing attribute type for %s from %s to %s"
                                % (name, type(existing), type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed" % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _check_container_with_block(self):
        pass

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of this block and children, optionally filtered by
        regex (reference block.py:504)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks, hook)
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks, hook)
        return handle

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer as init_mod
        if init is None:
            init = init_mod.Uniform()
        params = self.collect_params()
        if not _trace.tracer._enabled or getattr(_initializing, "on", False):
            params.initialize(init, ctx, verbose, force_reinit)
            return
        # one span for the outermost call: a subclass that initializes its
        # children one by one stays one span
        _initializing.on = True
        try:
            with _trace.span("block.initialize", params=len(params)):
                params.initialize(init, ctx, verbose, force_reinit)
        finally:
            _initializing.on = False

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def save_parameters(self, filename, deduplicate=False):
        """Structural-name save (reference block.py:428 save_parameters);
        format is a dict-of-arrays file loadable by ``mx.nd.load``."""
        params = self._collect_params_with_prefix()
        from ..ndarray import ndarray as _nd
        arg_dict = {key: val._reduce() for key, val in params.items()}
        _nd.save(filename, arg_dict)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        from ..ndarray import ndarray as _nd
        loaded = _nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # legacy full-name format fallback (ParameterDict.save)
        if loaded and not any("." in k for k in loaded.keys()) and \
                not set(loaded.keys()) & set(params.keys()):
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    "Parameter %s is missing in file %s" % (name, filename)
        for name in loaded:
            if name not in params:
                assert ignore_extra, \
                    "Parameter %s loaded from file %s is not present in this " \
                    "block" % (name, filename)
                continue
            from .parameter import load_param_from_array
            load_param_from_array(params[name], loaded[name], ctx)

    save_params = save_parameters
    load_params = load_parameters

    def summary(self, *inputs):
        from ..visualization import block_summary
        return block_summary(self, *inputs)

    def __call__(self, *args):
        # while JAX traces the call, its ops carry the block's name in
        # their metadata (the reference profiler showed operators under the
        # symbol's name). An eager call compiles op by op and keeps no
        # scope, so it does not pay for one.
        if self._name and any(
                isinstance(getattr(a, "_data", None), _Tracer)
                for a in args):
            name = self._name
            with jax.named_scope(name + "_" if name in PROGRAM_SCOPES
                                 else name):
                return self._call(*args)
        return self._call(*args)

    def _call(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError


def recomputed(block, x, keep=()):
    """``block(x)`` whose forward is run again in the backward pass of the
    traced program that holds it (``jax.checkpoint`` around the call: only
    ``x`` is kept for the backward, not what the block computed on the way,
    except the arrays the ops tagged with a name in ``keep``). The block's
    parameters ride as the closed-over tracers they are under
    ``parallel.functionalize``; the block must write no auxiliary state.
    Outside a traced program (an eager call, the autograd tape) it is the
    plain call."""
    from .. import _tape
    if not isinstance(getattr(x, "_data", None), _Tracer) \
            or _tape.is_recording():
        return block(x)
    return NDArray(jax.checkpoint(
        lambda data: block(NDArray(data))._data,
        policy=jax.checkpoint_policies.save_only_these_names(*keep))(x._data))


class _HookHandle:
    _id = 0

    def __init__(self, hooks, hook):
        _HookHandle._id += 1
        self._hooks = hooks
        self._key = _HookHandle._id
        hooks[self._key] = hook

    def detach(self):
        self._hooks.pop(self._key, None)


def _indent(s, num):
    lines = s.split("\n")
    first = lines.pop(0)
    return first + "".join("\n" + " " * num + line for line in lines)


class HybridBlock(Block):
    """Block that can be compiled to one XLA program (reference
    `gluon/block.py:838`). Subclasses implement
    ``hybrid_forward(F, x, *args, **params)``; parameters registered via
    ``self.params.get(...)`` are injected as keyword arguments."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags = {}
        self._cached_op = None
        self._cached_params = None

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            if not isinstance(block, Block):
                raise ValueError("children of HybridBlock must be HybridBlock")
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._clear_cached_op()
        for child in self._children.values():
            child.hybridize(active, static_alloc=static_alloc,
                            static_shape=static_shape, **kwargs)

    def _clear_cached_op(self):
        self._cached_op = None
        self._cached_params = None

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes. Layers with
        lazily-shaped weights override this (the reference resolves it via
        symbolic infer_shape, `gluon/block.py:785 _deferred_infer_shape`)."""
        raise DeferredInitializationError(
            "%s has parameters with unresolved shapes and does not implement "
            "infer_shape" % type(self).__name__)

    def infer_type(self, *args):
        pass

    def _get_ctx(self, args):
        flat, _ = _flatten(list(args))
        for a in flat:
            if isinstance(a, NDArray):
                return a.ctx
        return current_context()

    def _eager_forward(self, *args):
        ctx = self._get_ctx(args)
        params = {}
        try:
            for name, p in self._reg_params.items():
                params[name] = p.data(ctx)
        except DeferredInitializationError:
            self._finish_deferred(args, ctx)
            params = {name: p.data(ctx)
                      for name, p in self._reg_params.items()}
        from .. import ndarray as F
        return self.hybrid_forward(F, *args, **params)

    def _finish_deferred(self, args, ctx):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init:
                p._finish_deferred_init()

    def _symbolic_forward(self, *args):
        """Compose this block into a Symbol graph: parameters become named
        variables, so nested blocks build one DAG (reference
        `gluon/block.py:1128` HybridBlock.forward's symbol branch)."""
        from .. import symbol as sym_ns
        # aux-ness (BatchNorm moving stats etc.) is marked by the op the
        # variable composes into (_sym_op aux slots), not by grad_req —
        # a frozen weight is still an argument
        params = {name: sym_ns.var(p.name)
                  for name, p in self._reg_params.items()}
        return self.hybrid_forward(sym_ns, *args, **params)

    def forward(self, *args):
        from ..symbol.symbol import Symbol as _Sym
        flat, fmt = _flatten(list(args))
        self._in_fmt = fmt
        if any(isinstance(a, _Sym) for a in flat):
            return self._symbolic_forward(*args)
        # remember which flat slots carried tensors (and the values of the
        # ones that didn't) so export() can rebuild the exact call
        self._in_tensor_mask = [isinstance(a, NDArray) for a in flat]
        self._in_const_vals = [None if isinstance(a, NDArray) else a
                               for a in flat]
        if self._active:
            return self._call_cached_op(*args)
        return self._eager_forward(*args)

    # ---- cached-op machinery ---------------------------------------------
    def _build_cache(self, args):
        """reference `gluon/block.py:932 _build_cache`."""
        from ..cached_op import CachedOp
        params = list(self.collect_params().values())
        # filter params that never initialized (e.g. unused)
        self._cached_params = params
        n_in_box = {}

        def fn(*vals):
            n_in = n_in_box["n"]
            inputs, pvals = vals[:n_in], vals[n_in:]
            with swapped_in(params, pvals):
                args_re, _ = _regroup(list(inputs), self._in_fmt)
                if not isinstance(args_re, list):
                    args_re = [args_re]
                out = self._eager_forward(*args_re)
            flat_out, self._out_fmt = _flatten(out)
            return flat_out if len(flat_out) > 1 else flat_out[0]

        self._cached_fn_meta = n_in_box
        self._cached_op = CachedOp(fn, name=self.name or "CachedOp",
                                   **{k: v for k, v in self._flags.items()
                                      if k in ("static_alloc", "static_shape",
                                               "inline_limit",
                                               "forward_bulk_size",
                                               "backward_bulk_size")})

    def _call_cached_op(self, *args):
        ctx = self._get_ctx(args)
        # make sure all deferred inits are resolved before tracing: run one
        # eager step if needed (reference runs _deferred_infer_shape first)
        try:
            params = list(self.collect_params().values())
            pvals = [p.data(ctx) for p in params if p._grad_req is not None]
        except (DeferredInitializationError, RuntimeError):
            return self._eager_forward(*args)

        flat_args, self._in_fmt = _flatten(list(args))
        if self._cached_op is None:
            self._build_cache(args)
        self._cached_fn_meta["n"] = len(flat_args)
        pvals = [p.data(ctx) for p in self._cached_params]
        out = self._cached_op(*(flat_args + pvals))
        if isinstance(out, list):
            regrouped, _ = _regroup(out, self._out_fmt)
            return regrouped
        return out

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Serialize for deployment (reference `gluon/block.py:1077`):
        traces the block into a Symbol DAG, saving ``path-symbol.json``
        (loadable via ``SymbolBlock.imports`` / ``mx.sym.load``) and
        ``path-%04d.params`` (``arg:``/``aux:``-prefixed binary container,
        the reference's export format). Returns (symbol_file, params_file).

        Call the block on real data once first so the input structure is
        known (same requirement as the reference)."""
        from .. import symbol as sym_ns
        fmt = getattr(self, "_in_fmt", None)
        if fmt is None:
            fmt = int(0)  # never called: assume a single input named 'data'
        flat_n = 1 if not isinstance(fmt, list) else len(fmt)
        mask = getattr(self, "_in_tensor_mask", None) or [True] * flat_n
        consts = getattr(self, "_in_const_vals", None) or [None] * flat_n
        n_tensors = sum(mask)
        names = ["data"] if n_tensors == 1 else \
            ["data%d" % i for i in range(n_tensors)]
        # non-tensor slots (None masks, scalar flags) are replayed with the
        # values from the last forward call, not turned into graph inputs
        slots, it = [], iter(names)
        for is_tensor, const in zip(mask, consts):
            slots.append(sym_ns.var(next(it)) if is_tensor else const)
        args_re, _ = _regroup(slots, fmt)
        if not isinstance(args_re, list):
            args_re = [args_re]
        out = self(*args_re)
        if isinstance(out, (list, tuple)):
            out = sym_ns.Group(list(out))
        symbol_file = "%s-symbol.json" % path
        out.save(symbol_file)
        graph_inputs = set(out.list_inputs())
        aux_names = set(out.list_auxiliary_states())
        from ..ndarray import ndarray as _nd
        arg_dict = {}
        for name, p in self.collect_params().items():
            if name not in graph_inputs:
                continue  # params unused by forward aren't part of the graph
            kind = "aux" if name in aux_names else "arg"
            arg_dict["%s:%s" % (kind, name)] = p._reduce()
        params_file = "%s-%04d.params" % (path, epoch)
        _nd.save(params_file, arg_dict)
        return symbol_file, params_file

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args):
        return super().__call__(*args)


class SymbolBlock(HybridBlock):
    """Run a Symbol graph as a Block (reference `gluon/block.py:1190`).

    The graph's variables (minus the declared inputs) become Parameters, so
    an imported model supports the full Block surface: forward on NDArrays
    (with autograd — ops dispatch through the registry and record on the
    tape), ``hybridize()`` (the evaluator is pure-JAX, so CachedOp jits the
    whole graph to one XLA program), re-export, and fine-tuning."""

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load an exported model: symbol JSON + optional binary params
        (reference `gluon/block.py:1252`)."""
        from .. import symbol as sym_ns
        out = sym_ns.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_ns.var(n) for n in input_names]
        ret = SymbolBlock(out, inputs)
        if param_file is not None:
            from ..ndarray import ndarray as _nd
            from .parameter import load_param_from_array
            loaded = _nd.load(param_file)
            if isinstance(loaded, list):  # zero-name container == no params
                if loaded:
                    raise ValueError(
                        "params file %s has unnamed arrays; SymbolBlock "
                        "needs name->array entries" % param_file)
                loaded = {}
            params = ret.collect_params()
            for key, v in loaded.items():
                name = key.split(":", 1)[1] \
                    if key.startswith(("arg:", "aux:")) else key
                if name not in params._params:
                    raise AssertionError(
                        "Parameter %s in file %s is not a variable of the "
                        "symbol graph" % (name, param_file))
                load_param_from_array(params._params[name], v, ctx)
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from ..symbol.symbol import Symbol as _Sym, Group as _Group
        if isinstance(outputs, (list, tuple)):
            outputs = _Group(list(outputs))
        if isinstance(inputs, _Sym):
            inputs = [inputs]
        self._sb_outputs = outputs
        self._input_names = [i.name for i in inputs]
        for node in outputs._toposort():
            if node._op is not None or node._name in self._input_names:
                continue
            is_aux = bool(node._attr.get("__aux__"))
            p = self.params.get(node._name,
                                grad_req="null" if is_aux else "write",
                                allow_deferred_init=True)
            self._reg_params[node._name] = p

    def infer_shape(self, *args):
        """Resolve parameter shapes from input shapes via the symbol shape
        pass — lets an imports() without a param file be initialized."""
        known = {n: a.shape for n, a in zip(self._input_names, args)}
        from ..symbol.symbol import _infer_shapes
        shapes = _infer_shapes(self._sb_outputs, known)
        for name, p in self._reg_params.items():
            if shapes.get(name) is not None:
                p.shape = tuple(shapes[name])

    def hybrid_forward(self, F, *args, **params):
        if len(args) != len(self._input_names):
            raise ValueError("SymbolBlock expects %d inputs (%s), got %d"
                             % (len(self._input_names), self._input_names,
                                len(args)))
        bindings = dict(zip(self._input_names, args))
        bindings.update(params)
        outs = _eval_symbol_graph(self._sb_outputs, bindings, F)
        return outs if len(outs) > 1 else outs[0]


def _eval_symbol_graph(root, bindings, F):
    """Topologically evaluate a Symbol DAG by dispatching each node through
    the F namespace (nd → registry invoke with tape recording; symbol →
    graph re-composition). The graph-executor analogue for Block use."""
    from ..symbol.symbol import _out_key, _node_arg_values
    values = {}
    for node in root._toposort():
        if node._op is None:
            if node._name not in bindings:
                raise ValueError("unbound variable %r in SymbolBlock"
                                 % node._name)
            values[_out_key(node, 0)] = bindings[node._name]
            continue
        call_args = _node_arg_values(node, values)
        out = getattr(F, node._op.name)(*call_args, **node._kwargs)
        if isinstance(out, (tuple, list)):
            for i, v in enumerate(out):
                values[_out_key(node, i)] = v
        else:
            values[_out_key(node, 0)] = out
    return [values[_out_key(s, i)] for s, i in root._outputs_list()]
