"""Python support layer for the flat C ABI (src/c_api/c_api.cc).

Role parity: the reference implements its C ABI in `src/c_api/*.cc`
directly against the C++ runtime (c_api.cc, c_api_symbolic.cc,
c_api_executor.cc, c_predict_api.cc). In the TPU rebuild the runtime
objects live in Python (over JAX/XLA), so the C boundary is a thin
marshalling layer (c_api.cc: strings/arrays/handles <-> Python) and THIS
module is where each entry point lands — one flat function per ABI call,
operating on the same runtime objects the Python frontend uses.

Nothing here is Python-public API; the stable surface is
src/include/mxtpu_c.h.
"""
import json
import os
import tempfile

import numpy as _np


# ----------------------------------------------------------------- helpers

def _ctx(s):
    """Parse a device string: 'cpu', 'cpu(0)', 'gpu(1)', 'tpu(0)'."""
    from . import context
    if not s:
        return context.current_context()
    s = s.strip()
    dev_id = 0
    if "(" in s:
        name, rest = s.split("(", 1)
        dev_id = int(rest.rstrip(")") or 0)
    else:
        name = s
    name = name.strip()
    if name in ("cpu", "cpu_pinned"):
        return context.cpu(dev_id)
    if name in ("gpu", "tpu"):
        return context.tpu(dev_id)
    raise ValueError("unknown device string %r" % s)


def _parse_val(v):
    """Reference frontends pass op params as strings; recover typed values
    the way dmlc::Parameter would (bool/int/float/tuple), else keep str."""
    if not isinstance(v, str):
        return v
    s = v.strip()
    low = s.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    import ast
    try:
        return ast.literal_eval(s)  # ints, floats, tuples incl. "(4,)"
    except (ValueError, SyntaxError):
        pass
    try:
        return json.loads(s)
    except (json.JSONDecodeError, ValueError):
        pass
    return v


def _kwargs(keys, vals):
    return {k: _parse_val(v) for k, v in zip(keys, vals)}


# ----------------------------------------------------------------- ndarray

def ndarray_create(shape, dtype, ctx_str):
    from .ndarray import ndarray as nd
    return nd.zeros(tuple(shape), ctx=_ctx(ctx_str) if ctx_str else None,
                    dtype=dtype or "float32")


def ndarray_dtype(a):
    return _np.dtype(a.dtype).name


def ndarray_ctx(a):
    c = a.ctx
    return "%s(%d)" % (c.device_type, c.device_id)


def ndarray_storage_type(a):
    return getattr(a, "stype", "default")


def ndarray_reshape(a, dims):
    return a.reshape(tuple(dims))


def ndarray_slice(a, begin, end):
    return a[begin:end]


def ndarray_at(a, idx):
    return a[idx]


def ndarray_detach(a):
    return a.detach() if hasattr(a, "detach") else a


def ndarray_grad(a):
    return a.grad


def ndarray_wait_to_read(a):
    a.wait_to_read()


def ndarray_save(fname, arrays, keys):
    from .ndarray import ndarray as nd
    if keys:
        nd.save(fname, dict(zip(keys, arrays)))
    else:
        nd.save(fname, list(arrays))


def ndarray_load(fname):
    from .ndarray import ndarray as nd
    data = nd.load(fname)
    if isinstance(data, dict):
        names = list(data.keys())
        return names, [data[n] for n in names]
    return [], list(data)


def ndarray_load_from_bytes(buf):
    """Reference MXNDArrayLoadFromBuffer (c_api.cc): the predict API hands
    the .params file CONTENT, not a path."""
    with tempfile.NamedTemporaryFile(suffix=".params", delete=False) as fh:
        fh.write(buf)
        path = fh.name
    try:
        return ndarray_load(path)
    finally:
        os.unlink(path)


# ---------------------------------------------------------------- autograd

def autograd_set_recording(flag):
    from . import autograd
    return autograd.set_recording(bool(flag))


def autograd_set_training(flag):
    from . import autograd
    return autograd.set_training(bool(flag))


def autograd_is_recording():
    from . import autograd
    return autograd.is_recording()


def autograd_is_training():
    from . import autograd
    return autograd.is_training()


# reference OpReqType: 0 kNullOp, 1 kWriteTo, 2 kWriteInplace, 3 kAddTo
_GRAD_REQ = {0: "null", 1: "write", 2: "write", 3: "add"}


def autograd_mark_variables(arrays, reqs, grads):
    from . import autograd
    autograd.mark_variables(
        list(arrays), list(grads),
        [_GRAD_REQ.get(int(r), "write") for r in reqs])


def autograd_backward(outputs, ograds, retain_graph, train_mode):
    from . import autograd
    autograd.backward(list(outputs),
                      list(ograds) if ograds else None,
                      retain_graph=bool(retain_graph),
                      train_mode=bool(train_mode))


# ------------------------------------------------------------------ symbol

class _AtomicSymbol:
    """Two-phase construction mirroring the reference ABI
    (MXSymbolCreateAtomicSymbol then MXSymbolCompose mutates the SAME
    handle — c_api_symbolic.cc). Until compose the node is pending; after
    compose every call forwards to the composed Symbol."""

    def __init__(self, op_name, kwargs):
        self._pending = (op_name, kwargs)
        self._real = None

    def compose(self, name, keys, args):
        from .symbol import symbol as sym
        op_name, kwargs = self._pending
        maker = sym._sym_op(op_name)
        pos, kw = [], dict(kwargs)
        unwrapped = [_sym_unwrap(a) for a in args]
        if keys and any(keys):
            for k, a in zip(keys, unwrapped):
                if k:
                    kw[k] = a
                else:
                    pos.append(a)
        else:
            pos = unwrapped
        self._real = maker(*pos, name=name or None, **kw)
        return None


def _sym_unwrap(h):
    if isinstance(h, _AtomicSymbol):
        if h._real is None:
            h.compose(None, [], [])
        return h._real
    return h


def symbol_create_variable(name):
    from .symbol import symbol as sym
    return sym.var(name)


def symbol_create_atomic(op_name, keys, vals):
    from .ops.registry import get_op
    if get_op(op_name) is None:
        raise ValueError("unknown operator: %s" % op_name)
    return _AtomicSymbol(op_name, _kwargs(keys, vals))


def symbol_compose(h, name, keys, args):
    if isinstance(h, _AtomicSymbol):
        h.compose(name, keys, args)
    else:
        raise TypeError("MXSymbolCompose: handle is already composed")


def symbol_create_group(handles):
    from .symbol import symbol as sym
    return sym.Group([_sym_unwrap(h) for h in handles])


def symbol_get_output(h, index):
    return _sym_unwrap(h)[index]


def symbol_get_internals(h):
    return _sym_unwrap(h).get_internals()


def symbol_get_name(h):
    return _sym_unwrap(h).name


def symbol_num_outputs(h):
    return len(_sym_unwrap(h)._outputs_list())


def symbol_list_arguments(h):
    return _sym_unwrap(h).list_arguments()


def symbol_list_outputs(h):
    return _sym_unwrap(h).list_outputs()


def symbol_list_aux(h):
    return _sym_unwrap(h).list_auxiliary_states()


def symbol_infer_shape(h, keys, shapes, partial):
    s = _sym_unwrap(h)
    # None = unknown shape (C side encodes ndim=-1): leave unconstrained
    kw = {k: tuple(v) for k, v in zip(keys, shapes) if v is not None}
    if partial:
        arg, out, aux = s.infer_shape_partial(**kw)
    else:
        arg, out, aux = s.infer_shape(**kw)

    def clean(lst):
        return [tuple(int(d) for d in t) if t is not None else None
                for t in (lst or [])]
    complete = arg is not None and all(t is not None for t in (arg or []))
    return clean(arg), clean(out), clean(aux), complete


def symbol_tojson(h):
    return _sym_unwrap(h).tojson()


def symbol_from_json(js):
    from .symbol import symbol as sym
    return sym.load_json(js)


def symbol_save_file(h, fname):
    _sym_unwrap(h).save(fname)


def symbol_load_file(fname):
    from .symbol import symbol as sym
    return sym.load(fname)


def symbol_copy(h):
    from .symbol import symbol as sym
    return sym.load_json(_sym_unwrap(h).tojson())


def symbol_get_attr(h, key):
    return _sym_unwrap(h).attr(key)


def symbol_set_attr(h, key, val):
    _sym_unwrap(h)._set_attr(**{key: val})


def symbol_print(h):
    s = _sym_unwrap(h)
    lines = ["Symbol outputs: %s" % ", ".join(s.list_outputs())]
    for n in s._toposort():
        op = n._op.name if n._op else "null"
        lines.append("  %-24s %s" % (n._name or "?", op))
    return "\n".join(lines)


# ---------------------------------------------------------------- executor

def executor_simple_bind(h, ctx_str, grad_req, keys, shapes):
    s = _sym_unwrap(h)
    kw = {k: tuple(v) for k, v in zip(keys, shapes) if v is not None}
    return s.simple_bind(_ctx(ctx_str), grad_req=grad_req or "write", **kw)


def executor_forward(ex, is_train):
    ex.forward(is_train=bool(is_train))


def executor_backward(ex, ograds):
    ex.backward(list(ograds) if ograds else None)


def executor_outputs(ex):
    return list(ex.outputs)


def executor_arg_names(ex):
    return list(ex._arg_names)


def executor_arg_arrays(ex):
    return [ex.arg_dict[n] for n in ex._arg_names]


def executor_grad_arrays(ex):
    return [ex.grad_dict.get(n) for n in ex._arg_names]


def executor_aux_arrays(ex):
    return [ex.aux_dict[n] for n in ex._aux_names]


def executor_print(ex):
    return ex.debug_str()


# ----------------------------------------------------------------- kvstore

def kvstore_create(kind):
    from . import kvstore
    return kvstore.create(kind or "local")


def kvstore_init(kv, keys, vals):
    kv.init(list(keys), list(vals))


def kvstore_push(kv, keys, vals, priority):
    # KVStore.push already aggregates repeated keys (per-device values)
    kv.push(list(keys), list(vals), priority=priority)


def kvstore_pull(kv, keys, outs, priority):
    for k, o in zip(keys, outs):
        kv.pull(k, out=o, priority=priority)


def kvstore_type(kv):
    return kv.type


def kvstore_rank(kv):
    return kv.rank


def kvstore_group_size(kv):
    return kv.num_workers


def kvstore_barrier(kv):
    kv.barrier()


def kvstore_num_dead_node(kv):
    return kv.num_dead_node


def kvstore_set_gradient_compression(kv, keys, vals):
    kv.set_gradient_compression(_kwargs(keys, vals))


# ---------------------------------------------------------------- data io

# C-creatable iterators: the file-fed ones whose every parameter is a
# string (reference MXListDataIters lists the C++ iterators only;
# NDArrayIter is a Python-frontend construct there too).
_ITER_NAMES = ["CSVIter", "MNISTIter", "ImageRecordIter"]


class _IterState:
    """Holds the live iterator plus its current batch (the reference C
    iterator contract: Next() advances, GetData/GetLabel read the current
    position — c_api.cc MXDataIterNext)."""

    def __init__(self, it):
        self.it = it
        self.batch = None


def list_data_iters():
    return list(_ITER_NAMES)


def dataiter_create(name, keys, vals):
    from . import io
    if name not in _ITER_NAMES:
        raise ValueError("unknown data iter: %s" % name)
    kw = _kwargs(keys, vals)
    return _IterState(getattr(io, name)(**kw))


def dataiter_next(st):
    try:
        st.batch = st.it.next()
        return 1
    except StopIteration:
        st.batch = None
        return 0


def dataiter_before_first(st):
    st.it.reset()
    st.batch = None


def dataiter_get_data(st):
    if st.batch is None:
        raise RuntimeError("call MXDataIterNext first")
    return st.batch.data[0]


def dataiter_get_label(st):
    if st.batch is None:
        raise RuntimeError("call MXDataIterNext first")
    return st.batch.label[0]


def dataiter_get_pad(st):
    if st.batch is None:
        raise RuntimeError("call MXDataIterNext first")
    return int(st.batch.pad or 0)


# ---------------------------------------------------------------- recordio

def recordio_writer_create(uri):
    from . import recordio
    return recordio.MXRecordIO(uri, "w")  # __init__ opens


def recordio_writer_write(w, buf):
    w.write(bytes(buf))


def recordio_writer_tell(w):
    return w.tell()


def recordio_close(rw):
    rw.close()


def recordio_reader_create(uri):
    from . import recordio
    return recordio.MXRecordIO(uri, "r")  # __init__ opens


def recordio_reader_read(r):
    return r.read()  # bytes or None at EOF


def recordio_reader_seek(r, pos):
    r.seek(pos)


def recordio_reader_tell(r):
    return r.tell()


# ----------------------------------------------------------------- predict

class _Predictor:
    """Inference-only executor over an exported (symbol-json, params)
    pair — reference c_predict_api.cc MXPredCreate/SetInput/Forward/
    GetOutput lifecycle."""

    def __init__(self, symbol_json, param_bytes, dev_str, input_keys,
                 input_shapes):
        from .ndarray import ndarray as nd
        self.ctx = _ctx(dev_str)
        self.sym = symbol_from_json(symbol_json)
        names, arrays = (ndarray_load_from_bytes(param_bytes)
                         if param_bytes else ([], []))
        params = {}
        for n, a in zip(names, arrays):
            params[n.split(":", 1)[-1]] = a  # strip arg:/aux: prefixes
        shape_kw = {k: tuple(v) for k, v in zip(input_keys, input_shapes)}
        self.input_keys = list(input_keys)
        self.exec = self.sym.simple_bind(self.ctx, grad_req="null",
                                         **shape_kw)
        for n in self.exec._arg_names:
            if n in params:
                self.exec.arg_dict[n][:] = params[n]
        for n in self.exec._aux_names:
            if n in params:
                self.exec.aux_dict[n][:] = params[n]
        self._nd = nd

    def set_input(self, name, buf):
        arr = self.exec.arg_dict[name]
        host = _np.frombuffer(buf, dtype=_np.float32).reshape(arr.shape)
        arr[:] = host

    def forward(self):
        self.exec.forward(is_train=False)

    def output_shape(self, i):
        return tuple(int(d) for d in self.exec.outputs[i].shape)

    def output(self, i):
        return self.exec.outputs[i].asnumpy().astype(
            _np.float32).tobytes()

    def reshape(self, keys, shapes):
        kw = {k: tuple(v) for k, v in zip(keys, shapes)}
        self.exec = self.exec.reshape(allow_up_sizing=True, **kw)


def pred_create(symbol_json, param_bytes, dev_str, input_keys,
                input_shapes):
    return _Predictor(symbol_json, param_bytes, dev_str, input_keys,
                      input_shapes)


# -------------------------------------------------------------------- misc

def random_seed(seed):
    from . import random
    random.seed(int(seed))


def lib_info_features():
    from .runtime import feature_list
    feats = feature_list()
    names = [f.name for f in feats]
    enabled = [1 if f.enabled else 0 for f in feats]
    return names, enabled


def device_count():
    import jax
    return len(jax.devices())


def is_np_shape():
    from . import numpy_extension as npx
    return 1 if npx.is_np_shape() else 0


def set_np_shape(active):
    from . import numpy_extension as npx
    prev = npx.is_np_shape()
    if active:
        npx.set_np()
    else:
        npx.reset_np()
    return 1 if prev else 0


def profiler_set_state(state):
    from . import profiler
    profiler.set_state(state)


def profiler_set_config(keys, vals):
    from . import profiler
    profiler.set_config(**_kwargs(keys, vals))


def profiler_dump(finished):
    from . import profiler
    profiler.dump(bool(finished))


# ------------------------------------------------- round-5 ABI additions
# (introspection / cached-op / monitor callbacks / kvstore updater /
#  Ex-surface support; reference c_api.h names cited per entry point)


def atomic_symbol_creators():
    """MXSymbolListAtomicSymbolCreators (reference c_api.h:1076): the op
    registry's names, sorted for a stable creator ordering."""
    from .ops.registry import list_ops
    return sorted(list_ops())


def atomic_symbol_info(name):
    """MXSymbolGetAtomicSymbolInfo (reference c_api.h:1090): enough
    signature metadata to generate a language binding mechanically."""
    import inspect
    from .ops.registry import get_op
    op = get_op(name)
    fn = op.fn
    doc = inspect.getdoc(fn) or ""
    arg_names, arg_types, arg_descs = [], [], []
    key_var_num_args = ""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        sig = None
    if sig is not None:
        # tensor prefix: leading params with no default are tensor inputs.
        # None-defaulted params INSIDE that prefix are OPTIONAL tensor
        # inputs only when their NAME is a conventional tensor slot
        # (bias/gamma/...): signatures interleave None-defaulted config
        # params (num_hidden=None) with the tensor prefix, so name is the
        # only reliable discriminator without per-op arity metadata
        tensor_slots = {"bias", "gamma", "beta", "moving_mean",
                        "moving_var", "weight", "label", "state_cell",
                        "aux_states"}
        in_tensor_prefix = True
        for pname, p in sig.parameters.items():
            if pname in ("key", "train"):      # state-binder internals
                continue
            if p.kind == inspect.Parameter.VAR_POSITIONAL:
                key_var_num_args = "num_args"
                arg_names.append(pname)
                arg_types.append("NDArray-or-Symbol[]")
                arg_descs.append("variadic tensor inputs")
                continue
            if p.kind == inspect.Parameter.VAR_KEYWORD:
                continue
            if p.default is inspect.Parameter.empty:
                arg_names.append(pname)
                arg_types.append("NDArray-or-Symbol")
                arg_descs.append("tensor input")
            elif (p.default is None and in_tensor_prefix
                  and pname in tensor_slots):
                arg_names.append(pname)
                arg_types.append("NDArray-or-Symbol, optional")
                arg_descs.append("optional tensor input")
            else:
                in_tensor_prefix = False
                arg_names.append(pname)
                d = p.default
                t = ("boolean" if isinstance(d, bool) else
                     "int" if isinstance(d, int) else
                     "float" if isinstance(d, float) else
                     "Shape(tuple)" if isinstance(d, tuple) else
                     "string")
                arg_types.append("%s, optional, default=%r" % (t, d))
                arg_descs.append("parameter")
    return (name, doc, arg_names, arg_types, arg_descs, key_var_num_args,
            "NDArray-or-Symbol")


def symbol_infer_type(h, keys, types, partial):
    """MXSymbolInferType (c_api.h:1418): dtype strings in/out."""
    s = _sym_unwrap(h)
    kw = {k: t for k, t in zip(keys, types) if t}
    if partial and hasattr(s, "infer_type_partial"):
        arg, out, aux = s.infer_type_partial(**kw)
    else:
        arg, out, aux = s.infer_type(**kw)

    def clean(lst):
        return [_np.dtype(t).name if t is not None else ""
                for t in (lst or [])]
    complete = arg is not None and all(t is not None for t in (arg or []))
    return clean(arg), clean(out), clean(aux), complete


def symbol_get_children(h):
    """MXSymbolGetChildren: the node's immediate input symbols, grouped
    (reference c_api_symbolic.cc GetChildren returns a grouped symbol)."""
    s = _sym_unwrap(h)
    from .symbol import symbol as sym_mod
    kids = [p for p, _ in getattr(s, "_inputs", [])]
    return sym_mod.Group(kids) if kids else sym_mod.Group([])


def symbol_remove_amp_cast(h):
    """MXSymbolRemoveAmpCast: our graphs never materialize amp casts as
    nodes (AMP rides dtype policy), so the symbol is returned as-is
    (symbols are immutable graphs)."""
    return _sym_unwrap(h)


def executor_set_monitor(ex, cb_addr, cb_data_addr, monitor_all):
    """MXExecutorSetMonitorCallback (c_api.h:2205): the C callback
    (fn(name, NDArrayHandle, void*)) is rebuilt with ctypes inside the
    embedded interpreter and invoked per monitored output."""
    import ctypes
    CB = ctypes.CFUNCTYPE(None, ctypes.c_char_p, ctypes.c_void_p,
                          ctypes.c_void_p)
    cfn = CB(cb_addr)

    def monitor(name, arr):
        from .ndarray.ndarray import NDArray
        if not isinstance(arr, NDArray):
            arr = NDArray(arr)
        # CPython: id(obj) IS the PyObject* the ABI's handles are; `arr`
        # stays alive for the duration of the call via this local (the
        # callback must copy out, same contract as every TLS return)
        cfn(str(name).encode(), id(arr), cb_data_addr or None)

    ex.set_monitor_callback(monitor, bool(monitor_all))


def executor_reshape(ex, keys, shapes):
    kw = {k: tuple(v) for k, v in zip(keys, shapes) if v is not None}
    return ex.reshape(**kw)


def executor_optimized_symbol(ex):
    """MXExecutorGetOptimizedSymbol: graph passes are XLA's; the bound
    symbol IS the optimized graph at this layer."""
    return ex._symbol


def cached_op_create(h, keys, vals):
    """MXCreateCachedOp/Ex (c_api.h:1280): the cached callable evaluates
    the symbol's graph over positional inputs ordered as
    list_arguments() + list_auxiliary_states()."""
    s = _sym_unwrap(h)
    from .cached_op import CachedOp
    from .symbol.symbol import evaluate_graph
    from .ndarray.ndarray import NDArray
    arg_names = s.list_arguments()
    aux_names = s.list_auxiliary_states()
    names = arg_names + aux_names
    flags = _kwargs(keys, vals)
    flags = {k: v for k, v in flags.items()
             if k in ("static_alloc", "static_shape", "inline_limit",
                      "forward_bulk_size", "backward_bulk_size")}

    def fn(*arrs):
        assert len(arrs) == len(names), \
            "CachedOp expects %d inputs (%d args + %d aux), got %d" % (
                len(names), len(arg_names), len(aux_names), len(arrs))
        binds = {n: a._data for n, a in zip(names, arrs)}
        outs = evaluate_graph(s, binds)
        return [NDArray(o) for o in outs]

    op = CachedOp(fn, **flags)
    op._abi_num_inputs = len(names)
    return op


def cached_op_invoke(op, inputs):
    outs = op(*inputs)
    return outs if isinstance(outs, (list, tuple)) else [outs]


def autograd_backward_ex(heads, head_grads, variables, retain_graph,
                         create_graph, is_train):
    """MXAutogradBackwardEx (c_api.h:1180). Returns variable grads when
    ``variables`` is non-empty (x-grad mode), else writes .grad."""
    from . import autograd as ag
    hg = None
    if head_grads and any(g is not None for g in head_grads):
        hg = list(head_grads)
    if variables:
        grads = ag.grad(heads, variables, head_grads=hg,
                        retain_graph=bool(retain_graph),
                        create_graph=bool(create_graph),
                        train_mode=bool(is_train))
        return list(grads)
    ag.backward(heads, head_grads=hg, retain_graph=bool(retain_graph),
                train_mode=bool(is_train))
    return []


def kvstore_set_updater(kv, cb_addr, cb_data_addr):
    """MXKVStoreSetUpdater (c_api.h:2610): C updater
    fn(int key, NDArrayHandle recv, NDArrayHandle local, void*) rebuilt
    via ctypes; invoked on every push-aggregated value."""
    import ctypes
    CB = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                          ctypes.c_void_p, ctypes.c_void_p)
    cfn = CB(cb_addr)

    def updater(key, recv, local):
        try:
            ikey = int(key)
        except (TypeError, ValueError):
            ikey = abs(hash(str(key))) % (2 ** 31)
        # CPython: id(obj) IS the PyObject*; recv/local stay alive for
        # the duration of the call via these locals
        cfn(ikey, id(recv), id(local), cb_data_addr or None)

    kv._updater = updater
    if hasattr(kv, "set_updater"):
        kv.set_updater(updater)


def kvstore_pushpull(kv, keys, ins, outs, priority):
    kv.pushpull(list(keys), list(ins), out=list(outs),
                priority=priority)


def kvstore_pull_row_sparse(kv, keys, outs, row_ids, priority):
    kv.row_sparse_pull(list(keys), out=list(outs), priority=priority,
                       row_ids=list(row_ids))


def ndarray_create_none():
    from .ndarray.ndarray import NDArray
    import jax.numpy as jnp
    return NDArray(jnp.zeros((0,), jnp.float32))


def ndarray_wait_to_write(a):
    a.wait_to_read()   # functional arrays: read-ready == write-ready


def ndarray_save_raw_bytes(a):
    from .ndarray import ndarray as nd_mod
    import tempfile as _tf
    with _tf.NamedTemporaryFile(suffix=".params", delete=False) as f:
        path = f.name
    try:
        nd_mod.save(path, [a])
        with open(path, "rb") as f:
            return f.read()
    finally:
        os.unlink(path)


def _load_params_bytes(buf):
    from .ndarray import ndarray as nd_mod
    import tempfile as _tf
    with _tf.NamedTemporaryFile(suffix=".params", delete=False) as f:
        f.write(bytes(buf))
        path = f.name
    try:
        return nd_mod.load(path)
    finally:
        os.unlink(path)


def ndarray_load_from_raw_bytes(buf):
    out = _load_params_bytes(buf)
    if isinstance(out, dict):
        out = list(out.values())
    return out[0]


def ndarray_load_from_buffer(buf):
    """MXNDArrayLoadFromBuffer: the list/dict form of the raw loader."""
    out = _load_params_bytes(buf)
    if isinstance(out, dict):
        return list(out.keys()), list(out.values())
    return [], list(out)


def ndarray_sync_copy_from(dst, src):
    dst[:] = src


def ndarray_grad_state(a):
    return 1 if getattr(a, "_fresh_grad", False) else 0


def ndarray_set_grad_state(a, state):
    a._fresh_grad = bool(state)


def shallow_copy_ndarray(a):
    from .ndarray.ndarray import NDArray
    return NDArray(a._data, ctx=a.ctx)


def shallow_copy_symbol(h):
    s = _sym_unwrap(h)
    return s


def storage_empty_cache(dev_str):
    import gc
    gc.collect()
    try:
        import jax
        jax.clear_caches()
    except Exception:
        pass


def engine_set_bulk_size(size):
    from . import config
    prev = int(os.environ.get("MXNET_ENGINE_BULK_SIZE", "15") or 15)
    os.environ["MXNET_ENGINE_BULK_SIZE"] = str(int(size))
    return prev


def random_seed_context(seed, dev_str):
    from . import random as rnd
    rnd.seed(seed)


def profiler_pause(paused):
    from . import profiler
    profiler.pause() if paused else profiler.resume()


def profiler_aggregate_stats(reset, format_, sort_by, ascending):
    from . import profiler
    try:
        return profiler.dumps(reset=bool(reset))
    except TypeError:
        return profiler.dumps()


def load_lib(path):
    from . import library
    library.load(path)


def quantize_symbol(h, keys, vals):
    """MXQuantizeSymbol (c_api.h quantization surface): symbol-level
    entry over contrib.quantization.quantize_model's graph pass."""
    s = _sym_unwrap(h)
    from .contrib import quantization as q
    kw = _kwargs(keys, vals)
    qsym = q.quantize_graph(s, **kw) if hasattr(q, "quantize_graph") \
        else None
    if qsym is None:
        # quantize_model needs params; expose the symbol pass via the
        # model-level API with empty params where supported
        raise RuntimeError(
            "symbol-only quantization requires calibration params; use "
            "MXQuantizeSymbolWithParams / contrib.quantization."
            "quantize_model from the frontend")
    return qsym


def gen_backend_subgraph(h, backend):
    s = _sym_unwrap(h)
    from .symbol import subgraph
    return subgraph.partition(s, backend)


def dataiter_info(name):
    """MXDataIterGetIterInfo: signature metadata for a registered data
    iterator (string-name convention; reference uses creator handles)."""
    import inspect
    from .io import io as io_mod
    cls = getattr(io_mod, name, None)
    if cls is None:
        raise ValueError("unknown data iterator %r" % name)
    doc = inspect.getdoc(cls) or ""
    names, types, descs = [], [], []
    try:
        sig = inspect.signature(cls.__init__)
        for pname, p in sig.parameters.items():
            if pname == "self":
                continue
            names.append(pname)
            d = p.default
            if d is inspect.Parameter.empty:
                types.append("required")
            else:
                types.append("optional, default=%r" % (d,))
            descs.append("constructor parameter")
    except (TypeError, ValueError):
        pass
    return name, doc, names, types, descs
