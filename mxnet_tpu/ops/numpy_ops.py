"""NumPy-semantics operator registrations (_npi_* / _np_* / _npx_*).

Role parity: reference ``src/operator/numpy/`` (16K LoC of np_* kernels
behind the mx.np/mx.npx frontends). Most are aliases onto the existing
jnp-backed corpus (which already has numpy semantics); the rest register
here. Value-dependent-shape ops (nonzero, unique, boolean indexing) work
eagerly on concrete arrays but cannot be traced under jit — the same
limitation the reference documents for their use inside hybridized
blocks.
"""
from __future__ import annotations

import numpy as _np
import jax
import jax.numpy as jnp

from ..base import dtype_np
from ._common import _bind_key, _RNG, _dt  # noqa: F401
from .registry import register, register_alias








# ------------------------------------------------------------- new ops

@register("around", aliases=("_npi_around",))
def around(x, decimals=0):
    return jnp.round(x, int(decimals))


@register("nonzero", aliases=("_npi_nonzero", "_npx_nonzero"),
          differentiable=False)
def nonzero(x):
    """Indices of nonzero elements, (N, ndim) int64 (reference
    np_nonzero_op.cc). Eager-only: output shape is value-dependent."""
    idx = _np.nonzero(_np.asarray(x))
    return jnp.stack([jnp.asarray(i, jnp.int64) for i in idx], axis=-1)


@register("rot90", aliases=("_npi_rot90",))
def rot90(x, k=1, axes=(0, 1)):
    return jnp.rot90(x, int(k), tuple(int(a) for a in axes))


@register("std", aliases=("_npi_std",))
def std(x, axis=None, dtype=None, ddof=0, keepdims=False):
    axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis
    out = jnp.std(x, axis=axis, ddof=int(ddof), keepdims=keepdims)
    return out.astype(dtype_np(dtype)) if dtype is not None else out


@register("var", aliases=("_npi_var",))
def var(x, axis=None, dtype=None, ddof=0, keepdims=False):
    axis = tuple(axis) if isinstance(axis, (list, tuple)) else axis
    out = jnp.var(x, axis=axis, ddof=int(ddof), keepdims=keepdims)
    return out.astype(dtype_np(dtype)) if dtype is not None else out


@register("unique", aliases=("_npi_unique",), differentiable=False,
          n_out=-1)
def unique(x, return_index=False, return_inverse=False,
           return_counts=False, axis=None):
    """Eager-only (value-dependent output shape), like the reference's
    np_unique_op.cc."""
    res = _np.unique(_np.asarray(x), return_index=return_index,
                     return_inverse=return_inverse,
                     return_counts=return_counts, axis=axis)
    if isinstance(res, tuple):
        return tuple(jnp.asarray(r) for r in res)
    return jnp.asarray(res)


@register("_npi_svd", aliases=("svd",), n_out=3)
def _npi_svd(A):
    """gesvd returning (UT, L, V) in the reference's layout
    (np_linalg svd: A = u @ diag(s) @ vh).

    Off-CPU the decomposition runs eagerly on the host (numpy) — the same
    move the reference makes routing gesvd to LAPACK when the device
    lacks a solver (`src/operator/tensor/la_op.h` CPU path). Host path is
    forward-only (no custom VJP), matching the reference's
    no-backward-for-gesvd contract on non-LAPACK devices. Whether libtpu
    compiles ``jnp.linalg.svd`` for the chip has not been tried on the
    current machine."""
    try:
        on_accel = any(d.platform not in ("cpu",) for d in jax.devices())
    except RuntimeError:
        on_accel = False
    if not on_accel:
        u, s, vh = jnp.linalg.svd(A, full_matrices=False)
        return u, s, vh

    import numpy as onp
    from jax.core import Tracer
    if isinstance(A, Tracer):
        # the host route only exists eagerly
        raise NotImplementedError(
            "svd inside jit is unsupported on TPU; call it eagerly or on "
            "a CPU context")
    dt = onp.dtype(onp.asarray(A).dtype)
    u, s, vh = onp.linalg.svd(onp.ascontiguousarray(A), full_matrices=False)
    return (jnp.asarray(u.astype(dt)), jnp.asarray(s.astype(dt)),
            jnp.asarray(vh.astype(dt)))


@register("einsum", aliases=("_npi_einsum",))
def einsum(*operands, subscripts="", optimize=0):
    return jnp.einsum(subscripts, *operands,
                      optimize="optimal" if optimize else "auto")


@register("tensordot", aliases=("_npi_tensordot",))
def tensordot(a, b, a_axes_summed=None, b_axes_summed=None, axes=None):
    if a_axes_summed is not None:
        return jnp.tensordot(a, b, axes=(tuple(a_axes_summed),
                                         tuple(b_axes_summed)))
    return jnp.tensordot(a, b, axes=2 if axes is None else axes)


@register("_npi_tensordot_int_axes")
def _npi_tensordot_int_axes(a, b, axes=2):
    return jnp.tensordot(a, b, axes=int(axes))


@register("diff", aliases=("_npi_diff",))
def diff(x, n=1, axis=-1):
    return jnp.diff(x, n=int(n), axis=int(axis))


@register("copysign", aliases=("_npi_copysign",))
def copysign(x1, x2):
    return jnp.copysign(x1, x2)


@register("_npi_copysign_scalar")
def _npi_copysign_scalar(x, scalar=1.0):
    return jnp.copysign(x, scalar)


@register("_npi_rcopysign_scalar")
def _npi_rcopysign_scalar(x, scalar=1.0):
    return jnp.copysign(jnp.asarray(scalar, x.dtype), x)


@register("lcm", aliases=("_npi_lcm",))
def lcm(x1, x2):
    return jnp.lcm(x1, x2)


@register("_npi_lcm_scalar")
def _npi_lcm_scalar(x, scalar=1):
    return jnp.lcm(x, jnp.asarray(int(scalar), x.dtype))


@register("ldexp", aliases=("_npi_ldexp",))
def ldexp(x1, x2):
    return jnp.ldexp(x1, x2.astype(jnp.int32))


@register("_npi_ldexp_scalar")
def _npi_ldexp_scalar(x, scalar=0):
    return jnp.ldexp(x, int(scalar))


@register("_npi_rldexp_scalar")
def _npi_rldexp_scalar(x, scalar=1.0):
    return jnp.ldexp(jnp.asarray(scalar, x.dtype), x.astype(jnp.int32))


@register("arctan2", aliases=("_npi_arctan2",))
def arctan2(x1, x2):
    return jnp.arctan2(x1, x2)


@register("_npi_arctan2_scalar")
def _npi_arctan2_scalar(x, scalar=0.0):
    return jnp.arctan2(x, jnp.asarray(scalar, x.dtype))


@register("_npi_rarctan2_scalar")
def _npi_rarctan2_scalar(x, scalar=0.0):
    return jnp.arctan2(jnp.asarray(scalar, x.dtype), x)


@register("nan_to_num", aliases=("_npi_nan_to_num",))
def nan_to_num(x, copy=True, nan=0.0, posinf=None, neginf=None):
    return jnp.nan_to_num(x, nan=nan, posinf=posinf, neginf=neginf)


@register("_npi_indices", aliases=("indices",))
def _npi_indices(dimensions=(), dtype=None, ctx=None):
    return jnp.indices(tuple(int(d) for d in dimensions),
                       dtype=_dt(dtype, _np.int32))


@register("logspace", aliases=("_npi_logspace",))
def logspace(start=0.0, stop=1.0, num=50, endpoint=True, base=10.0,
             ctx=None, dtype=None):
    return jnp.logspace(start, stop, int(num), endpoint=endpoint,
                        base=base, dtype=_dt(dtype))


@register("_npi_blackman", aliases=("blackman",))
def _npi_blackman(M=0, ctx=None, dtype=None):
    return jnp.blackman(int(M)).astype(_dt(dtype))


@register("_npi_hamming", aliases=("hamming",))
def _npi_hamming(M=0, ctx=None, dtype=None):
    return jnp.hamming(int(M)).astype(_dt(dtype))


@register("_npi_hanning", aliases=("hanning",))
def _npi_hanning(M=0, ctx=None, dtype=None):
    return jnp.hanning(int(M)).astype(_dt(dtype))


@register("column_stack", aliases=("_npi_column_stack",))
def column_stack(*data, num_args=None):
    return jnp.column_stack(data)


@register("dstack", aliases=("_npi_dstack",))
def dstack(*data, num_args=None):
    return jnp.dstack(data)


@register("vstack", aliases=("_npi_vstack",))
def vstack(*data, num_args=None):
    return jnp.vstack(data)


@register("_npi_hsplit", n_out=-1)
def _npi_hsplit(x, indices=(), sections=0, axis=None, squeeze_axis=False):
    if sections:
        return tuple(jnp.split(x, int(sections), axis=1 if x.ndim > 1
                               else 0))
    return tuple(jnp.split(x, [int(i) for i in indices],
                           axis=1 if x.ndim > 1 else 0))


@register("tril", aliases=("_npi_tril",))
def tril(x, k=0):
    return jnp.tril(x, int(k))


@register("moveaxis", aliases=("_np_moveaxis",))
def moveaxis(x, source=0, destination=0):
    src = tuple(source) if isinstance(source, (list, tuple)) else int(source)
    dst = tuple(destination) if isinstance(destination, (list, tuple)) \
        else int(destination)
    return jnp.moveaxis(x, src, dst)


@register("trace", aliases=("_np_trace",))
def trace(x, offset=0, axis1=0, axis2=1):
    return jnp.trace(x, int(offset), int(axis1), int(axis2))


@register("_npi_identity")
def _npi_identity(n=0, ctx=None, dtype=None):
    return jnp.eye(int(n), dtype=_dt(dtype))


@register("share_memory", aliases=("_npi_share_memory",),
          differentiable=False)
def share_memory(a, b):
    """Whether two arrays may share memory — always False across jax
    functional arrays (reference np_memory_op.cc)."""
    return jnp.zeros((), dtype=bool)


@register("_npi_boolean_mask_assign_scalar")
def _npi_boolean_mask_assign_scalar(data, mask, value=0.0):
    return jnp.where(mask.astype(bool), jnp.asarray(value, data.dtype),
                     data)


@register("_npi_boolean_mask_assign_tensor")
def _npi_boolean_mask_assign_tensor(data, mask, value):
    """Eager-only when value must be scattered by mask count; supports
    broadcastable value tensors directly."""
    m = mask.astype(bool)
    if value.shape == data.shape:
        return jnp.where(m, value, data)
    flat_idx = _np.nonzero(_np.asarray(m).ravel())[0]
    flat = data.ravel()
    flat = flat.at[jnp.asarray(flat_idx)].set(value.ravel())
    return flat.reshape(data.shape)


@register("_npi_bernoulli", differentiable=False, state_binders=_RNG)
def _npi_bernoulli(prob=None, logit=None, size=None, ctx=None, dtype=None,
                   key=None):
    if prob is None and logit is None:
        prob = 0.5
    elif prob is None:
        prob = jax.nn.sigmoid(jnp.asarray(logit))
    shape = tuple(size) if size is not None else jnp.shape(prob)
    out = jax.random.bernoulli(key, prob, shape)
    return out.astype(_dt(dtype))


@register("_npi_choice", differentiable=False, state_binders=_RNG)
def _npi_choice(a=None, size=None, replace=True, p=None, ctx=None,
                key=None, weights=None):
    n = int(a) if not hasattr(a, "shape") else a.shape[0]
    shape = tuple(size or ())
    pool = jnp.arange(n) if not hasattr(a, "shape") else a
    probs = p if p is not None else weights
    return jax.random.choice(key, pool, shape, replace=bool(replace),
                             p=probs)


@register("_npi_multinomial", differentiable=False, state_binders=_RNG)
def _npi_multinomial(n=1, pvals=None, size=None, key=None):
    """np.random.multinomial: draw counts over categories (reference
    np_multinomial_op.h)."""
    k = pvals.shape[-1] if hasattr(pvals, "shape") else len(pvals)
    p = jnp.asarray(pvals)
    shape = tuple(size or ()) + (int(n),)
    draws = jax.random.categorical(key, jnp.log(jnp.maximum(p, 1e-30)),
                                   shape=shape)
    counts = jax.nn.one_hot(draws, k, dtype=jnp.int64).sum(axis=-2)
    return counts


# ------------------------------------------------------------- aliases

_NPI_ALIASES = {
    "abs": ("_npi_abs", "_npi_absolute"),
    "add": ("_npi_add",),
    "_plus_scalar": ("_npi_add_scalar",),
    "subtract": ("_npi_subtract",),
    "_minus_scalar": ("_npi_subtract_scalar",),
    "_rminus_scalar": ("_npi_rsubtract_scalar",),
    "multiply": ("_npi_multiply",),
    "_mul_scalar": ("_npi_multiply_scalar",),
    "divide": ("_npi_true_divide",),
    "_div_scalar": ("_npi_true_divide_scalar",),
    "_rdiv_scalar": ("_npi_rtrue_divide_scalar",),
    "mod": ("_npi_mod",),
    "_mod_scalar": ("_npi_mod_scalar",),
    "_rmod_scalar": ("_npi_rmod_scalar",),
    "power": ("_npi_power",),
    "_power_scalar": ("_npi_power_scalar",),
    "_rpower_scalar": ("_npi_rpower_scalar",),
    "maximum": ("_npi_maximum",),
    "_maximum_scalar": ("_npi_maximum_scalar",),
    "minimum": ("_npi_minimum",),
    "_minimum_scalar": ("_npi_minimum_scalar",),
    "hypot": ("_npi_hypot",),
    "_hypot_scalar": ("_npi_hypot_scalar",),
    "arccos": ("_npi_arccos",), "arccosh": ("_npi_arccosh",),
    "arcsin": ("_npi_arcsin",), "arcsinh": ("_npi_arcsinh",),
    "arctan": ("_npi_arctan",), "arctanh": ("_npi_arctanh",),
    "cos": ("_npi_cos",), "cosh": ("_npi_cosh",),
    "sin": ("_npi_sin",), "sinh": ("_npi_sinh",),
    "tan": ("_npi_tan",), "tanh": ("_npi_tanh",),
    "exp": ("_npi_exp",), "expm1": ("_npi_expm1",),
    "log": ("_npi_log",), "log10": ("_npi_log10",),
    "log1p": ("_npi_log1p",), "log2": ("_npi_log2",),
    "sqrt": ("_npi_sqrt",), "square": ("_npi_square",),
    "cbrt": ("_npi_cbrt",), "ceil": ("_npi_ceil",),
    "floor": ("_npi_floor",), "fix": ("_npi_fix",),
    "rint": ("_npi_rint",), "trunc": ("_npi_trunc",),
    "sign": ("_npi_sign",), "negative": ("_npi_negative",),
    "reciprocal": ("_npi_reciprocal",),
    "radians": ("_npi_radians", "_npi_deg2rad"),
    "degrees": ("_npi_degrees", "_npi_rad2deg"),
    "logical_not": ("_npi_logical_not",),
    "argmax": ("_npi_argmax",), "argmin": ("_npi_argmin",),
    "cast": ("_npi_cast", "_npx_cast"),
    "clip": ("_npi_clip",),
    "concat": ("_npi_concatenate",),
    "cumsum": ("_np_cumsum",),
    "gather_nd": ("_npi_gather_nd",),
    "expand_dims": ("_npi_expand_dims",),
    "flip": ("_npi_flip",),
    "_eye": ("_npi_eye",),
    "_full": ("_npi_full",),
    "_ones": ("_npi_ones",),
    "_zeros": ("_npi_zeros",),
    "_linspace": ("_npi_linspace",),
    "_arange": ("_npi_arange",),
    "_histogram": ("_npi_histogram",),
    "mean": ("_npi_mean",),
    "max": ("_np_max",), "min": ("_np_min",),
    "sum": ("_np_sum",), "prod": ("_np_prod",),
    "broadcast_to": ("_np_broadcast_to",),
    "_copy": ("_np_copy",),
    "ones_like": ("_np_ones_like",), "zeros_like": ("_np_zeros_like",),
    "squeeze": ("_np_squeeze",),
    "repeat": ("_np_repeat",),
    "roll": ("_np_roll",),
    "dot": ("_np_dot",),
    "reshape": ("_npi_reshape", "_np_reshape", "_npx_reshape"),
    "transpose": ("_np_transpose",),
    "swapaxes": ("_npi_swapaxes",),
    "take": ("_npi_take",),
    "tile": ("_npi_tile",),
    "stack": ("_npi_stack",),
    "split": ("_npi_split",),
    "slice": ("_npi_slice", "_npx_slice"),
    "_slice_assign": ("_npi_slice_assign",),
    "_slice_assign_scalar": ("_npi_slice_assign_scalar",),
    "_scatter_set_nd": ("_npi_scatter_set_nd",),
    "_shuffle": ("_np__random_shuffle",),
    "_rnn_param_concat": ("_npi_rnn_param_concat",),
    "_contrib_boolean_mask": ("_npi_boolean_mask",),
    "linalg_potrf": ("_npi_cholesky",),
    "linalg_inverse": ("_npi_inv",),
    "_random_normal": ("_npi_normal",),
    "_random_uniform": ("_npi_uniform",),
    "_random_randint": ("_npi_random_randint",),
    # npx nn aliases
    "activation": ("_npx_activation",),
    "batch_dot": ("_npx_batch_dot",),
    "flatten": ("_npx_batch_flatten",),
    "batch_norm": ("_npx_batch_norm",),
    "convolution": ("_npx_convolution",),
    "deconvolution": ("_npx_deconvolution",),
    "dropout": ("_npx_dropout",),
    "embedding": ("_npx_embedding",),
    "fully_connected": ("_npx_fully_connected",),
    "gamma": ("_npx_gamma",),
    "layer_norm": ("_npx_layer_norm",),
    "LeakyReLU": ("_npx_leaky_relu",),
    "log_softmax": ("_npx_log_softmax",),
    "one_hot": ("_npx_one_hot",),
    "pick": ("_npx_pick",),
    "pooling": ("_npx_pooling",),
    "relu": ("_npx_relu",),
    "reshape_like": ("_npx_reshape_like",),
    "ROIPooling": ("_npx_roi_pooling",),
    "sequence_mask": ("_npx_sequence_mask",),
    "sigmoid": ("_npx_sigmoid",),
    "smooth_l1": ("_npx_smooth_l1",),
    "softmax": ("_npx_softmax",),
    "topk": ("_npx_topk",),
}

for _existing, _names in _NPI_ALIASES.items():
    register_alias(_existing, *_names)
