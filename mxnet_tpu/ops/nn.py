"""Neural-network operators.

Role parity: reference ``src/operator/nn/`` (~29K LoC: convolution-inl.h,
fully_connected, pooling, batch_norm, layer_norm, softmax, dropout,
activation, rnn-inl.h RNNOp, + cudnn/ and mkldnn/ vendor forks).

TPU-native: every op lowers to XLA HLO via lax — conv_general_dilated hits
the MXU directly, reduce_window does pooling, and normalization/softmax are
fused elementwise chains XLA optimizes. No vendor forks: one code path for
eager and compiled, all layouts NCHW to match MXNet's API contract (XLA
re-layouts internally for the TPU).
"""
from __future__ import annotations

import math as _math
import os as _os

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

# round-5 perf-experiment gates (each a measured end-to-end loss in its
# default-off state -- see PERF.md round-5 study)
_POOL_EQBWD = _os.environ.get("MXTPU_MAXPOOL_EQBWD", "0") == "1"
_CONV_S2D = _os.environ.get("MXTPU_CONV_S2D", "0") == "1"
_BN_BARRIER = _os.environ.get("MXTPU_BN_BARRIER", "0") == "1"
# threefry restores jax.random.bernoulli dropout masks (10x costlier on
# the VPU than the default counter-hash; see PERF.md round-5 LM study)
_DROPOUT_THREEFRY = _os.environ.get("MXTPU_DROPOUT_THREEFRY", "0") == "1"

from ..base import dtype_np
from ._common import _bind_key, _bind_train
from .registry import register


# ------------------------------------------------------------ dense / conv


@register("FullyConnected", aliases=("fully_connected",))
def FullyConnected(data, weight, bias=None, num_hidden=None, no_bias=False,
                   flatten=True):
    """reference `src/operator/nn/fully_connected.cc:258` registration,
    kernel `fully_connected-inl.h` (cuBLAS gemm) — here: one jnp.dot on the
    MXU, bf16-friendly."""
    if flatten and data.ndim > 2:
        data = data.reshape((data.shape[0], -1))
    out = jnp.dot(data, weight.T)
    if not no_bias and bias is not None:
        out = out + bias
    return out


def _pair(v, n=2):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    t = tuple(v)
    return t if t else (1,) * n


def _conv_s2d_stride2(data, weight, padding):
    """Stride-2 conv with few input channels, rewritten via space-to-depth.

    A 7x7/s2 stem conv on 3 channels runs the MXU at ~3/128 packing — the
    round-5 profile measured the ResNet-50 stem fwd+dw at 5.2% of step time
    (~24 TFLOP/s vs the 54 conv ceiling). Mathematically identical rewrite:
    block-2 space-to-depth on the (padded) input (C -> 4C channels, half
    spatial) turns it into a ceil(k/2)^2 STRIDE-1 conv on 4C channels:
        out[o,i,j] = sum_{c,u,v} xp[c,2i+u,2j+v] w[o,c,u,v]
                   = sum_{c,r_u,r_v,q_u,q_v} X2[(c,ru,rv), i+qu, j+qv]
                                             W2[o,(c,ru,rv), qu, qv]
    with u = 2 qu + ru (kernel zero-padded k -> 2*ceil(k/2)). Same FLOPs,
    4x the MXU contraction depth, and the gradient convs (autodiff through
    the reshape/transpose) get the same packing win."""
    N, C, H, W = data.shape
    O, _, K, _ = weight.shape
    K2 = (K + 1) // 2
    xp = jnp.pad(data, [(0, 0), (0, 0), padding[0], padding[1]])
    Hp, Wp = xp.shape[2], xp.shape[3]
    x2 = xp.reshape(N, C, Hp // 2, 2, Wp // 2, 2)
    x2 = x2.transpose(0, 1, 3, 5, 2, 4).reshape(N, C * 4, Hp // 2, Wp // 2)
    wp = jnp.pad(weight, [(0, 0), (0, 0), (0, 2 * K2 - K), (0, 2 * K2 - K)])
    w2 = wp.reshape(O, C, K2, 2, K2, 2)
    w2 = w2.transpose(0, 1, 3, 5, 2, 4).reshape(O, C * 4, K2, K2)
    dn = lax.conv_dimension_numbers(x2.shape, w2.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return lax.conv_general_dilated(x2, w2, (1, 1), [(0, 0), (0, 0)],
                                    dimension_numbers=dn)


@register("Convolution", aliases=("convolution",))
def Convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                layout=None, cudnn_tune=None, cudnn_off=False, workspace=None):
    """reference `src/operator/nn/convolution-inl.h` — lowered to
    lax.conv_general_dilated (MXU systolic matmul path). Supports 1D/2D/3D
    NC* layouts + grouped conv."""
    nd = data.ndim - 2
    stride = _pair(stride, nd)
    dilate = _pair(dilate, nd)
    pad = _pair(pad, nd) if pad else (0,) * nd
    padding = [(p, p) for p in pad]
    if (_CONV_S2D and nd == 2 and num_group == 1 and stride == (2, 2)
            and dilate == (1, 1)
            and weight.ndim == 4 and weight.shape[1] * weight.shape[2] <= 32
            and weight.shape[2] == weight.shape[3]
            and weight.shape[2] % 2 == 1 and weight.shape[2] >= 5
            and (data.shape[2] + 2 * pad[0]) % 2 == 0
            and (data.shape[3] + 2 * pad[1]) % 2 == 0):
        # OFF by default: measured on-chip (round 5, ResNet-50 b32) the
        # space-to-depth shuffle cost exceeded the MXU-packing gain
        # (2695 vs 2782 img/s end-to-end, barrier'd or fused) — the stem
        # conv is latency- not depth-bound at these shapes. Kept behind
        # MXTPU_CONV_S2D=1; the rewrite itself is oracle-exact.
        out = _conv_s2d_stride2(data, weight, padding)
        if not no_bias and bias is not None:
            out = out + bias.reshape((1, -1, 1, 1))
        return out
    dn_str = {1: ("NCH", "OIH", "NCH"),
              2: ("NCHW", "OIHW", "NCHW"),
              3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dn_str)
    # NB: no preferred_element_type override — XLA already accumulates bf16
    # convs in fp32 on the TPU MXU, and an explicit f32 override breaks the
    # transpose (VJP) rule's dtype matching.
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@register("Deconvolution", aliases=("deconvolution",))
def Deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter=0, num_group=1, no_bias=True,
                  layout=None, target_shape=None, cudnn_tune=None,
                  cudnn_off=False, workspace=None):
    """reference `src/operator/nn/deconvolution-inl.h` — transposed conv via
    lax.conv_transpose."""
    nd = data.ndim - 2
    stride = _pair(stride, nd)
    dilate = _pair(dilate, nd)
    pad = _pair(pad, nd) if pad else (0,) * nd
    kernel = _pair(kernel, nd)
    adj = _pair(adj, nd) if adj else (0,) * nd
    # output padding semantics: out = (in-1)*s - 2p + dil*(k-1) + 1 + adj
    padding = []
    for p, k, d, a in zip(pad, kernel, dilate, adj):
        eff_k = d * (k - 1) + 1
        padding.append((eff_k - 1 - p, eff_k - 1 - p + a))
    # MXNet deconv weight layout is (C_in, C_out/g, k...): the transposed
    # conv is a regular conv with spatially-mirrored kernel and I/O swapped
    # (what lax's removed transpose_kernel flag used to do).
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    dn_str = {1: ("NCH", "IOH", "NCH"),
              2: ("NCHW", "IOHW", "NCHW"),
              3: ("NCDHW", "IODHW", "NCDHW")}[nd]
    dn = lax.conv_dimension_numbers(data.shape, w.shape, dn_str)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out




@jax.custom_vjp
def _fwd_barrier(x):
    """optimization_barrier in the forward pass only; gradients flow
    through untouched (a plain barrier transposes to a cotangent barrier,
    which breaks backward fusions)."""
    return lax.optimization_barrier(x)


_fwd_barrier.defvjp(lambda x: (lax.optimization_barrier(x), None),
                    lambda _, g: (g,))


# -- max-pool with a TPU-friendly backward ---------------------------------
#
# XLA derives reduce_window's max-pool gradient as select-and-scatter, which
# the round-2/round-5 profiles measured as the single slowest HLO in the
# ResNet-50 step (3.8% of device time for ONE op, plus a 1.8% forward that
# re-reads windows). This custom VJP keeps the reduce_window forward but
# replaces the backward with an equality-spread: each input position checks
# the <=ceil(k/s)^2 windows that cover it and accumulates g/count for every
# window whose max it equals (count = number of tied positions, computed
# with k^2 strided slices in output space). Tie handling differs from
# select-and-scatter (which gives the whole gradient to the FIRST max):
# ties SHARE the gradient — per-window gradient mass is identical, and for
# the no-tie case (distinct window values) the two are exactly equal.

def _cover_indices(in_size, out_size, k, s, p):
    """Per input coordinate y, the <=2 output windows covering it (valid
    for k <= 2s): index vectors (lo, hi) and hi's validity mask."""
    yp = _np.arange(in_size) + p
    lo = (yp - k + s) // s          # ceil((yp - k + 1) / s)
    hi = yp // s
    # full membership check (window i covers yp iff i*s <= yp < i*s + k):
    # with k < s there are inter-window gaps, and a clamped/gap index must
    # not claim coverage
    lo_ok = (lo >= 0) & (lo <= out_size - 1) & \
        (lo * s <= yp) & (lo * s + k > yp)
    hi_ok = (hi >= 0) & (hi <= out_size - 1) & (hi != lo) & \
        (hi * s <= yp) & (hi * s + k > yp)
    return (_np.clip(lo, 0, out_size - 1), lo_ok,
            _np.clip(hi, 0, out_size - 1), hi_ok)


def _maxpool2d_fwd(data, kernel, stride, padding):
    init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) \
        else jnp.asarray(jnp.iinfo(data.dtype).min, data.dtype)
    return lax.reduce_window(data, init, lax.max, (1, 1) + kernel,
                             (1, 1) + stride, [(0, 0), (0, 0)] + padding)


def _maxpool2d_nchw_bwd(kernel, stride, padding, res, g):
    data, out = res
    (kh, kw), (sh, sw) = kernel, stride
    (ph, _), (pw, _) = padding
    N, C, H, W = data.shape
    OH, OW = out.shape[2], out.shape[3]
    neg = jnp.asarray(-jnp.inf, data.dtype)
    xp = jnp.pad(data, [(0, 0), (0, 0), padding[0], padding[1]],
                 constant_values=neg)
    # ties per window: k*k strided slices of the padded input, all fused
    # into one elementwise pass in output space
    count = None
    for dy in range(kh):
        for dx in range(kw):
            sl = lax.slice(xp, (0, 0, dy, dx),
                           (N, C, dy + sh * (OH - 1) + 1,
                            dx + sw * (OW - 1) + 1), (1, 1, sh, sw))
            eq = (sl == out).astype(jnp.float32)
            count = eq if count is None else count + eq
    gn = (g.astype(jnp.float32) / count).astype(data.dtype)
    # spread back: for each of the <=2x2 covering windows per position,
    # gather out/gn rows (constant index vectors -> fused gathers) and
    # accumulate where the input equals the window max
    ylo, ylo_ok, yhi, yhi_ok = _cover_indices(H, OH, kh, sh, ph)
    xlo, xlo_ok, xhi, xhi_ok = _cover_indices(W, OW, kw, sw, pw)
    gin = jnp.zeros(data.shape, data.dtype)
    for yi, ym in ((ylo, ylo_ok), (yhi, yhi_ok)):
        for xi, xm in ((xlo, xlo_ok), (xhi, xhi_ok)):
            o = jnp.take(jnp.take(out, yi, axis=2), xi, axis=3)
            gv = jnp.take(jnp.take(gn, yi, axis=2), xi, axis=3)
            m = (ym[:, None] & xm[None, :])
            gin = gin + jnp.where((data == o) & m, gv,
                                  jnp.zeros((), data.dtype))
    return (gin,)


# kernel/stride/padding are static python values (nondiff)
_maxpool2d_nchw = jax.custom_vjp(_maxpool2d_fwd, nondiff_argnums=(1, 2, 3))


def _maxpool2d_res_fwd(data, kernel, stride, padding):
    out = _maxpool2d_fwd(data, kernel, stride, padding)
    return out, (data, out)


_maxpool2d_nchw.defvjp(_maxpool2d_res_fwd, _maxpool2d_nchw_bwd)


@register("Pooling", aliases=("pooling",))
def Pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid", cudnn_off=False,
            p_value=2, count_include_pad=True, layout=None):
    """reference `src/operator/nn/pooling-inl.h` — lax.reduce_window."""
    nd = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(data, axis=axes, keepdims=True)
        if pool_type == "lp":
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value),
                                     axis=axes, keepdims=True), 1.0 / p_value)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _pair(kernel, nd)
    stride = _pair(stride, nd)
    pad = _pair(pad, nd) if pad else (0,) * nd
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad high edge enough for a final partial window
        padding = [(0, 0), (0, 0)]
        for i in range(nd):
            size = data.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
            padding.append((pad[i], pad[i] + extra))
    else:
        padding = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pool_type == "max":
        spad = padding[2:]
        if (_POOL_EQBWD and nd == 2
                and jnp.issubdtype(data.dtype, jnp.floating)
                and all(k <= 2 * s for k, s in zip(kernel, stride))
                and all(p[0] == p[1] for p in spad)):
            # Equality-spread backward (see _maxpool2d_nchw above). OFF by
            # default: measured on-chip (round 5), the gather-based spread
            # lowered to materialized layout copies and LOST ~25% end-to-end
            # vs XLA's select-and-scatter; kept behind MXTPU_MAXPOOL_EQBWD=1
            # for future reruns against newer XLA gather fusion.
            return _maxpool2d_nchw(data, kernel, stride, list(spad))
        init = (-jnp.inf if jnp.issubdtype(data.dtype, jnp.floating)
                else jnp.asarray(jnp.iinfo(data.dtype).min, data.dtype))
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum", "lp"):
        x = jnp.power(jnp.abs(data), p_value) if pool_type == "lp" else data
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if pool_type == "lp":
            return jnp.power(s, 1.0 / p_value)
        if count_include_pad:
            return s / _np.prod(kernel)
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    raise ValueError("unknown pool_type %s" % pool_type)


# AdaptiveAvgPooling2D / BilinearResize2D live in detection_ops.py
# (exact integral-image windows + mode='like' support).


# ------------------------------------------------------------ activations


@register("Activation", aliases=("activation",))
def Activation(data, act_type="relu"):
    """reference `src/operator/nn/activation-inl.h`."""
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %s" % act_type)


@register("relu")
def relu(data):
    return jax.nn.relu(data)


@register("sigmoid")
def sigmoid(data):
    return jax.nn.sigmoid(data)


@register("hard_sigmoid")
def hard_sigmoid(data, alpha=0.2, beta=0.5):
    return jnp.clip(alpha * data + beta, 0.0, 1.0)


@register("softsign")
def softsign(data):
    return jax.nn.soft_sign(data)


@register("softrelu")
def softrelu(data):
    return jax.nn.softplus(data)


@register("gelu", aliases=("LeakyReLU_gelu", "_contrib_gelu"))
def gelu(data):
    return jax.nn.gelu(data, approximate=False)


@register("LeakyReLU",
          state_binders={"key": _bind_key, "train": _bind_train})
def LeakyReLU(data, gamma=None, act_type="leaky", slope=0.25,
              lower_bound=0.125, upper_bound=0.334, key=None, train=False):
    """reference `src/operator/leaky_relu-inl.h` — leaky/prelu/elu/selu/gelu/
    rrelu variants."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim == 1 and data.ndim > 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data > 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        if train:
            u = jax.random.uniform(key, data.shape, data.dtype,
                                   lower_bound, upper_bound)
            return jnp.where(data > 0, data, u * data)
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, mid * data)
    raise ValueError("unknown act_type %s" % act_type)


# ------------------------------------------------------------ softmax family


@register("softmax")
def softmax(data, axis=-1, length=None, temperature=None, dtype=None,
            use_length=False):
    """reference `src/operator/nn/softmax-inl.h`."""
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if use_length and length is not None:
        steps = jnp.arange(x.shape[axis])
        shp = [1] * x.ndim
        shp[axis] = x.shape[axis]
        mask = steps.reshape(shp) < jnp.expand_dims(length, axis=axis)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(dtype_np(dtype)) if dtype else out


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False,
                length=None):
    x = data if temperature in (None, 1.0) else data / temperature
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(dtype_np(dtype)) if dtype else out


@register("softmin")
def softmin(data, axis=-1, temperature=None, dtype=None):
    return softmax.fn(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("SoftmaxActivation")
def SoftmaxActivation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("SoftmaxOutput", aliases=("softmax_output", "Softmax"))
def SoftmaxOutput(data, label, grad_scale=1.0, ignore_label=-1.0,
                  multi_output=False, use_ignore=False, preserve_shape=False,
                  normalization="null", out_grad=False, smooth_alpha=0.0):
    """reference `src/operator/softmax_output-inl.h` — forward is softmax;
    the custom gradient (softmax-minus-onehot) is wired via custom_vjp so
    `backward` reproduces MXNet's loss-layer semantics."""
    return _softmax_output(data, label, grad_scale, ignore_label,
                           float(use_ignore), float(multi_output))


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                    multi_output):
    axis = 1 if multi_output else -1
    return jax.nn.softmax(data, axis=axis)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output):
    axis = 1 if multi_output else -1
    out = jax.nn.softmax(data, axis=axis)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                        res, g):
    out, label = res
    axis = 1 if multi_output else -1
    depth = out.shape[axis]
    oh = jax.nn.one_hot(label.astype(jnp.int32), depth, axis=axis,
                        dtype=out.dtype)
    grad = (out - oh) * grad_scale
    if use_ignore:
        keep = (label != ignore_label).astype(out.dtype)
        keep = jnp.expand_dims(keep, axis=axis)
        grad = grad * keep
    # match batch mean semantics of MXNet: grad already per-example
    return (grad, jnp.zeros_like(label, dtype=out.dtype))


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    # label < 0 = ignore (native RecordIO emits -1 for corrupt records)
    logp = jax.nn.log_softmax(data, axis=-1)
    idx = label.astype(jnp.int32)
    nll = -jnp.take_along_axis(logp, jnp.maximum(idx, 0)[:, None], axis=-1)
    nll = jnp.where(idx[:, None] >= 0, nll, 0.0)
    return jnp.sum(nll)


# ------------------------------------------------------------ normalization


@register("BatchNorm", aliases=("batch_norm", "BatchNorm_v1"),
          state_binders={"train": _bind_train})
def BatchNorm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
              momentum=0.9, fix_gamma=True, use_global_stats=False,
              output_mean_var=False, axis=1, cudnn_off=False,
              min_calib_range=None, max_calib_range=None, train=False):
    """reference `src/operator/nn/batch_norm-inl.h`. Note: running-stat
    *updates* are handled functionally by the Gluon layer (gluon/nn/basic_layers
    BatchNorm) — this op is the pure compute. The train flag is bound at
    invoke time so backward replay keeps batch-stat mode."""
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if use_global_stats or not train:
        mean, var = moving_mean, moving_var
    else:
        # one-pass stats (E[x^2] - E[x]^2, accumulated in fp32): both
        # reductions fuse into a single sweep over the activations, unlike
        # jnp.var which re-reads data after computing the mean. Same
        # formulation and precision as cuDNN/TF fused batch norm (the
        # reference's backend); fp32 accumulation bounds the cancellation
        # error at ~mean^2 * 2^-24, which the max(.., 0) clamp backstops.
        if _BN_BARRIER:
            # Keep the stat reductions OUT of the producing conv's fusion:
            # measured on-chip (round 5, scan probes at ResNet stage-2/3
            # shapes), a conv with BN-stat epilogue fused runs at 74-80
            # TFLOP/s vs 86-96 with this barrier (+17-20%). Forward-only
            # (identity gradient): a plain optimization_barrier transposes
            # to a cotangent barrier that measurably breaks backward
            # fusions (2495 vs 2772 img/s end-to-end ResNet-50).
            data = _fwd_barrier(data)
        xf = data.astype(jnp.float32)
        mean = jnp.mean(xf, axis=reduce_axes)
        var = jnp.maximum(
            jnp.mean(jnp.square(xf), axis=reduce_axes) - jnp.square(mean),
            0.0)
        mean = mean.astype(moving_mean.dtype)
        var = var.astype(moving_var.dtype)
    inv = lax.rsqrt(var + eps).astype(data.dtype)
    out = (data - mean.reshape(bshape).astype(data.dtype)) * inv.reshape(bshape) \
        * g.reshape(bshape).astype(data.dtype) + beta.reshape(bshape).astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("LayerNorm", aliases=("layer_norm",))
def LayerNorm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """reference `src/operator/nn/layer_norm-inl.h`."""
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    ax = axis if axis >= 0 else data.ndim + axis
    bshape[ax] = data.shape[ax]
    out = (data - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


@register("InstanceNorm")
def InstanceNorm(data, gamma, beta, eps=1e-3):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) \
        + beta.reshape(bshape)


@register("GroupNorm")
def GroupNorm(data, gamma, beta, num_groups=1, eps=1e-5):
    b, c = data.shape[:2]
    rest = data.shape[2:]
    x = data.reshape((b, num_groups, c // num_groups) + rest)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def L2Normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
    nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / nrm


@register("LRN")
def LRN(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = jnp.zeros_like(data)
    for i in range(nsize):
        acc = acc + padded[:, i:i + data.shape[1]]
    return data / jnp.power(knorm + alpha / nsize * acc, beta)


# ------------------------------------------------------------ dropout & rng


def _hash_keep_mask(key, shape, keep_prob):
    """Counter-hash keep mask: lowbias32 over the element's linear index
    mixed with the key — the same PRNG the Pallas flash kernel uses for
    in-kernel dropout (`pallas_kernels._keep_bits`). Deterministic in
    (key, shape), platform-independent, and ~10x cheaper on the VPU than
    threefry: the round-5 XPlane study measured threefry mask generation
    at 21% of a BERT-base s128 training step (5 loop fusions of ~3 ms/step
    emitting pred[64,128,768] masks)."""
    kd = key
    if jnp.issubdtype(kd.dtype, jax.dtypes.prng_key):
        kd = jax.random.key_data(kd)
    kd = kd.reshape(-1).astype(jnp.uint32)
    s0, s1 = kd[0], kd[-1]
    U = jnp.uint32
    idx = jnp.zeros(shape, U)
    stride = 1
    for ax in range(len(shape) - 1, -1, -1):
        idx = idx + lax.broadcasted_iota(U, tuple(shape), ax) * U(stride)
        stride *= shape[ax]
    from .pallas_kernels import _lowbias32
    c = idx * U(0x9E3779B9) ^ s0 * U(0x85EBCA6B) ^ s1 * U(0xC2B2AE35)
    c = _lowbias32(c)
    thresh = U(min(int(keep_prob * 4294967296.0), 4294967295))
    return c < thresh


@register("Dropout", aliases=("dropout",),
          state_binders={"key": _bind_key, "train": _bind_train})
def Dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False,
            key=None, train=False):
    """reference `src/operator/nn/dropout-inl.h`. The RNG key and train flag
    are bound at invoke time (state_binders) so tape replay is deterministic;
    under jit the key is a tracer split from the per-call base key."""
    if (not train and mode != "always") or p <= 0.0:
        return data
    shape = list(data.shape)
    for ax in (axes or ()):
        shape[ax] = 1
    if _DROPOUT_THREEFRY:
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
    else:
        keep = _hash_keep_mask(key, tuple(shape), 1.0 - p)
    return jnp.where(keep, data / (1.0 - p), jnp.zeros((), dtype=data.dtype))


# samplers as ops (MXNet `_random_*` / `_sample_*` namespaces,
# reference src/operator/random/sample_op.cc)
@register("_random_uniform", differentiable=False)
def _random_uniform(low=0.0, high=1.0, shape=(), dtype="float32", ctx=None):
    from .. import random as _rnd
    return jax.random.uniform(_rnd.next_key(), tuple(shape),
                              dtype_np(dtype), low, high)


@register("_random_normal", differentiable=False)
def _random_normal(loc=0.0, scale=1.0, shape=(), dtype="float32", ctx=None):
    from .. import random as _rnd
    return loc + scale * jax.random.normal(_rnd.next_key(), tuple(shape),
                                           dtype_np(dtype))


@register("_sample_uniform", differentiable=False)
def _sample_uniform(low, high, shape=(), dtype="float32"):
    from .. import random as _rnd
    s = tuple(low.shape) + tuple(shape)
    u = jax.random.uniform(_rnd.next_key(), s, dtype_np(dtype))
    bshape = low.shape + (1,) * len(tuple(shape))
    return low.reshape(bshape) + u * (high - low).reshape(bshape)


@register("_sample_normal", differentiable=False)
def _sample_normal(mu, sigma, shape=(), dtype="float32"):
    from .. import random as _rnd
    s = tuple(mu.shape) + tuple(shape)
    n = jax.random.normal(_rnd.next_key(), s, dtype_np(dtype))
    bshape = mu.shape + (1,) * len(tuple(shape))
    return mu.reshape(bshape) + n * sigma.reshape(bshape)


# ------------------------------------------------------------ embedding-ish


@register("batch_take")
def batch_take(a, indices):
    return jnp.take_along_axis(
        a, indices.astype(jnp.int32).reshape(-1, 1), axis=1).reshape(-1)


@register("UpSampling")
def UpSampling(*data, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=None):
    x = data[0]
    b, c, h, w = x.shape
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    else:
        out = jax.image.resize(x, (b, c, h * scale, w * scale), "linear")
    return out


# ------------------------------------------------------------ attention


def _flash_enabled():
    """Single gate for the pallas flash-attention dispatch: the
    registered ``MXNET_FLASH_ATTENTION`` knob (0 disables — the
    with/without benchmark switch) plus the legacy ``MXTPU_DISABLE_FLASH``
    escape hatch."""
    import os
    if os.environ.get("MXTPU_DISABLE_FLASH"):
        return False
    from .. import config as _config
    return bool(_config.get("MXNET_FLASH_ATTENTION"))


def _reduce_key_mask(mask, batch, key_len):
    """Reduce a BERT-style broadcastable keep-mask to (B, S_k) for the
    flash kernels. Returns (kv_mask, ok): ok=False means the mask shape
    is unsupported by the fused path (full (B,H,Q,K) masks etc.)."""
    if mask is None:
        return None, True
    nd = getattr(mask, "ndim", 0)
    if nd == 4 and mask.shape[1] == 1 and mask.shape[2] == 1 and \
            mask.shape[0] == batch and mask.shape[3] == key_len:
        return mask[:, 0, 0, :], True
    if nd == 2 and mask.shape == (batch, key_len):
        return mask, True
    return None, False


def _shard_flash(call, operands, num_heads, heads_dim, mesh, batch_axes,
                 kv_mask, seed):
    """``call(operands, kv_mask, seed)`` under ``jax.shard_map`` over
    ``mesh``: GSPMD cannot partition a Mosaic kernel, so each device runs
    it on its own shard — the batch dim split over ``batch_axes``, the
    heads dim (``heads_dim``; ``None`` = the operands have none to split,
    the packed projection) over ``tp``, each only where the dim divides
    (what does not divide is computed replicated). Every shard folds its
    global batch/head offset into the dropout seed operand, so the
    keep-mask is the unsharded call's."""
    from jax.sharding import PartitionSpec as P
    B, H = operands[0].shape[0], num_heads
    b_axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    n_b = _math.prod(mesh.shape[a] for a in b_axes)
    if not b_axes or B % n_b:
        b_axes, n_b = None, 1
    tp = mesh.shape.get("tp", 1)
    h_axis, n_h = ("tp", tp) if heads_dim is not None and tp > 1 \
        and H % tp == 0 else (None, 1)
    spec = [b_axes] + [None] * (operands[0].ndim - 1)
    if heads_dim is not None:
        spec[heads_dim] = h_axis
    spec = P(*spec)

    def shard(kv_mask, seed, *operands):
        if seed is not None:
            b_off = lax.axis_index(b_axes) * (B // n_b) if b_axes else 0
            h_off = lax.axis_index(h_axis) * (H // n_h) if h_axis else 0
            seed = jnp.stack([seed, jnp.int32(b_off * H + h_off),
                              jnp.int32(H)])
        return call(operands, kv_mask, seed)

    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(P(b_axes, None), P()) + (spec,) * len(operands),
        out_specs=spec, check_vma=False)(kv_mask, seed, *operands)


def _sharded_flash(kernel, heads_dim, mesh, batch_axes, query, key, value,
                   kv_mask, seed, causal, drop, interpret=False):
    """A q/k/v ``kernel`` on its shard of ``mesh`` (:func:`_shard_flash`)."""
    return _shard_flash(
        lambda qkv, m, s: kernel(*qkv, m, s, causal, drop, interpret),
        (query, key, value), query.shape[heads_dim], heads_dim, mesh,
        batch_axes, kv_mask, seed)


def _flash_seed(drop, rng_key):
    if drop > 0.0:
        return jax.random.randint(rng_key, (), -2**31, 2**31 - 1,
                                  dtype=jnp.int32)
    return None


def _flash_call(kernel, heads_dim, query, key, value, kv_mask, rng_key,
                causal, drop):
    """Run a flash kernel on the devices the enclosing program spans:
    the bare call on one device, :func:`_sharded_flash` when the trainer
    or serving lane tracing this op made a larger mesh visible
    (``parallel.mesh.mesh_scope``)."""
    from ..parallel.mesh import current_scope
    seed = _flash_seed(drop, rng_key)
    scope = current_scope()
    if scope is None or scope[0].size == 1:
        return kernel(query, key, value, kv_mask, seed, causal, drop)
    return _sharded_flash(kernel, heads_dim, scope[0], scope[1], query,
                          key, value, kv_mask, seed, causal, drop)


def _packed_flash_call(qkv, num_heads, kv_mask, rng_key, causal, drop):
    """:func:`_flash_call` for the packed projection: the batch over the
    visible mesh's batch axes, nothing over ``tp`` (the caller sends a
    ``tp`` > 1 mesh down the split path, whose heads dim can shard)."""
    from ..parallel.mesh import current_scope
    from .pallas_kernels import flash_attention_packed
    seed = _flash_seed(drop, rng_key)
    scope = current_scope()
    if scope is None or scope[0].size == 1:
        return flash_attention_packed(qkv, num_heads, kv_mask, seed, causal,
                                      drop)
    return _shard_flash(
        lambda ops, m, s: flash_attention_packed(ops[0], num_heads, m, s,
                                                 causal, drop),
        (qkv,), num_heads, None, scope[0], scope[1], kv_mask, seed)


# Which implementation each attention call was traced into, counted where
# the dispatcher decides (once a trace, not once a step): "packed" = the
# flash kernels on the unsplit QKV projection, "flash" = the flash kernels
# on separate q/k/v, "latent" = the latent-attention flash kernels (score
# of two dot products, keys wider than values), "xla" = the composed
# softmax.
_DISPATCHED = {"packed": 0, "flash": 0, "latent": 0, "xla": 0}


def attention_dispatch_stats():
    """Snapshot of the dispatcher's path counts since the process
    started: ``{"packed", "flash", "latent", "xla"}``."""
    return dict(_DISPATCHED)


def _on_accelerator():
    return any(d.platform != "cpu" for d in jax.devices())


@register("_contrib_dot_product_attention",
          state_binders={"rng_key": _bind_key, "train": _bind_train})
def dot_product_attention(query, key, value, mask=None, dropout=0.0,
                          scaled=True, causal=False, layout="BHSD",
                          rng_key=None, train=False):
    """TPU-native fused attention entry. Not in MXNet 1.6 (attention was
    composed from ops there) — exposed as a contrib op. When the problem
    aligns to the pallas tiling (seq % 128 == 0) and a TPU is present,
    lowers to the flash-attention pallas kernel (ops/pallas_kernels.py) —
    including BERT's padding keep-mask ((B,1,1,T) or (B,T), reduced to a
    per-key mask) and train-time attention dropout (in-kernel counter RNG,
    fwd/bwd consistent). Full (B,H,Q,K) masks and cross-attention take the
    XLA softmax path. Whatever implements it, every op it traces (mask
    reduction, kernels, transposes, the kernels' backward rules) carries
    the ``attention`` scope in its metadata."""
    with jax.named_scope("attention"):
        return _attention(query, key, value, mask, dropout, scaled, causal,
                          layout, rng_key, train)


@register("_contrib_packed_self_attention",
          state_binders={"rng_key": _bind_key, "train": _bind_train})
def packed_self_attention(qkv, mask=None, num_heads=1, dropout=0.0,
                          scaled=True, causal=False, rng_key=None,
                          train=False):
    """Self-attention straight from the packed QKV projection: ``qkv`` is
    (B, S, 3*H*D) as the Dense produced it ([q | k | v] along the last
    axis), the result (B, S, H*D) as the output projection reads it; mask,
    dropout and causal as :func:`dot_product_attention`. Where the
    head-fused flash kernels run (accelerator present, S a multiple of 128,
    H*D of 128, mask reducible to (B, S), no ``tp`` > 1 in the visible
    mesh) they read q, k and v as column blocks of the one array and the
    backward returns one packed gradient: no split, no 4-D view, no
    relayout copy around the kernels. Everywhere else the projection is
    split into (B, S, H, D) views and attended as
    ``dot_product_attention(..., layout="BSHD")`` does, bit for bit."""
    with jax.named_scope("attention"):
        B, S, C3 = qkv.shape
        H, D = int(num_heads), C3 // (3 * int(num_heads))
        kv_mask, mask_ok = _reduce_key_mask(mask, B, S)
        if mask_ok and _packed_flash_usable((B, S, H, D), dropout, scaled,
                                            rng_key, train):
            _DISPATCHED["packed"] += 1
            return _packed_flash_call(qkv, H, kv_mask, rng_key, causal,
                                      float(dropout) if train else 0.0)
        split = qkv.reshape(B, S, 3, H, D)
        out = _attention(split[:, :, 0], split[:, :, 1], split[:, :, 2],
                         mask, dropout, scaled, causal, "BSHD", rng_key,
                         train)
        return out.reshape(B, S, H * D)


def _bshd_flash_usable(q_shape, dropout, scaled, rng_key, train):
    """Whether the head-fused flash kernels take a self-attention whose q,
    k and v are each ``q_shape`` (B, S, H, D) and whose mask, if any,
    reduces to (B, S): decided from shapes, the platform and the knob
    alone."""
    from .pallas_kernels import flash_attention_bshd_usable
    drop = float(dropout) if train else 0.0
    return (scaled and (drop == 0.0 or rng_key is not None)
            and flash_attention_bshd_usable(q_shape, q_shape[-1])
            and _flash_enabled() and _on_accelerator())


def _packed_flash_usable(q_shape, dropout, scaled, rng_key, train):
    """The packed form runs wherever the separate-operand kernels would,
    except under tensor parallelism: there the heads dim shards over
    ``tp``, which one (B, S, 3*H*D) operand cannot express."""
    from ..parallel.mesh import current_scope
    scope = current_scope()
    if scope is not None and scope[0].shape.get("tp", 1) > 1:
        return False
    return _bshd_flash_usable(q_shape, dropout, scaled, rng_key, train)


def _attention(query, key, value, mask, dropout, scaled, causal, layout,
               rng_key, train):
    if layout == "BSHD" and getattr(query, "ndim", 0) == 4:
        # (B, S, H, D) views of the qkv projection: the head-fused kernels
        # read them as (B, S, H*D) with no head transpose (the BHSD kernels
        # force one on each side); the 4-D views themselves still cost a
        # relayout each way on the chip, which the packed entry spares
        kv_mask, mask_ok = _reduce_key_mask(mask, query.shape[0],
                                            key.shape[1])
        if (mask_ok and key.shape == query.shape
                and value.shape == query.shape
                and _bshd_flash_usable(query.shape, dropout, scaled,
                                       rng_key, train)):
            from .pallas_kernels import flash_attention_bshd
            _DISPATCHED["flash"] += 1
            return _flash_call(flash_attention_bshd, 2, query, key, value,
                               kv_mask, rng_key, causal,
                               float(dropout) if train else 0.0)
        # fallback: run the BHSD path and restore the layout; XLA fuses
        # these transposes into the surrounding einsums
        out = _attention(
            jnp.transpose(query, (0, 2, 1, 3)),
            jnp.transpose(key, (0, 2, 1, 3)),
            jnp.transpose(value, (0, 2, 1, 3)),
            mask, dropout, scaled, causal, "BHSD", rng_key, train)
        return jnp.transpose(out, (0, 2, 1, 3))

    if query.ndim == 4 and scaled and _flash_enabled():
        from .pallas_kernels import flash_attention, flash_attention_usable
        # BERT-style key padding masks broadcast over q: reducible to (B,S)
        kv_mask, mask_ok = _reduce_key_mask(mask, query.shape[0],
                                            key.shape[2])
        drop = float(dropout) if train else 0.0
        # kernel tiles assume self-attention layout; cross-attention with
        # kv_len != q_len must take the XLA path
        if (mask_ok and key.shape == query.shape
                and value.shape == query.shape
                and (drop == 0.0 or rng_key is not None)
                and flash_attention_usable(query.shape, causal)
                and _on_accelerator()):
            _DISPATCHED["flash"] += 1
            return _flash_call(flash_attention, 1, query, key, value,
                               kv_mask, rng_key, causal, drop)
    _DISPATCHED["xla"] += 1
    d = query.shape[-1]
    scores = jnp.einsum("...qd,...kd->...qk", query, key)
    if scaled:
        scores = scores / _np.sqrt(d).astype(scores.dtype)
    if causal:
        q, k = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((q, k), dtype=bool))
        scores = jnp.where(cm, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        m = mask
        if getattr(m, "ndim", 0) == 2 and scores.ndim == 4 and \
                m.shape == (scores.shape[0], scores.shape[-1]):
            m = m[:, None, None, :]  # (B,T) key mask -> broadcast form
        scores = jnp.where(m.astype(bool), scores, jnp.finfo(scores.dtype).min)
    w = jax.nn.softmax(scores, axis=-1)
    if dropout > 0.0 and train:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout), 0.0)
    return jnp.einsum("...qk,...kd->...qd", w, value)


# ------------------------------------------------- latent attention (MLA)


@register("RMSNorm", aliases=("rms_norm",))
def RMSNorm(data, gamma, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, computed in
    float32 whatever the storage type (Zhang & Sennrich 2019)."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


def _rotary_tables(seq, dim, theta):
    """cos and sin of position x frequency, (seq, dim / 2) float32, worked
    out in float64 on the host (the length is static): frequency i is
    ``theta ** (-2 i / dim)``."""
    inv = 1.0 / (float(theta) ** (_np.arange(0, dim, 2, dtype=_np.float64)
                                  / dim))
    angle = _np.arange(seq, dtype=_np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(_np.cos(angle), jnp.float32),
            jnp.asarray(_np.sin(angle), jnp.float32))


@register("_contrib_rotary_embedding")
def rotary_embedding(data, theta=10000.0):
    """Rotary position embedding (Su et al. 2021) of ``data (B, S, ..., D)``
    by the position along axis 1, pairing element i with element i + D/2
    (the "halves" convention), in float32."""
    seq, dim = data.shape[1], data.shape[-1]
    cos, sin = _rotary_tables(seq, dim, theta)
    shape = (1, seq) + (1,) * (data.ndim - 3) + (dim // 2,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x = data.astype(jnp.float32)
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(data.dtype)


def _latent_flash_call(q_nope, q_rope, kv, k_rope, num_heads, causal):
    """The latent flash kernels on the devices the enclosing program spans:
    the bare call on one device, each device's share of the batch under
    ``shard_map`` where a larger mesh is visible."""
    from jax.sharding import PartitionSpec as P
    from ..parallel.mesh import current_scope
    from .pallas_kernels import flash_attention_latent

    def call(*operands):
        return flash_attention_latent(*operands, num_heads, causal)

    scope = current_scope()
    if scope is None or scope[0].size == 1:
        return call(q_nope, q_rope, kv, k_rope)
    mesh, batch_axes = scope
    axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    if not axes or q_nope.shape[0] % _math.prod(mesh.shape[a] for a in axes):
        axes = None
    return jax.shard_map(call, mesh=mesh, in_specs=(P(axes),) * 4,
                         out_specs=P(axes), check_vma=False)(
        q_nope, q_rope, kv, k_rope)


@register("_contrib_latent_attention")
def latent_attention(q_nope, q_rope, kv, k_rope, num_heads=1, causal=True):
    """Multi-head latent attention in its training form (DeepSeek-V2): the
    score of head h is ``(q_nope_h . k_nope_h + q_rope_h . k_rope) /
    sqrt(nope + rope)`` with ONE rotary key a position shared by the heads,
    and the keys (nope + rope wide) are wider than the values.

    ``q_nope (B, S, H*nope)``; ``q_rope (B, S, H, rope)`` and ``k_rope
    (B, S, rope)``, both already rotated; ``kv (B, S, H*(nope + v))`` as the
    up-projection of the latent made it, ``[k_nope_h | v_h]`` head by head.
    Returns ``(B, S, H*v)`` as the output projection reads it. Where the
    latent flash kernels run (accelerator present, S a multiple of 128,
    nope == v a multiple of 128) nothing is padded, split or broadcast in
    memory; everywhere else the plain XLA form computes the same. Either way
    every op carries the ``attention`` scope."""
    with jax.named_scope("attention"):
        B, S, _ = q_nope.shape
        H = int(num_heads)
        dn, dr = q_nope.shape[-1] // H, q_rope.shape[-1]
        dv = kv.shape[-1] // H - dn
        from .pallas_kernels import flash_attention_latent_usable
        if (flash_attention_latent_usable(S, dn, dr, dv) and _flash_enabled()
                and _on_accelerator()):
            _DISPATCHED["latent"] += 1
            return _latent_flash_call(q_nope, q_rope, kv, k_rope, H, causal)
        _DISPATCHED["xla"] += 1
        kv4 = kv.reshape(B, S, H, dn + dv)
        q = jnp.concatenate([q_nope.reshape(B, S, H, dn), q_rope], axis=-1)
        k = jnp.concatenate(
            [kv4[..., :dn],
             jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], axis=-1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / _np.float32(_np.sqrt(dn + dr))
        if causal:
            scores = jnp.where(jnp.tril(jnp.ones((S, S), dtype=bool)), scores,
                               -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(kv.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, kv4[..., dn:])
        return out.reshape(B, S, H * dv)


@register("_contrib_held_experts_ffn", n_out=2)
def held_experts_ffn_op(data, router_weight, router_bias, gate_weight,
                        up_weight, down_weight, first=0, top_k=1, scale=1.0,
                        normalize=True):
    """The routed part of a dropless expert layer over the experts held
    here (``parallel.moe.held_experts_ffn``) for ``data (..., d)``:
    ``(result, rows routed to each held expert)``."""
    from ..parallel.moe import held_experts_ffn
    lead = data.shape[:-1]
    y, rows = held_experts_ffn(
        data.reshape(-1, data.shape[-1]), router_weight, router_bias,
        gate_weight, up_weight, down_weight, first=int(first),
        top_k=int(top_k), scale=float(scale), normalize=bool(normalize))
    return y.reshape(lead + (data.shape[-1],)), rows


@register("_contrib_gated_ffn")
def gated_ffn_op(data, gate_weight, up_weight, down_weight, scope=None):
    """``W_down(silu(W_gate x) * W_up x)``, matrices stored (out, in);
    ``scope`` names a program scope to enter (``moe_shared``)."""
    import contextlib
    from ..parallel.moe import gated_ffn
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return gated_ffn(data, gate_weight, up_weight, down_weight)


# --------------------------------------------- chunked cross-entropy head

def _ce_chunks(tokens, chunk):
    chunk = min(int(chunk), tokens)
    return chunk if tokens % chunk == 0 else tokens


@_partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_ce(hidden, weight, labels, chunk):
    return _chunked_ce_fwd(hidden, weight, labels, chunk)[0]


def _chunk_logits(h, weight):
    return lax.dot_general(h, weight, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _chunked_ce_fwd(hidden, weight, labels, chunk):
    T = hidden.shape[0]
    valid = labels >= 0
    count = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)

    def one(_, args):
        h, lab = args
        logits = _chunk_logits(h, weight)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[:, None], axis=-1)[:, 0]
        return None, (lse, jnp.where(lab >= 0, lse - picked, 0.0))

    _, (lse, nll) = lax.scan(one, None, (
        hidden.reshape(T // chunk, chunk, -1), labels.reshape(-1, chunk)))
    return jnp.sum(nll) / count, (hidden, weight, labels, lse.reshape(T),
                                  count)


def _chunked_ce_bwd(chunk, res, g):
    hidden, weight, labels, lse, count = res
    T = hidden.shape[0]
    ids = jnp.arange(weight.shape[0], dtype=labels.dtype)

    def one(dw, args):
        h, lab, l = args
        probs = jnp.exp(_chunk_logits(h, weight) - l[:, None])
        d = jnp.where((lab >= 0)[:, None],
                      probs - (ids[None, :] == lab[:, None]), 0.0)
        d = (d * (g / count)).astype(hidden.dtype)
        dw = dw + lax.dot_general(d, h, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, jnp.matmul(d, weight)

    dw, dh = lax.scan(one, jnp.zeros(weight.shape, jnp.float32), (
        hidden.reshape(T // chunk, chunk, -1), labels.reshape(-1, chunk),
        lse.reshape(-1, chunk)))
    return dh.reshape(hidden.shape), dw.astype(weight.dtype), None


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


@register("_contrib_chunked_softmax_cross_entropy")
def chunked_softmax_cross_entropy(hidden, weight, labels, chunk=2048):
    """Mean cross-entropy of ``hidden (..., d) @ weight (V, d).T`` against
    ``labels (...)`` over the positions whose label is not negative, a
    ``chunk`` of positions at a time: no (positions, V) array is live whole,
    forward or backward (the backward pass recomputes each chunk's logits
    from the saved log-sum-exp and adds the head's gradient up in
    float32)."""
    flat = hidden.reshape(-1, hidden.shape[-1])
    lab = labels.reshape(-1).astype(jnp.int32)
    return _chunked_ce(flat, weight, lab, _ce_chunks(flat.shape[0], chunk))
