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
from functools import partial as _partial

import numpy as _np
import jax
import jax.numpy as jnp
from jax import lax

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
#
# Three entries (separate q, k, v; the packed QKV projection; latent
# attention), ONE decision (`_attention_path`), one way to run a Mosaic
# kernel on the visible mesh (`_flash` over `_shard_flash`), and the XLA
# forms every kernel is tested against.

# Which implementation each attention call was traced into, counted where
# the dispatcher decides (once a trace, not once a step): "packed" = the
# flash kernels on the unsplit QKV projection, "flash" = the flash kernels
# on separate q/k/v (head-fused or per-head), "latent" = the
# latent-attention flash kernels (score of two dot products, keys wider
# than values), "eva" = the window-plus-summaries flash kernels, "grouped" =
# the grouped-query flash kernels over streamed key blocks, "xla" = the
# composed softmax.
_DISPATCHED = {"packed": 0, "flash": 0, "latent": 0, "eva": 0, "grouped": 0,
               "xla": 0}


def attention_dispatch_stats():
    """Snapshot of the dispatcher's path counts since the process
    started: ``{"packed", "flash", "latent", "eva", "grouped", "xla"}``."""
    return dict(_DISPATCHED)


# Where ``ssm_scan`` sent each scan it traced (once a trace): "kernel" = the
# chunked-scan kernels of ``ops/pallas_kernels.py``, "xla" =
# :func:`xla_ssm_scan`.
_SSM_SCANS = {"kernel": 0, "xla": 0}


def ssm_scan_stats():
    """Snapshot of where :func:`ssm_scan` sent the scans it traced since
    the process started: ``{"kernel", "xla"}``."""
    return dict(_SSM_SCANS)


# Where ``causal_conv1d`` sent each convolution it traced (once a trace):
# "kernel" = the convolution kernels of ``ops/pallas_kernels.py``, "xla" =
# :func:`xla_causal_conv1d`.
_SSM_CONVS = {"kernel": 0, "xla": 0}


def ssm_conv_stats():
    """Snapshot of where :func:`causal_conv1d` sent the convolutions it
    traced since the process started: ``{"kernel", "xla"}``."""
    return dict(_SSM_CONVS)


def _on_accelerator():
    return any(d.platform != "cpu" for d in jax.devices())


def _one_device_accelerator():
    """An accelerator and no visible mesh of several devices: where a
    kernel that has no ``shard_map`` wrapper (the scan's, the convolution's)
    can run."""
    from ..parallel.mesh import current_scope
    scope = current_scope()
    return _on_accelerator() and (scope is None or scope[0].size == 1)


def _attention_path(form, shape, kv_shapes=(), mask_shape=None, scaled=True,
                    drop=0.0, keyed=True):
    """Name the implementation an attention call runs, from what can be
    observed of it and nothing else: ``"packed"``, ``"bshd"``, ``"bhsd"``,
    ``"latent"``, ``"eva"``, ``"grouped"`` (the flash kernels of
    ``ops/pallas_kernels.py``) or ``"xla"`` (the composed softmax).

    =========  ===============================  ==========================
    ``form``   ``shape``                        kernels tried, in order
    =========  ===============================  ==========================
    packed     the projection's (B, S, H, D)    packed, bshd, bhsd
    BSHD       q's (B, S, H, D)                 bshd, bhsd
    BHSD       q's (B, H, S, D)                 bhsd
    latent     (S, nope, rope, v)               latent
    eva        (S, D, window, chunk)            eva
    grouped    (S, D, q heads, kv heads)        grouped
    =========  ===============================  ==========================

    ``kv_shapes``: k's and v's shapes; ``mask_shape``: the keep-mask's, or
    ``None``; ``drop``: the dropout rate in force, ``keyed`` whether a key
    came with it. Every kernel needs an accelerator and S a multiple of
    128. The q/k/v kernels besides need k and v of q's shape (self-
    attention, no grouped heads), the 1/sqrt(D) scale, a mask that reduces
    to one row of keys a batch entry ((B, 1, 1, S) or (B, S)), a key
    wherever there is dropout, and D <= 256. The head-fused ``bshd``
    kernels read (S, H*D) rows: H*D must be a multiple of 128 and two whole
    operands fit 8 MiB of VMEM (else the per-head ``bhsd`` kernels, at the
    price of a transpose each way). ``packed`` is ``bshd`` on the unsplit
    projection, which cannot shard its heads: under a visible mesh with
    ``tp`` > 1 the projection is split for ``bshd``. The latent kernels
    need nope, v multiples of 128 and rope a multiple of 8 up to 128. The
    EVA kernels need D a multiple of 128, S a multiple of the window, the
    window a multiple of 128 and of the chunk, and the summaries (S / chunk)
    a multiple of 128. The grouped kernels (causal, no mask, no dropout,
    the 1/sqrt(D) scale: the op has no other form) need the query heads a
    multiple of the key-value heads and D a multiple of 8 up to 256."""
    from ..parallel.mesh import current_scope
    from . import pallas_kernels as pk
    if not _on_accelerator():
        return "xla"
    if form == "latent":
        return "latent" if pk.flash_attention_latent_usable(*shape) else "xla"
    if form == "eva":
        return "eva" if pk.flash_attention_eva_usable(*shape) else "xla"
    if form == "grouped":
        return "grouped" if pk.flash_attention_grouped_usable(*shape) \
            else "xla"
    if (len(shape) != 4 or any(tuple(s) != tuple(shape) for s in kv_shapes)
            or not scaled or (drop > 0.0 and not keyed)):
        return "xla"
    B, S, H, D = (shape[0], shape[2], shape[1], shape[3]) if form == "BHSD" \
        else shape
    if mask_shape not in (None, (B, 1, 1, S), (B, S)):
        return "xla"
    if form != "BHSD" and pk.flash_attention_bshd_usable((B, S, H, D), D):
        scope = current_scope()
        tp = scope[0].shape.get("tp", 1) if scope is not None else 1
        return "packed" if form == "packed" and tp == 1 else "bshd"
    return "bhsd" if pk.flash_attention_usable((B, H, S, D)) else "xla"


def _dispatch(form, shape, **observed):
    """:func:`_attention_path` for a call being traced, counted."""
    path = _attention_path(form, shape, **observed)
    _DISPATCHED["flash" if path in ("bshd", "bhsd") else path] += 1
    return path


def _mask_shape(mask):
    return None if mask is None else tuple(jnp.shape(mask))


def _shard_flash(call, operands, num_heads, heads_dim, mesh, batch_axes,
                 kv_mask, seed):
    """``call(operands, kv_mask, seed)`` under ``jax.shard_map`` over
    ``mesh``: GSPMD cannot partition a Mosaic kernel, so each device runs
    it on its own shard — every operand's batch dim split over
    ``batch_axes``, the heads dim (``heads_dim``; ``None`` = the operands
    have none to split: the packed projection, the latent's four) over
    ``tp``, each only where the dim divides (what does not divide is
    computed replicated). Every shard folds its global batch/head offset
    into the dropout seed operand, so the keep-mask is the unsharded
    call's."""
    from jax.sharding import PartitionSpec as P
    B, H = operands[0].shape[0], num_heads
    b_axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    n_b = _math.prod(mesh.shape[a] for a in b_axes)
    if not b_axes or B % n_b:
        b_axes, n_b = None, 1
    tp = mesh.shape.get("tp", 1)
    h_axis, n_h = ("tp", tp) if heads_dim is not None and tp > 1 \
        and H % tp == 0 else (None, 1)

    def spec(ndim):
        dims = [b_axes] + [None] * (ndim - 1)
        if heads_dim is not None:
            dims[heads_dim] = h_axis
        return P(*dims)

    specs = tuple(spec(a.ndim) for a in operands)

    def shard(kv_mask, seed, *operands):
        if seed is not None:
            b_off = lax.axis_index(b_axes) * (B // n_b) if b_axes else 0
            h_off = lax.axis_index(h_axis) * (H // n_h) if h_axis else 0
            seed = jnp.stack([seed, jnp.int32(b_off * H + h_off),
                              jnp.int32(H)])
        return call(operands, kv_mask, seed)

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(P(b_axes, None), P()) + specs,
        out_specs=specs[0], check_vma=False)(kv_mask, seed, *operands)


def _flash(path, operands, num_heads, mask, rng_key, causal, drop,
           window=None, kv_heads=None):
    """The flash kernels of ``path`` on the devices the enclosing program
    spans: the bare call on one device, :func:`_shard_flash` when the
    trainer or serving lane tracing this op made a larger mesh visible
    (``parallel.mesh.mesh_scope``). ``mask`` is in one of the two forms
    :func:`_attention_path` lets through."""
    from ..parallel.mesh import current_scope
    from . import pallas_kernels as pk
    heads_dim = {"bshd": 2, "bhsd": 1}.get(path)
    if path == "packed":
        def call(ops, m, s):
            return pk.flash_attention_packed(ops[0], num_heads, m, s, causal,
                                             drop)
    elif path == "latent":
        def call(ops, m, s):
            return pk.flash_attention_latent(*ops, num_heads, causal)
    elif path == "eva":
        def call(ops, m, s):
            return pk.flash_attention_eva(*ops, num_heads, window)
    elif path == "grouped":
        def call(ops, m, s):
            return pk.flash_attention_grouped(*ops, num_heads, kv_heads)
    else:
        kernel = pk.flash_attention_bshd if path == "bshd" \
            else pk.flash_attention

        def call(ops, m, s):
            return kernel(*ops, m, s, causal, drop)
    kv_mask = mask[:, 0, 0, :] if mask is not None and mask.ndim == 4 \
        else mask
    seed = jax.random.randint(rng_key, (), -2**31, 2**31 - 1,
                              dtype=jnp.int32) if drop > 0.0 else None
    scope = current_scope()
    if scope is None or scope[0].size == 1:
        return call(operands, kv_mask, seed)
    return _shard_flash(call, operands, num_heads, heads_dim, *scope,
                        kv_mask, seed)


def xla_attention(query, key, value, mask=None, dropout=0.0, scaled=True,
                  causal=False, rng_key=None):
    """The composed softmax over ``(..., S, D)`` operands (heads before
    positions): what runs wherever no kernel does, and the form the kernels
    are tested against. ``mask`` broadcasts against the (..., Q, K) scores,
    or is a (B, K) keep-mask of the keys."""
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
    if dropout > 0.0:
        keep = jax.random.bernoulli(rng_key, 1.0 - dropout, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout), 0.0)
    return jnp.einsum("...qk,...kd->...qd", w, value)


def _qkv_attention(path, bshd, query, key, value, mask, drop, scaled, causal,
                   rng_key):
    """Attention of separate q, k, v down ``path``. ``bshd``: the operands
    are (B, S, H, D) views of the qkv projection, which the head-fused
    kernels read as (B, S, H*D) with no head transpose; the per-head
    kernels and the composed softmax take them transposed (XLA fuses these
    transposes into the surrounding einsums)."""
    if path == "bshd":
        return _flash(path, (query, key, value), query.shape[2], mask,
                      rng_key, causal, drop)
    if bshd:
        query, key, value = (jnp.transpose(a, (0, 2, 1, 3))
                             for a in (query, key, value))
    if path == "bhsd":
        out = _flash(path, (query, key, value), query.shape[1], mask,
                     rng_key, causal, drop)
    else:
        out = xla_attention(query, key, value, mask, drop, scaled, causal,
                            rng_key)
    return jnp.transpose(out, (0, 2, 1, 3)) if bshd else out


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
    XLA softmax path (:func:`_attention_path` has the whole rule).
    Whatever implements it, every op it traces (mask reduction, kernels,
    transposes, the kernels' backward rules) carries the ``attention``
    scope in its metadata."""
    with jax.named_scope("attention"):
        drop = float(dropout) if train else 0.0
        bshd = layout == "BSHD" and getattr(query, "ndim", 0) == 4
        path = _dispatch(
            "BSHD" if bshd else "BHSD", query.shape,
            kv_shapes=(key.shape, value.shape), mask_shape=_mask_shape(mask),
            scaled=scaled, drop=drop, keyed=rng_key is not None)
        return _qkv_attention(path, bshd, query, key, value, mask, drop,
                              scaled, causal, rng_key)


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
        drop = float(dropout) if train else 0.0
        path = _dispatch("packed", (B, S, H, D), mask_shape=_mask_shape(mask),
                         scaled=scaled, drop=drop, keyed=rng_key is not None)
        if path == "packed":
            return _flash(path, (qkv,), H, mask, rng_key, causal, drop)
        split = qkv.reshape(B, S, 3, H, D)
        out = _qkv_attention(path, True, split[:, :, 0], split[:, :, 1],
                             split[:, :, 2], mask, drop, scaled, causal,
                             rng_key)
        return out.reshape(B, S, H * D)


# ------------------------------------------------- latent attention (MLA)


@register("RMSNorm", aliases=("rms_norm",))
def RMSNorm(data, gamma, eps=1e-5, unit_offset=False):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, computed in
    float32 whatever the storage type (Zhang & Sennrich 2019). With
    ``unit_offset`` the gain is ``1 + gamma``: the leaf stores its offset
    from one."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    gain = gamma.astype(jnp.float32)
    if unit_offset:
        gain = 1.0 + gain
    return (y * gain).astype(data.dtype)


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


def xla_latent_attention(q_nope, q_rope, kv, k_rope, num_heads=1,
                         causal=True):
    """:func:`latent_attention` composed from XLA ops: keys concatenated
    from their two parts, the rotary key broadcast over the heads, the
    (B, H, S, S) scores whole in float32."""
    B, S, _ = q_nope.shape
    H = int(num_heads)
    dn, dr = q_nope.shape[-1] // H, q_rope.shape[-1]
    dv = kv.shape[-1] // H - dn
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
    latent flash kernels run (accelerator present, S, nope and v multiples
    of 128) nothing is padded, split or broadcast in memory and each device
    of a visible mesh attends its share of the batch; everywhere else
    :func:`xla_latent_attention` computes the same. Either way every op
    carries the ``attention`` scope."""
    with jax.named_scope("attention"):
        H = int(num_heads)
        dn = q_nope.shape[-1] // H
        path = _dispatch("latent", (q_nope.shape[1], dn, q_rope.shape[-1],
                                    kv.shape[-1] // H - dn))
        if path == "latent":
            return _flash(path, (q_nope, q_rope, kv, k_rope), H, None, None,
                          causal, 0.0)
        return xla_latent_attention(q_nope, q_rope, kv, k_rope, H, causal)


# ------------------------------------- window + chunk summaries (EVA)


def eva_chunk_summaries(k, v, mu, phi, num_heads=1, chunk=16):
    """The learned pooling of EVA attention: for each head and each chunk c
    of ``chunk`` consecutive positions, ``kt_c = sum_m softmax_m(s mu_h .
    k_m) k_m`` and ``vt_c = sum_m softmax_m(s phi_h . k_m) v_m`` over the
    chunk's positions m, s = 1/sqrt(D). ``k`` (already rotated) and ``v`` are
    (B, S, H*D), ``mu`` and ``phi`` (H, D); returns ``(kt, vt)``, each
    (B, S / chunk, H*D). Logits and softmaxes in float32; written under the
    ``eva_pool`` scope."""
    with jax.named_scope("eva_pool"):
        B, S, HD = k.shape
        H, C = int(num_heads), int(chunk)
        D = HD // H
        kc = k.reshape(B, S // C, C, H, D)
        vc = v.reshape(B, S // C, C, H, D)
        scale = _np.float32(1.0 / _np.sqrt(D))

        def pooled(vector, what):
            # multiply-and-reduce, not dots: sixteen positions a chunk are
            # no matmul. On the chip at 1 x 32,768 x 32 x 128 (PR 31) XLA
            # takes 9.2 ms forward and 20.7 with the gradient, fourteen
            # times the reads' bound: a kernel's to win (PERF.md section 7)
            logits = scale * jnp.sum(
                kc.astype(jnp.float32) * vector.astype(jnp.float32), axis=-1)
            weights = jax.nn.softmax(logits, axis=2)
            out = jnp.sum(weights[..., None] * what.astype(jnp.float32),
                          axis=2)
            return out.astype(k.dtype).reshape(B, S // C, HD)

        return pooled(mu, kc), pooled(phi, vc)


def xla_eva_aggregate(q, k, v, kt, vt, num_heads, window):
    """The aggregation of EVA attention composed from XLA ops, a window at
    a time (no (S, S) array): the queries of window w against the positions
    of their own window at or before them and the summaries ``kt``, ``vt``
    of every chunk of an earlier window, under one float32 softmax."""
    B, S, HD = q.shape
    H, W = int(num_heads), int(window)
    D, n_sum = HD // H, kt.shape[1]
    per_window = n_sum // (S // W)
    scale = _np.float32(1.0 / _np.sqrt(D))
    kt4, vt4 = kt.reshape(B, n_sum, H, D), vt.reshape(B, n_sum, H, D)
    below = jnp.tril(jnp.ones((W, W), dtype=bool))
    earlier = jnp.arange(n_sum) // per_window

    def one(w):
        qw, kw, vw = (lax.dynamic_slice_in_dim(a, w * W, W, axis=1)
                      .reshape(B, W, H, D) for a in (q, k, v))
        exact = jnp.einsum("bqhd,bkhd->bhqk", qw, kw,
                           preferred_element_type=jnp.float32) * scale
        far = jnp.einsum("bqhd,bchd->bhqc", qw, kt4,
                         preferred_element_type=jnp.float32) * scale
        scores = jnp.concatenate(
            [jnp.where(below, exact, -1e30),
             jnp.where((earlier < w)[None, None, None, :], far, -1e30)], -1)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs[..., :W], vw) \
            + jnp.einsum("bhqc,bchd->bqhd", probs[..., W:], vt4)
        return out.reshape(B, W, HD)

    out = lax.map(one, jnp.arange(S // W))              # (S/W, B, W, HD)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, HD)


def xla_eva_attention(q, k, v, mu, phi, num_heads=1, window=2048, chunk=16):
    """:func:`eva_attention` composed from XLA ops."""
    kt, vt = eva_chunk_summaries(k, v, mu, phi, num_heads, chunk)
    return xla_eva_aggregate(q, k, v, kt, vt, num_heads, window)


@register("_contrib_eva_attention")
def eva_attention(q, k, v, mu, phi, num_heads=1, window=2048, chunk=16):
    """EVA attention (Zheng et al., "Efficient Attention via Control
    Variates", ICLR 2023, as a byte-level decoder fixes it): causal softmax
    attention in which the query at t sees the positions of ITS OWN window of
    ``window`` positions at or before t exactly, and every EARLIER window
    through the summaries of its chunks of ``chunk`` positions
    (:func:`eva_chunk_summaries`: a learned pooling of the chunk's keys and
    values by the per-head vectors ``mu`` and ``phi``), both kinds of key
    under ONE softmax of scale 1/sqrt(D). The first window sees no summary
    and is plain causal attention; with ``chunk`` 1 a summary is its
    position and the whole is plain causal attention over the sequence.

    ``q``, ``k`` (both already rotated) and ``v`` are (B, S, H*D) as the
    projections made them, ``mu`` and ``phi`` (H, D); returns (B, S, H*D) as
    the output projection reads it. Where the EVA flash kernels run
    (:func:`_attention_path`, form ``eva``) the aggregation steps through
    the live tiles only and each device of a visible mesh attends its share
    of the batch; everywhere else :func:`xla_eva_aggregate` computes the
    same. The pooling is XLA either way. Every op carries the ``attention``
    scope, the pooling ``eva_pool`` inside it."""
    with jax.named_scope("attention"):
        H, W, C = int(num_heads), int(window), int(chunk)
        path = _dispatch("eva", (q.shape[1], q.shape[-1] // H, W, C))
        kt, vt = eva_chunk_summaries(k, v, mu, phi, H, C)
        if path == "eva":
            return _flash(path, (q, k, v, kt, vt), H, None, None, True, 0.0,
                          window=W)
        return xla_eva_aggregate(q, k, v, kt, vt, H, W)


# ------------------------------------------------ grouped-query attention


def xla_grouped_attention(q, k, v, num_heads, num_kv_heads, block=512):
    """:func:`grouped_attention` composed from XLA ops, a block of queries
    at a time (no (S, S) array): query head j against key-value head
    ``j // (num_heads / num_kv_heads)``, one float32 causal softmax."""
    B, S, HD = q.shape
    H, KV = int(num_heads), int(num_kv_heads)
    D, G = HD // H, H // KV
    k4, v4 = k.reshape(B, S, KV, D), v.reshape(B, S, KV, D)
    scale = _np.float32(1.0 / _np.sqrt(D))
    block = min(int(block), S)
    if S % block:
        block = S
    key_pos = jnp.arange(S)

    def one(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=1).reshape(
            B, block, KV, G, D)
        scores = jnp.einsum("bqcgd,bkcd->bcgqk", qb, k4,
                            preferred_element_type=jnp.float32) * scale
        seen = (i * block + jnp.arange(block))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30),
                               axis=-1).astype(v.dtype)
        return jnp.einsum("bcgqk,bkcd->bqcgd", probs, v4).reshape(
            B, block, HD)

    out = lax.map(jax.checkpoint(one), jnp.arange(S // block))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, HD)


@register("_contrib_grouped_attention")
def grouped_attention(q, k, v, num_heads=1, num_kv_heads=1):
    """Causal grouped-query attention (Ainslie et al. 2023) without
    positions: ``num_heads`` query heads share ``num_kv_heads`` key-value
    heads, query head j reading key-value head ``j // (num_heads /
    num_kv_heads)``, scores times 1/sqrt(D) under one causal softmax (a model
    with another multiplier folds the ratio into q).

    ``q (B, S, H*D)``, ``k`` and ``v (B, S, KV*D)`` as the projections made
    them; returns ``(B, S, H*D)`` as the output projection reads it. Where
    the grouped flash kernels run (:func:`_attention_path`, form
    ``grouped``) the key blocks are streamed, a group's query heads are
    stacked against their one key-value head and each device of a visible
    mesh attends its share of the batch; everywhere else
    :func:`xla_grouped_attention` computes the same. Either way every op
    carries the ``attention`` scope."""
    with jax.named_scope("attention"):
        H, KV = int(num_heads), int(num_kv_heads)
        path = _dispatch("grouped", (q.shape[1], q.shape[-1] // H, H, KV))
        if path == "grouped":
            return _flash(path, (q, k, v), H, None, None, True, 0.0,
                          kv_heads=KV)
        return xla_grouped_attention(q, k, v, H, KV)


# ------------------------------- state-space mixer (Mamba-2's chunked scan)


def xla_causal_conv1d(data, weight, bias):
    """:func:`causal_conv1d` composed from XLA ops: the K shifted views of
    the padded input weighed and added up in float32, then ``silu``."""
    S, K = data.shape[1], weight.shape[1]
    padded = jnp.pad(data, ((0, 0), (K - 1, 0), (0, 0)))
    taps = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for j in range(K):
        out = out + taps[:, j] * padded[:, j:j + S].astype(jnp.float32)
    return jax.nn.silu(out).astype(data.dtype)


def _ssm_conv_path(seq, channels, taps):
    """``"kernel"`` (the convolution kernels of ``ops/pallas_kernels.py``)
    or ``"xla"`` (:func:`xla_causal_conv1d`), from shapes and platform
    alone: the kernels need an accelerator, no visible mesh of several
    devices (GSPMD cannot partition a Mosaic kernel and the convolution has
    no ``shard_map`` wrapper, as the scan has none), channels a multiple of
    128 and at most 8 taps; a sequence of any length (``seq``) is padded to
    whole position blocks."""
    from . import pallas_kernels as pk
    return "kernel" if _one_device_accelerator() and pk.causal_conv_usable(
        seq, channels, taps) else "xla"


@register("_contrib_causal_conv1d")
def causal_conv1d(data, weight, bias):
    """``silu`` of the causal depthwise convolution along axis 1 of ``data
    (B, S, C)``: ``conv[t, c] = bias[c] + sum_k weight[c, k] data[t - (K - 1)
    + k, c]`` (tap K - 1 weighs the position itself; nothing before the
    sequence), in float32 whatever the storage type. ONE decision
    (:func:`_ssm_conv_path`) sends it to the kernels (one pass forward, one
    backward, no float32 array of the sequence's size) or to
    :func:`xla_causal_conv1d`; counted once a trace in
    :func:`ssm_conv_stats`. Written under the ``ssm_conv`` scope."""
    from . import pallas_kernels as pk
    with jax.named_scope("ssm_conv"):
        path = _ssm_conv_path(data.shape[1], data.shape[2], weight.shape[1])
        _SSM_CONVS[path] += 1
        if path == "kernel":
            return pk.causal_conv1d(data, weight, bias)
        return xla_causal_conv1d(data, weight, bias)


@register("_contrib_gated_rms_norm")
def gated_rms_norm(data, gate, gamma, eps=1e-5):
    """``RMSNorm(data * silu(gate)) * gamma`` over the last axis, in
    float32 whatever the storage type."""
    x = data.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


@_partial(jax.custom_vjp, nondiff_argnums=(1,))
def _split_columns(x, sizes):
    """``x`` cut along its last axis into blocks of ``sizes`` columns. The
    gradient is the blocks' gradients side by side (one concatenation in
    x's type, where slicing's own transpose pads each to x's width and adds
    them up)."""
    edges = _np.cumsum((0,) + tuple(sizes))
    return tuple(x[..., lo:hi] for lo, hi in zip(edges[:-1], edges[1:]))


def _split_columns_fwd(x, sizes):
    return _split_columns(x, sizes), None


def _split_columns_bwd(sizes, _, grads):
    return (jnp.concatenate(grads, axis=-1),)


_split_columns.defvjp(_split_columns_fwd, _split_columns_bwd)


def _chunk_log_decay(dt, a, chunk):
    """``cumsum`` of ``dt * a`` over each chunk's positions: ``dt (B, S, H)``
    float32 -> (B, S, H), where position i of a chunk holds the log of the
    decay from the chunk's start through i."""
    B, S, H = dt.shape
    steps = (dt * a).reshape(B, S // chunk, chunk, H)
    return jnp.cumsum(steps, axis=2).reshape(B, S, H)


def xla_ssm_scan(x, dt, a, b, c, num_heads, chunk):
    """The chunked scan composed from XLA ops, a chunk at a time (no
    (heads, S / chunk, chunk, chunk) array): ``y`` of :func:`ssm_scan`
    without the ``D`` skip. A chunk's step is checkpointed, so the backward
    pass keeps one (B, H, P, N) float32 state a chunk."""
    B, S, HP = x.shape
    H, Q = int(num_heads), int(chunk)
    P, N, nc = HP // H, b.shape[-1], S // Q
    dt, a = dt.astype(jnp.float32), a.astype(jnp.float32)
    below = jnp.tril(jnp.ones((Q, Q), dtype=bool))[None, :, :, None]

    @jax.checkpoint
    def one(state, args):
        xc, dtc, bc, cc = args          # (B,Q,H,P) (B,Q,H) (B,Q,N) (B,Q,N)
        cs = jnp.cumsum(dtc * a, axis=1)
        pairs = jnp.einsum("bqn,bkn->bqk", cc, bc,
                           preferred_element_type=jnp.float32)
        decay = jnp.exp(jnp.where(below, cs[:, :, None] - cs[:, None], -1e30))
        inside = (pairs[..., None] * decay * dtc[:, None]).astype(x.dtype)
        y = jnp.einsum("bqkh,bkhp->bqhp", inside, xc,
                       preferred_element_type=jnp.float32)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bqn,bhpn->bqhp", cc, state.astype(x.dtype),
            preferred_element_type=jnp.float32)
        to_end = jnp.exp(cs[:, -1:] - cs) * dtc
        state = jnp.exp(cs[:, -1])[:, :, None, None] * state + jnp.einsum(
            "bqhp,bqn->bhpn",
            (xc.astype(jnp.float32) * to_end[..., None]).astype(x.dtype), bc,
            preferred_element_type=jnp.float32)
        return state, y.astype(x.dtype)

    def chunks(arr):        # (B, S, ...) -> (S / Q, B, Q, ...)
        return jnp.moveaxis(arr.reshape((B, nc, Q) + arr.shape[2:]), 1, 0)

    _, y = lax.scan(one, jnp.zeros((B, H, P, N), jnp.float32), (
        chunks(x.reshape(B, S, H, P)), chunks(dt), chunks(b), chunks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(B, S, HP)


def _ssm_scan_path(seq, heads, head_dim, state, chunk):
    """``"kernel"`` (the chunked-scan kernels of ``ops/pallas_kernels.py``)
    or ``"xla"`` (:func:`xla_ssm_scan`), from shapes and platform alone:
    the kernels need an accelerator, no visible mesh of several devices
    (GSPMD cannot partition a Mosaic kernel and the scan has no
    ``shard_map`` wrapper yet), heads of 64 in even number, a state that
    is a multiple of 128 wide, a chunk that is a multiple of 128 and a
    sequence of whole chunks."""
    from . import pallas_kernels as pk
    return "kernel" if _one_device_accelerator() and pk.ssm_scan_usable(
        seq, heads, head_dim, state, chunk) else "xla"


@register("_contrib_ssm_scan")
def ssm_scan(x, dt, a_log, b, c, d, num_heads=1, chunk=256):
    """The selective state-space scan of Mamba-2 (Dao and Gu, "Transformers
    are SSMs", ICML 2024) with one group of B and C: for head h with state
    ``h (P, N)`` from nought, ``h_t = exp(dt[t, h] A[h]) h_{t-1} + dt[t, h]
    x[t, h] b[t]^T`` and ``y[t, h] = h_t c[t] + d[h] x[t, h]``, ``A =
    -exp(a_log)``.

    ``x (B, S, H*P)``; ``dt (B, S, H)`` the step AFTER its softplus;
    ``a_log`` and ``d (H,)``; ``b`` and ``c (B, S, N)``, shared by the heads.
    Returns ``(B, S, H*P)``. Computed in the chunked form: inside a chunk of
    ``chunk`` positions ``Y = (L o C B^T)(dt x)`` with ``L[i, j]`` the decay
    from j to i, the chunk's end state from ``B^T (decay to the end o dt
    x)``, the states passed from chunk to chunk by the chunks' total decays
    and ``Y += (decay from the start o C) h_prev``. Decays in float32 (the
    log-decays are summed, never the decays multiplied); products in the
    storage type with float32 accumulation. A sequence that is not whole
    chunks is padded with steps of nought, which neither decay nor feed the
    state. ONE decision (:func:`_ssm_scan_path`) sends it to the kernels
    (forward and backward, the state in float32 VMEM along the sequential
    chunk axis) or to :func:`xla_ssm_scan`; counted once a trace in
    :func:`ssm_scan_stats`. Written under the ``ssm_scan`` scope."""
    from . import pallas_kernels as pk
    with jax.named_scope("ssm_scan"):
        B, S, HP = x.shape
        H, Q = int(num_heads), int(chunk)
        P, N = HP // H, b.shape[-1]
        dt = dt.astype(jnp.float32)
        a = -jnp.exp(a_log.astype(jnp.float32))
        pad = -S % Q
        if pad:
            x, dt, b, c = (jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
                           for arr in (x, dt, b, c))
        path = _ssm_scan_path(S + pad, H, P, N, Q)
        _SSM_SCANS[path] += 1
        if path == "kernel":
            y = pk.ssm_scan(x, dt, _chunk_log_decay(dt, a, Q), b, c, H, Q)
        else:
            y = xla_ssm_scan(x, dt, a, b, c, H, Q)
        skip = d.astype(jnp.float32)[:, None] * x.astype(jnp.float32).reshape(
            x.shape[:2] + (H, P))
        y = (y.astype(jnp.float32) + skip.reshape(x.shape)).astype(x.dtype)
        return y[:, :S] if pad else y


@register("_contrib_ssm_mixer")
def ssm_mixer(zxbcdt, conv_weight, conv_bias, dt_bias, a_log, d, gamma,
              num_heads=1, head_dim=64, state=128, chunk=256, eps=1e-5):
    """What a Mamba-2 mixer does between its two projections, under the
    ``ssm`` scope: ``zxbcdt (B, S, inner + (inner + 2 state) + heads)`` is
    the input projection ``[z | xBC | dt]``; ``xBC = silu(conv(xBC))``
    (:func:`causal_conv1d`, scope ``ssm_conv``) splits into x, B and C;
    ``dt = softplus(dt + dt_bias)`` in float32; :func:`ssm_scan` (scope
    ``ssm_scan``); then ``RMSNorm(y * silu(z)) * gamma``
    (:func:`gated_rms_norm`). Returns ``(B, S, inner)`` as the output
    projection reads it."""
    with jax.named_scope("ssm"):
        H, N = int(num_heads), int(state)
        inner = H * int(head_dim)
        z, xbc, dt = _split_columns(zxbcdt, (inner, inner + 2 * N, H))
        x, b, c = _split_columns(
            causal_conv1d.fn(xbc, conv_weight, conv_bias),
            (inner, N, N))
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        y = ssm_scan.fn(x, dt, a_log, b, c, d, num_heads=H, chunk=chunk)
        return gated_rms_norm.fn(y, z, gamma, eps=eps)


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


def _ce_scan(hidden, weight, labels, chunk, grads):
    """The head's one loop over chunks of ``hidden (T, d)`` against
    ``weight (P * V, d)`` and ``labels (T, P)``: ``(loss, dh, dw)`` with
    ``grads``, the loss alone without. A chunk's ``(chunk, P * V)`` logits
    come from one product and are split into the P heads' ``V`` for the
    log-sum-exp; with ``grads`` the same logits give ``d`` (the loss's
    gradient by them at a cotangent of 1, rounded to ``hidden.dtype``), and
    ``d`` the chunk's ``dh = d W`` and its term of ``dw = sum d^T h``
    (float32 across the chunks)."""
    T, heads = labels.shape
    valid = labels >= 0
    count = jnp.maximum(jnp.sum(valid, axis=0), 1).astype(jnp.float32)
    ids = jnp.arange(weight.shape[0] // heads, dtype=labels.dtype)

    def one(dw, args):
        h, lab = args
        logits = lax.dot_general(
            h, weight, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(chunk, heads, -1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
        nll = jnp.where(lab >= 0, lse - picked, 0.0)
        if not grads:
            return dw, (nll, None)
        d = jnp.where((lab >= 0)[..., None],
                      jnp.exp(logits - lse[..., None])
                      - (ids == lab[..., None]), 0.0)
        d = (d * ((1.0 / heads) / count)[:, None]).astype(hidden.dtype)
        # written once: left to itself XLA computes ``d`` from the float32
        # logits inside each of the two products that read it, and both
        # slow down by more than the one pass over the logits costs
        d = lax.optimization_barrier(d.reshape(chunk, -1))
        dw = dw + lax.dot_general(d, h, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, (nll, jnp.matmul(d, weight))

    dw, (nll, dh) = lax.scan(
        one, jnp.zeros(weight.shape, jnp.float32) if grads else None,
        (hidden.reshape(T // chunk, chunk, -1),
         labels.reshape(T // chunk, chunk, heads)))
    loss = jnp.sum(jnp.sum(nll.reshape(T, heads), axis=0) / count) / heads
    if not grads:
        return loss
    return loss, dh.reshape(hidden.shape), dw.astype(weight.dtype)


@_partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_ce(hidden, weight, labels, chunk):
    return _ce_scan(hidden, weight, labels, chunk, grads=False)


def _chunked_ce_fwd(hidden, weight, labels, chunk):
    loss, dh, dw = _ce_scan(hidden, weight, labels, chunk, grads=True)
    return loss, (dh, dw)


def _chunked_ce_bwd(chunk, res, g):
    dh, dw = res
    return (dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), None


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


@register("_contrib_chunked_softmax_cross_entropy")
def chunked_softmax_cross_entropy(hidden, weight, labels, chunk=2048):
    """Mean cross-entropy of ``hidden (..., d) @ weight (V, d).T`` against
    ``labels (...)`` over the positions whose label is not negative, a
    ``chunk`` of positions at a time: no (positions, V) array is live whole.
    ``labels (..., P)``, one axis more than ``hidden`` has before its last:
    P targets a position, head j's rows of ``weight (P * V, d)`` from
    ``j * V`` on, and the mean over the heads of each head's mean.

    Differentiated, the loop that computes the loss computes the head's
    gradient from the logits it holds (three products a chunk: the logits,
    ``d W`` and ``d^T h``): live in a chunk's step are its float32 logits
    ``(chunk, P * V)``, ``d`` in ``hidden``'s dtype and the float32 sum of
    ``d(weight)``; kept for the backward pass are ``d(hidden)`` and
    ``d(weight)`` at a cotangent of 1, which it multiplies by the
    cotangent, and neither ``hidden`` nor ``weight``. Outside a gradient
    the loop is the logits' product and the loss alone."""
    flat = hidden.reshape(-1, hidden.shape[-1])
    heads = 1 if labels.ndim < hidden.ndim else labels.shape[-1]
    with jax.named_scope("loss_head"):
        return _chunked_ce(flat, weight,
                           labels.reshape(-1, heads).astype(jnp.int32),
                           _ce_chunks(flat.shape[0], chunk))
