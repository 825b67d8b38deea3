"""Base utilities: dtypes, errors, registry plumbing.

TPU-native re-design of the roles of ``python/mxnet/base.py`` (reference
`python/mxnet/base.py`) — but with no ctypes FFI for the compute path: the
"runtime" is JAX/XLA, so the bridge layer the reference needs (check_call,
handle types) collapses to plain Python. The native C++ runtime pieces this
framework does have (engine, recordio) expose their own ctypes bridge in
``mxnet_tpu._ffi``.
"""
from __future__ import annotations

import numpy as _np

__all__ = [
    "MXNetError", "string_types", "numeric_types", "integer_types",
    "dtype_np", "dtype_name", "_as_list", "PROGRAM_SCOPES",
]


class MXNetError(RuntimeError):
    """Error raised by the framework (parity with mxnet.base.MXNetError)."""


# ``jax.named_scope`` names the program itself writes into a traced
# program's op metadata, beside each Gluon block's own name (see
# docs/observability.md): ``attention`` around the whole attention op
# (ops/nn.py), ``optimizer`` around the trainer's update loop
# (parallel/trainer.py), ``moe_router`` / ``moe_experts`` / ``moe_shared``
# around the three parts of a dropless expert layer and ``moe_sort`` /
# ``moe_products`` / ``moe_combine`` inside ``moe_experts`` (parallel/moe.py),
# ``ssm`` around what a state-space mixer does between its two projections
# and ``ssm_conv`` / ``ssm_scan`` inside it, ``loss_head`` around the chunked
# cross-entropy head (ops/nn.py).
# A block of one of these names enters the scope under its name plus "_", so
# a trace reader that meets the bare word knows the program wrote it.
PROGRAM_SCOPES = ("attention", "optimizer", "moe_router", "moe_experts",
                  "moe_shared", "moe_sort", "moe_products", "moe_combine",
                  "ssm", "ssm_conv", "ssm_scan", "loss_head")

string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# Canonical dtype table. MXNet uses an int code enum (reference
# `python/mxnet/ndarray/ndarray.py:54` _DTYPE_NP_TO_MX); on TPU the canonical
# low-precision type is bfloat16 rather than float16, but both are supported.
_DTYPE_ALIASES = {
    "float": "float32",
    "double": "float64",
    "half": "float16",
    "bf16": "bfloat16",
}


def dtype_np(dtype):
    """Normalize a user dtype spec to a numpy dtype (incl. bfloat16)."""
    if dtype is None:
        return _np.dtype("float32")
    if isinstance(dtype, str):
        dtype = _DTYPE_ALIASES.get(dtype, dtype)
        if dtype == "bfloat16":
            import ml_dtypes
            return _np.dtype(ml_dtypes.bfloat16)
    if not isinstance(dtype, type) and hasattr(dtype, "dtype"):
        # array-like instance (NDArray, jax array): take its dtype; plain
        # scalar types like np.uint8 carry a class-level descriptor and
        # must go straight to np.dtype
        dtype = dtype.dtype
    return _np.dtype(dtype)


def dtype_name(dtype) -> str:
    return dtype_np(dtype).name


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]
