"""Test utilities.

Parity surface: reference ``python/mxnet/test_utils.py`` —
assert_almost_equal :534, check_numeric_gradient :981 (central finite
differences), default_context :58, check_consistency (cross-device oracle).
On TPU the cross-device oracle is XLA-CPU vs the chip; the numeric-gradient
oracle checks the tape+jax.vjp backward against finite differences.
"""
from __future__ import annotations

import numpy as np

from .context import Context, current_context
from .ndarray.ndarray import NDArray, array
from . import autograd as ag

_default_ctx = None


def default_context() -> Context:
    return _default_ctx or current_context()


def set_default_context(ctx):
    global _default_ctx
    _default_ctx = ctx


def assert_almost_equal(a, b, rtol=1e-5, atol=1e-8, names=("a", "b")):
    a = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, NDArray) else np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=rtol, atol=atol,
                               err_msg="%s vs %s" % names)


def almost_equal(a, b, rtol=1e-5, atol=1e-8):
    a = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    b = b.asnumpy() if isinstance(b, NDArray) else np.asarray(b)
    return np.allclose(a, b, rtol=rtol, atol=atol)


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    data = np.random.uniform(-1, 1, size=shape).astype(dtype or np.float32)
    out = array(data, ctx=ctx)
    if stype != "default":
        out = out.tostype(stype)
    return out


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def check_numeric_gradient(fn, inputs, eps=1e-3, rtol=1e-3, atol=1e-4):
    """Central finite differences vs tape backward
    (reference `python/mxnet/test_utils.py:981`)."""
    import jax
    try:
        on_accel = any(d.platform not in ("cpu",) for d in jax.devices())
    except RuntimeError:
        on_accel = False
    if on_accel:
        # f32 central differences on the accelerator carry ~1e-3 rel
        # truncation+rounding; the reference's GPU FD checks run at 1e-2
        # (test_utils.py check_numeric_gradient GPU defaults)
        rtol, atol = max(rtol, 1e-2), max(atol, 1e-3)
    inputs = [x if isinstance(x, NDArray) else array(x) for x in inputs]
    for x in inputs:
        x.attach_grad()
    with ag.record():
        y = fn(*inputs)
    y.backward()
    analytic = [x.grad.asnumpy().copy() for x in inputs]

    for i, x in enumerate(inputs):
        # ascontiguousarray: a backend may hand back F-contiguous host
        # arrays, and ravel() of an F-order array is a COPY — the nflat[j]
        # writes below would silently vanish (the
        # docs/consistency_tpu.md all-zero-numeric failure class)
        base = np.ascontiguousarray(x.asnumpy(), dtype=np.float64)
        num = np.zeros(base.shape, dtype=np.float64)
        flat = base.ravel()
        nflat = num.ravel()
        for j in range(flat.size):
            orig = flat[j]
            _set_flat(x, base, j, orig + eps)
            fp = float(fn(*inputs).asnumpy())
            _set_flat(x, base, j, orig - eps)
            fm = float(fn(*inputs).asnumpy())
            _set_flat(x, base, j, orig)
            flat[j] = orig
            nflat[j] = (fp - fm) / (2 * eps)
        np.testing.assert_allclose(analytic[i], num, rtol=rtol, atol=atol,
                                   err_msg="gradient mismatch for input %d" % i)


def _set_flat(x, base, j, val):
    import jax.numpy as jnp
    b = base.copy()
    b.ravel()[j] = val
    x._data = jnp.asarray(b.astype(np.asarray(x._data).dtype))
    return x._data


def check_consistency(fn, inputs, ctxs=None, rtol=1e-4, atol=1e-6):
    """Cross-device same-op comparison (reference check_consistency — GPU vs
    CPU oracle; here each ctx in ctxs, default cpu-only)."""
    outs = []
    for ctx in (ctxs or [Context("cpu", 0)]):
        with ctx:
            ins = [x.as_in_context(ctx) for x in inputs]
            outs.append(fn(*ins).asnumpy())
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=rtol, atol=atol)
    return outs


class DummyIter:
    def __init__(self, batches):
        self._batches = batches

    def __iter__(self):
        return iter(self._batches)


def same(a, b):
    """Exact array equality (reference test_utils.py same)."""
    return np.array_equal(np.asarray(a.asnumpy() if hasattr(a, "asnumpy")
                                     else a),
                          np.asarray(b.asnumpy() if hasattr(b, "asnumpy")
                                     else b))


def rand_shape_2d(dim0=10, dim1=10):
    """Random 2D shape (reference test_utils.py rand_shape_2d)."""
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1))


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (np.random.randint(1, dim0 + 1), np.random.randint(1, dim1 + 1),
            np.random.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=num_dim).tolist())


def list_gpus():
    """Enumerate accelerator ordinals (reference test_utils.py list_gpus —
    here, TPU chips; empty on a CPU-only host)."""
    import jax
    try:
        return [d.id for d in jax.devices() if d.platform != "cpu"]
    except RuntimeError:
        return []
