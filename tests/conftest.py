"""Test harness: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's device-retargeting test pattern
(`tests/python/unittest/common.py` + `mx.test_utils.default_context()`):
one suite, device chosen by environment. XLA-CPU is the oracle; the chip
is exercised by ``chip_smoke.py`` at the repo root.

JAX_PLATFORMS, XLA_FLAGS and JAX_ENABLE_COMPILATION_CACHE are read when jax
starts, so they are set here before jax is imported — and inherited by the
worker processes tests spawn.
"""
import os

_ON_CPU = os.environ.get("MXTPU_TEST_PLATFORM", "cpu") == "cpu"
if _ON_CPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
# The suite runs with the persistent compile cache OFF (jax's own switch):
# thousands of XLA:CPU entries would fill <checkout>/.jax_cache, the tree is
# copied whole to the chip machine, and CPU AOT entries are not portable
# between hosts. Tests that need a cache pass pcache.init their own tmp_path.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Same-suite device retargeting (reference test_utils.py:58
# default_context + tests/python/gpu/test_operator_gpu.py pattern):
# MXTPU_TEST_PLATFORM=tpu runs this suite on the real chip — the
# TPU-vs-CPU consistency sweep (tools/consistency_sweep.py) — with f32
# matmul precision pinned to "highest" so float32 semantics match the
# XLA-CPU oracle (TPU default would use bf16 MXU passes).
if not _ON_CPU:
    jax.config.update("jax_default_matmul_precision", "highest")

    # Device-tolerance floor, the reference's check_consistency pattern
    # (python/mxnet/test_utils.py: GPU fp32 compares at 1e-3): oracle
    # assertions written against XLA-CPU exactness get the accelerator
    # tolerance when the suite retargets the chip (TPU transcendental
    # approximations differ by ~1e-4 rel).
    import numpy as _np
    import numpy.testing as _npt
    _orig_allclose = _npt.assert_allclose

    def _tpu_allclose(actual, desired, rtol=1e-7, atol=0, *args, **kwargs):
        # Floor only floating-point comparisons that didn't ask for
        # exactness: rtol=0 is an explicit exact-match intent and integer
        # comparisons must stay bitwise — only default-ish float tolerances
        # get the accelerator floor.
        a, d = _np.asarray(actual), _np.asarray(desired)
        floaty = a.dtype.kind in "fc" or d.dtype.kind in "fc"
        if floaty and rtol != 0:
            rtol, atol = max(rtol, 1e-3), max(atol, 1e-5)
        return _orig_allclose(actual, desired, rtol=rtol, atol=atol,
                              *args, **kwargs)

    _npt.assert_allclose = _tpu_allclose

    # Optionally floor plain np.allclose too (reference check_consistency
    # applies the device tolerance to every comparison) — but patching the
    # GLOBAL np.allclose can mask intentionally-tight asserts, so it is
    # opt-in for the chip sweep (tools/consistency_sweep.py sets it),
    # not ambient for every TPU-targeted run.
    if os.environ.get("MXTPU_TEST_ALLCLOSE_FLOOR", "0") == "1":
        _orig_np_allclose = _np.allclose

        def _tpu_np_allclose(a, b, rtol=1e-5, atol=1e-8, **kw):
            aa, bb = _np.asarray(a), _np.asarray(b)
            floaty = aa.dtype.kind in "fc" or bb.dtype.kind in "fc"
            if floaty and rtol != 0:
                rtol, atol = max(rtol, 1e-3), max(atol, 1e-5)
            return _orig_np_allclose(a, b, rtol=rtol, atol=atol, **kw)

        _np.allclose = _tpu_np_allclose

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long end-to-end tests excluded from the tier-1 sweep "
        "(run explicitly with -m slow)")
    config.addinivalue_line(
        "markers",
        "chaos: tests that arm fault-injection points "
        "(mxnet_tpu.resilience.chaos) — deselect with -m 'not chaos' when "
        "debugging unrelated failures")


@pytest.fixture(autouse=True)
def _seed_everything():
    import mxnet_tpu as mx
    np.random.seed(0)
    mx.random.seed(0)
    yield


@pytest.fixture
def chip_present_interpreted(monkeypatch):
    """A chip is "present" and the attention kernels run interpreted: the
    dispatcher in ``ops/nn.py`` decides as it does on the chip
    (``_on_accelerator`` is its platform seam) and the eight kernel entries
    it calls are handed ``interpret=True``. Nothing else is patched."""
    import functools
    from mxnet_tpu.ops import nn as nn_ops
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    for name in ("flash_attention", "flash_attention_bshd",
                 "flash_attention_packed", "flash_attention_latent",
                 "flash_attention_eva", "flash_attention_grouped",
                 "ssm_scan", "causal_conv1d"):
        monkeypatch.setattr(pk, name, functools.partial(getattr(pk, name),
                                                        interpret=True))
