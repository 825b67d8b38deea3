"""C ABI tests (reference `include/mxnet/c_api.h` principle — §2.3: one C
boundary for all language bindings). Two scenarios:

1. ctypes in-process: the library attaches to THIS interpreter and shares
   its runtime/handles (how the reference's own Python frontend crosses
   the boundary).
2. standalone C host: a compiled C program boots the runtime itself via
   MXTpuInit — the R/Scala/Julia-binding scenario.
"""
import ctypes
import os
import pathlib
import subprocess

import numpy as onp
import pytest

from _capi_testlib import REPO, LIB, built

pytestmark = pytest.mark.skipif(not built(),
                                reason="libmxtpu_c.so not built")


@pytest.fixture(scope="module")
def capi():
    lib = ctypes.CDLL(str(LIB))
    c = ctypes
    lib.MXGetLastError.restype = c.c_char_p
    lib.MXTpuInit.argtypes = [c.c_char_p]
    lib.MXGetVersion.argtypes = [c.POINTER(c.c_int)]
    lib.MXNDArrayCreate.argtypes = [c.POINTER(c.c_int64), c.c_int,
                                    c.c_char_p, c.POINTER(c.c_void_p)]
    lib.MXNDArrayFree.argtypes = [c.c_void_p]
    lib.MXNDArrayGetShape.argtypes = [c.c_void_p, c.POINTER(c.c_int),
                                      c.POINTER(c.c_int64), c.c_int]
    lib.MXNDArraySyncCopyFromCPU.argtypes = [c.c_void_p,
                                             c.POINTER(c.c_float),
                                             c.c_int64]
    lib.MXNDArraySyncCopyToCPU.argtypes = [c.c_void_p,
                                           c.POINTER(c.c_float), c.c_int64]
    lib.MXImperativeInvoke.argtypes = [c.c_char_p, c.POINTER(c.c_void_p),
                                       c.c_int, c.c_char_p,
                                       c.POINTER(c.c_void_p),
                                       c.POINTER(c.c_int)]
    lib.MXListAllOpNames.argtypes = [c.POINTER(c.c_int),
                                     c.POINTER(c.POINTER(c.c_char_p))]
    assert lib.MXTpuInit(None) == 0, lib.MXGetLastError()
    return lib


def test_version_and_ops(capi):
    v = ctypes.c_int()
    assert capi.MXGetVersion(ctypes.byref(v)) == 0
    assert v.value >= 100  # 10000*maj + 100*min + patch (0.1.0 -> 100)
    n = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert capi.MXListAllOpNames(ctypes.byref(n), ctypes.byref(names)) == 0
    assert n.value > 400
    seen = {names[i].decode() for i in range(min(n.value, 2000))}
    assert "relu" in seen and "Convolution" in seen


def test_ndarray_roundtrip_and_invoke(capi):
    shape = (ctypes.c_int64 * 2)(2, 2)
    h = ctypes.c_void_p()
    assert capi.MXNDArrayCreate(shape, 2, b"float32",
                                ctypes.byref(h)) == 0
    src = (ctypes.c_float * 4)(-1.0, 2.0, -3.0, 4.0)
    assert capi.MXNDArraySyncCopyFromCPU(h, src, 4) == 0

    outs = (ctypes.c_void_p * 2)()
    n_out = ctypes.c_int(2)
    assert capi.MXImperativeInvoke(b"relu", ctypes.byref(h), 1, None,
                                   outs, ctypes.byref(n_out)) == 0
    assert n_out.value == 1
    dst = (ctypes.c_float * 4)()
    assert capi.MXNDArraySyncCopyToCPU(outs[0], dst, 4) == 0
    onp.testing.assert_allclose(list(dst), [0.0, 2.0, 0.0, 4.0])

    ndim = ctypes.c_int()
    oshape = (ctypes.c_int64 * 8)()
    assert capi.MXNDArrayGetShape(outs[0], ctypes.byref(ndim), oshape, 8) == 0
    assert ndim.value == 2 and oshape[0] == 2 and oshape[1] == 2

    capi.MXNDArrayFree(h)
    capi.MXNDArrayFree(outs[0])


def test_invoke_with_kwargs_and_error(capi):
    shape = (ctypes.c_int64 * 2)(2, 3)
    h = ctypes.c_void_p()
    assert capi.MXNDArrayCreate(shape, 2, b"float32", ctypes.byref(h)) == 0
    src = (ctypes.c_float * 6)(1, 2, 3, 4, 5, 6)
    assert capi.MXNDArraySyncCopyFromCPU(h, src, 6) == 0
    outs = (ctypes.c_void_p * 2)()
    n_out = ctypes.c_int(2)
    assert capi.MXImperativeInvoke(b"sum", ctypes.byref(h), 1,
                                   b'{"axis": 0}', outs,
                                   ctypes.byref(n_out)) == 0
    dst = (ctypes.c_float * 3)()
    assert capi.MXNDArraySyncCopyToCPU(outs[0], dst, 3) == 0
    onp.testing.assert_allclose(list(dst), [5.0, 7.0, 9.0])
    capi.MXNDArrayFree(outs[0])

    # unknown op surfaces through MXGetLastError, not a crash
    n_out = ctypes.c_int(2)
    assert capi.MXImperativeInvoke(b"definitely_not_an_op",
                                   ctypes.byref(h), 1, None, outs,
                                   ctypes.byref(n_out)) == -1
    assert b"unknown operator" in capi.MXGetLastError()
    capi.MXNDArrayFree(h)


def _build_and_run(c_name, exe_name, extra_args=(), timeout=600):
    exe = REPO / "lib" / exe_name
    src = REPO / "tests" / "c_api" / c_name
    inc = REPO / "src" / "include"
    r = subprocess.run(
        ["gcc", "-O1", str(src), "-I", str(inc),
         "-L", str(REPO / "lib"), "-lmxtpu_c", "-lm",
         "-Wl,-rpath," + str(REPO / "lib"), "-o", str(exe)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([str(exe), str(REPO), *map(str, extra_args)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_standalone_c_host():
    """Compile tests/c_api/host_test.c against the ABI and run it as its
    own process (boots the runtime via MXTpuInit)."""
    out = _build_and_run("host_test.c", "host_test")
    assert "C_API_HOST_OK" in out


def test_c_host_trains_lenet():
    """A pure-C host builds LeNet via the symbol ABI, binds an executor,
    trains with sgd_update, kvstore round-trips a weight, and exports the
    model (reference c_api_executor.cc + c_api.cc:986 capability proof)."""
    out = _build_and_run("train_lenet.c", "train_lenet")
    assert "C_API_TRAIN_OK" in out


def test_c_host_predict_exported_model(tmp_path):
    """A pure-C host loads the model the training host exported and runs
    inference through the predict ABI (reference c_predict_api.cc).
    Always regenerates the export so stale artifacts can't mask a
    save/export regression."""
    out = _build_and_run("train_lenet.c", "train_lenet",
                         extra_args=[tmp_path])
    assert "C_API_TRAIN_OK" in out
    out = _build_and_run("predict_host.c", "predict_host",
                         extra_args=[tmp_path / "lenet_capi-symbol.json",
                                     tmp_path / "lenet_capi.params"])
    assert "C_API_PREDICT_OK" in out


def _sig(lib):
    c = ctypes
    sigs = {
        "MXRandomSeed": [c.c_int],
        "MXGetGPUCount": [c.POINTER(c.c_int)],
        "MXLibInfoFeatures": [c.POINTER(c.POINTER(c.c_char_p)),
                              c.POINTER(c.POINTER(c.c_int)),
                              c.POINTER(c.c_int)],
        "MXNDArrayCreateEx": [c.POINTER(c.c_int64), c.c_int, c.c_char_p,
                              c.c_char_p, c.POINTER(c.c_void_p)],
        "MXNDArrayGetDType": [c.c_void_p, c.POINTER(c.c_char_p)],
        "MXNDArrayGetContext": [c.c_void_p, c.POINTER(c.c_char_p)],
        "MXNDArrayReshape": [c.c_void_p, c.c_int, c.POINTER(c.c_int64),
                             c.POINTER(c.c_void_p)],
        "MXNDArraySlice": [c.c_void_p, c.c_int64, c.c_int64,
                           c.POINTER(c.c_void_p)],
        "MXNDArraySave": [c.c_char_p, c.c_int, c.POINTER(c.c_void_p),
                          c.POINTER(c.c_char_p)],
        "MXNDArrayLoad": [c.c_char_p, c.POINTER(c.c_int),
                          c.POINTER(c.POINTER(c.c_void_p)),
                          c.POINTER(c.c_int),
                          c.POINTER(c.POINTER(c.c_char_p))],
        "MXAutogradSetIsRecording": [c.c_int, c.POINTER(c.c_int)],
        "MXAutogradMarkVariables": [c.c_int, c.POINTER(c.c_void_p),
                                    c.POINTER(c.c_int),
                                    c.POINTER(c.c_void_p)],
        "MXAutogradBackward": [c.c_int, c.POINTER(c.c_void_p),
                               c.POINTER(c.c_void_p), c.c_int],
        "MXNDArrayGetGrad": [c.c_void_p, c.POINTER(c.c_void_p)],
        "MXListDataIters": [c.POINTER(c.c_int),
                            c.POINTER(c.POINTER(c.c_char_p))],
        "MXDataIterCreateIter": [c.c_char_p, c.c_int,
                                 c.POINTER(c.c_char_p),
                                 c.POINTER(c.c_char_p),
                                 c.POINTER(c.c_void_p)],
        "MXDataIterNext": [c.c_void_p, c.POINTER(c.c_int)],
        "MXDataIterGetData": [c.c_void_p, c.POINTER(c.c_void_p)],
        "MXDataIterFree": [c.c_void_p],
        "MXRecordIOWriterCreate": [c.c_char_p, c.POINTER(c.c_void_p)],
        "MXRecordIOWriterWriteRecord": [c.c_void_p, c.c_char_p, c.c_int64],
        "MXRecordIOWriterFree": [c.c_void_p],
        "MXRecordIOReaderCreate": [c.c_char_p, c.POINTER(c.c_void_p)],
        "MXRecordIOReaderReadRecord": [c.c_void_p, c.POINTER(c.c_char_p),
                                       c.POINTER(c.c_int64)],
        "MXRecordIOReaderFree": [c.c_void_p],
    }
    for name, argtypes in sigs.items():
        getattr(lib, name).argtypes = argtypes
    return lib


def test_ndarray_extended_abi(capi, tmp_path):
    c = ctypes
    lib = _sig(capi)
    assert lib.MXRandomSeed(42) == 0
    n = c.c_int()
    assert lib.MXGetGPUCount(c.byref(n)) == 0 and n.value >= 1

    names = c.POINTER(c.c_char_p)()
    flags = c.POINTER(c.c_int)()
    sz = c.c_int()
    assert lib.MXLibInfoFeatures(c.byref(names), c.byref(flags),
                                 c.byref(sz)) == 0
    assert sz.value > 5

    shape = (c.c_int64 * 2)(4, 6)
    h = c.c_void_p()
    assert lib.MXNDArrayCreateEx(shape, 2, b"float32", b"cpu",
                                 c.byref(h)) == 0
    dt = c.c_char_p()
    assert lib.MXNDArrayGetDType(h, c.byref(dt)) == 0
    assert dt.value == b"float32"
    cx = c.c_char_p()
    assert lib.MXNDArrayGetContext(h, c.byref(cx)) == 0
    assert cx.value == b"cpu(0)"

    h2 = c.c_void_p()
    dims = (c.c_int64 * 2)(6, 4)
    assert lib.MXNDArrayReshape(h, 2, dims, c.byref(h2)) == 0
    nd = c.c_int()
    shp = (c.c_int64 * 8)()
    assert capi.MXNDArrayGetShape(h2, c.byref(nd), shp, 8) == 0
    assert (shp[0], shp[1]) == (6, 4)

    h3 = c.c_void_p()
    assert lib.MXNDArraySlice(h, 1, 3, c.byref(h3)) == 0
    assert capi.MXNDArrayGetShape(h3, c.byref(nd), shp, 8) == 0
    assert (shp[0], shp[1]) == (2, 6)

    # save / load named container
    fname = str(tmp_path / "x.params").encode()
    keys = (c.c_char_p * 1)(b"arg:w")
    arrs = (c.c_void_p * 1)(h)
    assert lib.MXNDArraySave(fname, 1, arrs, keys) == 0
    n_out, n_names = c.c_int(), c.c_int()
    out_arrs = c.POINTER(c.c_void_p)()
    out_names = c.POINTER(c.c_char_p)()
    assert lib.MXNDArrayLoad(fname, c.byref(n_out), c.byref(out_arrs),
                             c.byref(n_names), c.byref(out_names)) == 0
    assert n_out.value == 1 and out_names[0] == b"arg:w"
    capi.MXNDArrayFree(out_arrs[0])
    for hh in (h, h2, h3):
        capi.MXNDArrayFree(hh)


def test_autograd_abi(capi):
    c = ctypes
    lib = _sig(capi)
    shape = (c.c_int64 * 1)(3,)
    x = c.c_void_p()
    assert capi.MXNDArrayCreate(shape, 1, b"float32", c.byref(x)) == 0
    src = (c.c_float * 3)(1.0, 2.0, 3.0)
    assert capi.MXNDArraySyncCopyFromCPU(x, src, 3) == 0
    g = c.c_void_p()
    assert capi.MXNDArrayCreate(shape, 1, b"float32", c.byref(g)) == 0

    prev = c.c_int()
    assert lib.MXAutogradSetIsRecording(1, c.byref(prev)) == 0
    reqs = (c.c_int * 1)(1)
    vars_ = (c.c_void_p * 1)(x)
    grads = (c.c_void_p * 1)(g)
    assert lib.MXAutogradMarkVariables(1, vars_, reqs, grads) == 0

    # y = x * x under the tape
    outs = (c.c_void_p * 1)()
    n_out = c.c_int(1)
    ins = (c.c_void_p * 2)(x, x)
    assert capi.MXImperativeInvoke(b"elemwise_mul", ins, 2, None, outs,
                                   c.byref(n_out)) == 0
    assert lib.MXAutogradBackward(1, outs, None, 0) == 0
    assert lib.MXAutogradSetIsRecording(0, c.byref(prev)) == 0

    gh = c.c_void_p()
    assert lib.MXNDArrayGetGrad(x, c.byref(gh)) == 0
    dst = (c.c_float * 3)()
    assert capi.MXNDArraySyncCopyToCPU(gh, dst, 3) == 0
    onp.testing.assert_allclose(list(dst), [2.0, 4.0, 6.0], rtol=1e-5)
    for hh in (x, g, outs[0], gh):
        capi.MXNDArrayFree(hh)


def test_dataiter_and_recordio_abi(capi, tmp_path):
    c = ctypes
    lib = _sig(capi)

    # recordio round-trip
    uri = str(tmp_path / "t.rec").encode()
    w = c.c_void_p()
    assert lib.MXRecordIOWriterCreate(uri, c.byref(w)) == 0
    assert lib.MXRecordIOWriterWriteRecord(w, b"hello", 5) == 0
    assert lib.MXRecordIOWriterWriteRecord(w, b"worlds!", 7) == 0
    assert lib.MXRecordIOWriterFree(w) == 0
    r = c.c_void_p()
    assert lib.MXRecordIOReaderCreate(uri, c.byref(r)) == 0
    buf = c.c_char_p()
    nbytes = c.c_int64()
    assert lib.MXRecordIOReaderReadRecord(r, c.byref(buf),
                                          c.byref(nbytes)) == 0
    assert ctypes.string_at(buf, nbytes.value) == b"hello"
    assert lib.MXRecordIOReaderReadRecord(r, c.byref(buf),
                                          c.byref(nbytes)) == 0
    assert ctypes.string_at(buf, nbytes.value) == b"worlds!"
    assert lib.MXRecordIOReaderReadRecord(r, c.byref(buf),
                                          c.byref(nbytes)) == 0
    assert nbytes.value == -1  # EOF
    assert lib.MXRecordIOReaderFree(r) == 0

    # CSVIter through the C iterator ABI
    csv = tmp_path / "d.csv"
    csv.write_text("\n".join(
        ",".join(str(i * 4 + j) for j in range(4)) for i in range(6)))
    n = c.c_int()
    names = c.POINTER(c.c_char_p)()
    assert lib.MXListDataIters(c.byref(n), c.byref(names)) == 0
    listed = {names[i] for i in range(n.value)}
    assert b"CSVIter" in listed
    keys = (c.c_char_p * 3)(b"data_csv", b"data_shape", b"batch_size")
    vals = (c.c_char_p * 3)(str(csv).encode(), b"(4,)", b"2")
    it = c.c_void_p()
    assert lib.MXDataIterCreateIter(b"CSVIter", 3, keys, vals,
                                    c.byref(it)) == 0, capi.MXGetLastError()
    more = c.c_int()
    assert lib.MXDataIterNext(it, c.byref(more)) == 0 and more.value == 1
    d = c.c_void_p()
    assert lib.MXDataIterGetData(it, c.byref(d)) == 0
    nd = c.c_int()
    shp = (c.c_int64 * 4)()
    assert capi.MXNDArrayGetShape(d, c.byref(nd), shp, 4) == 0
    assert (shp[0], shp[1]) == (2, 4)
    host = (c.c_float * 8)()
    assert capi.MXNDArraySyncCopyToCPU(d, host, 8) == 0
    onp.testing.assert_allclose(list(host), list(range(8)))
    capi.MXNDArrayFree(d)
    assert lib.MXDataIterFree(it) == 0
