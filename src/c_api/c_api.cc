// Flat C ABI over the mxnet_tpu runtime.
//
// Role parity: reference `include/mxnet/c_api.h` (3,244-line flat ABI) and
// `src/c_api/` (NDArray CRUD c_api.cc:209-271, imperative invoke
// c_api_ndarray.cc:87-149, symbol c_api_symbolic.cc, executor
// c_api_executor.cc, kvstore c_api.cc:986-1331, predictor
// c_predict_api.cc). The reference keeps ONE C boundary so every language
// binding (§2.3: R/Scala/Julia/C++/...) stays mechanical; this library
// preserves that principle for the TPU rebuild.
//
// TPU-native design: the runtime's execution substrate is XLA behind the
// Python/JAX layer, so the C ABI embeds CPython and drives the SAME
// runtime objects the Python frontend uses (one handle type, one op
// registry) instead of duplicating a second native runtime. Each entry
// point marshals C arrays/strings to Python and lands in
// `mxnet_tpu/_c_api_impl.py` — one flat support function per ABI call. A
// C host can link this library standalone (MXTpuInit boots an
// interpreter) or live inside an existing Python process (handles share
// the interpreter). Every entry point is exception-safe: failures set a
// thread-local error string readable via MXGetLastError (reference
// c_api_error.cc contract).

#include <Python.h>
#include <omp.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

#define MXTPU_API extern "C" __attribute__((visibility("default")))

// compile against the public ABI so header/impl signature drift is a
// compile error, not runtime corruption in C hosts
#include "../include/mxtpu_c.h"

namespace {

thread_local std::string g_last_error;

void set_error(const std::string& msg) { g_last_error = msg; }

// Scoped GIL ownership for calls arriving from arbitrary host threads.
class GILGuard {
 public:
  GILGuard() : state_(PyGILState_Ensure()) {}
  ~GILGuard() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

std::string py_error_string() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  std::string msg = "unknown python error";
  if (value) {
    PyObject* s = PyObject_Str(value);
    if (s) {
      msg = PyUnicode_AsUTF8(s);
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
  return msg;
}

// Borrowed module cache (imported once per process).
PyObject* runtime_module() {
  static PyObject* mod = nullptr;
  if (mod == nullptr) {
    mod = PyImport_ImportModule("mxnet_tpu");
  }
  return mod;  // may be nullptr with python error set
}

PyObject* ndarray_module() {
  static PyObject* mod = nullptr;
  if (mod == nullptr) {
    mod = PyImport_ImportModule("mxnet_tpu.ndarray.ndarray");
  }
  return mod;
}

PyObject* registry_module() {
  static PyObject* mod = nullptr;
  if (mod == nullptr) {
    mod = PyImport_ImportModule("mxnet_tpu.ops.registry");
  }
  return mod;
}

PyObject* impl_module() {
  static PyObject* mod = nullptr;
  if (mod == nullptr) {
    mod = PyImport_ImportModule("mxnet_tpu._c_api_impl");
  }
  return mod;
}

// Call a support function in mxnet_tpu._c_api_impl. `args` is a NEW
// reference to an argument tuple and is consumed; returns a new reference
// or nullptr with the error string set. Caller must hold the GIL.
PyObject* impl_call(const char* fn, PyObject* args) {
  PyObject* mod = impl_module();
  if (!mod) {
    Py_XDECREF(args);
    set_error(py_error_string());
    return nullptr;
  }
  PyObject* f = PyObject_GetAttrString(mod, fn);
  if (!f) {
    Py_XDECREF(args);
    set_error(py_error_string());
    return nullptr;
  }
  PyObject* r = PyObject_CallObject(f, args);
  Py_DECREF(f);
  Py_XDECREF(args);
  if (!r) set_error(py_error_string());
  return r;
}

// ---- C -> Python marshalling -------------------------------------------

PyObject* py_str_or_none(const char* s) {
  if (s == nullptr) Py_RETURN_NONE;
  return PyUnicode_FromString(s);
}

PyObject* py_strlist(const char** arr, int n) {
  PyObject* l = PyList_New(n);
  for (int i = 0; i < n; ++i) {
    PyList_SET_ITEM(l, i, PyUnicode_FromString(
        (arr && arr[i]) ? arr[i] : ""));
  }
  return l;
}

// NULL entries become None; object refs are borrowed from handles.
PyObject* py_handlelist(void** arr, int n) {
  PyObject* l = PyList_New(n);
  for (int i = 0; i < n; ++i) {
    PyObject* o = arr ? static_cast<PyObject*>(arr[i]) : nullptr;
    if (o == nullptr) o = Py_None;
    Py_INCREF(o);
    PyList_SET_ITEM(l, i, o);
  }
  return l;
}

PyObject* py_shape_tuple(const int64_t* dims, int ndim) {
  PyObject* t = PyTuple_New(ndim);
  for (int i = 0; i < ndim; ++i) {
    PyTuple_SET_ITEM(t, i, PyLong_FromLongLong(dims[i]));
  }
  return t;
}

// flattened shape arrays -> list of tuples
PyObject* py_shapelist(const int* ndims, const int64_t* data, int n) {
  PyObject* l = PyList_New(n);
  const int64_t* p = data;
  for (int i = 0; i < n; ++i) {
    int nd = ndims ? ndims[i] : 0;
    if (nd < 0) {
      // unknown shape (partial inference): mirrors store_shapelist's -1
      Py_INCREF(Py_None);
      PyList_SET_ITEM(l, i, Py_None);
      continue;
    }
    PyList_SET_ITEM(l, i, py_shape_tuple(p, nd));
    p += nd;
  }
  return l;
}

// ---- Python -> C marshalling (thread-local result storage) -------------

struct StrStore {
  std::vector<std::string> s;
  std::vector<const char*> p;
};

// Store a python list of str into `st`; returns 0 and fills size/array,
// or -1 on type error.
int store_strlist(StrStore* st, PyObject* list, int* out_size,
                  const char*** out_array) {
  PyObject* seq = PySequence_Fast(list, "expected a list of strings");
  if (!seq) { set_error(py_error_string()); return -1; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  st->s.clear();
  st->p.clear();
  st->s.reserve(n);
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* it = PySequence_Fast_GET_ITEM(seq, i);
    const char* c = PyUnicode_Check(it) ? PyUnicode_AsUTF8(it) : "";
    st->s.emplace_back(c ? c : "");
  }
  for (auto& x : st->s) st->p.push_back(x.c_str());
  Py_DECREF(seq);
  *out_size = static_cast<int>(n);
  *out_array = st->p.data();
  return 0;
}

struct ShapeStore {
  std::vector<int> ndims;
  std::vector<int64_t> data;
};

// Store a python list of tuples (or None, encoded ndim=-1) into `st`.
int store_shapelist(ShapeStore* st, PyObject* list, int* out_size,
                    const int** out_ndims, const int64_t** out_data) {
  PyObject* seq = PySequence_Fast(list, "expected a list of shapes");
  if (!seq) { set_error(py_error_string()); return -1; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  st->ndims.clear();
  st->data.clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* t = PySequence_Fast_GET_ITEM(seq, i);
    if (t == Py_None) {
      st->ndims.push_back(-1);  // unknown shape (partial inference)
      continue;
    }
    PyObject* ts = PySequence_Fast(t, "shape must be a tuple");
    if (!ts) {
      Py_DECREF(seq);
      set_error(py_error_string());
      return -1;
    }
    Py_ssize_t nd = PySequence_Fast_GET_SIZE(ts);
    st->ndims.push_back(static_cast<int>(nd));
    for (Py_ssize_t j = 0; j < nd; ++j) {
      st->data.push_back(
          PyLong_AsLongLong(PySequence_Fast_GET_ITEM(ts, j)));
    }
    Py_DECREF(ts);
  }
  Py_DECREF(seq);
  *out_size = static_cast<int>(n);
  *out_ndims = st->ndims.data();
  *out_data = st->data.data();
  return 0;
}

// Store new handle refs from a python list (None -> NULL handle).
int store_handlelist(std::vector<void*>* st, PyObject* list, int* out_size,
                     void*** out_array) {
  PyObject* seq = PySequence_Fast(list, "expected a list of handles");
  if (!seq) { set_error(py_error_string()); return -1; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  st->clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    PyObject* o = PySequence_Fast_GET_ITEM(seq, i);
    if (o == Py_None) {
      st->push_back(nullptr);
    } else {
      Py_INCREF(o);
      st->push_back(o);
    }
  }
  Py_DECREF(seq);
  *out_size = static_cast<int>(n);
  *out_array = st->data();
  return 0;
}

thread_local StrStore tls_names;
thread_local std::string tls_str;        // single-string returns
thread_local std::string tls_bytes;      // recordio / predict byte returns
thread_local std::vector<void*> tls_handles;
thread_local ShapeStore tls_shape_in, tls_shape_out, tls_shape_aux;

// Return a single str (or None -> nullptr) through tls_str.
int ret_string(PyObject* r, const char** out) {
  if (r == Py_None) {
    *out = nullptr;
    return 0;
  }
  const char* c = PyUnicode_AsUTF8(r);
  if (!c) { set_error(py_error_string()); return -1; }
  tls_str = c;
  *out = tls_str.c_str();
  return 0;
}

// Common pattern: call impl fn, transfer the single result object out as
// a new handle.
int call_to_handle(const char* fn, PyObject* args, void** out) {
  PyObject* r = impl_call(fn, args);
  if (!r) return -1;
  *out = r;  // transfer ownership
  return 0;
}

// Common pattern: call impl fn, discard result.
int call_void(const char* fn, PyObject* args) {
  PyObject* r = impl_call(fn, args);
  if (!r) return -1;
  Py_DECREF(r);
  return 0;
}

// Common pattern: call impl fn, return string list via tls_names.
int call_to_strlist(const char* fn, PyObject* args, int* out_size,
                    const char*** out_array) {
  PyObject* r = impl_call(fn, args);
  if (!r) return -1;
  int rc = store_strlist(&tls_names, r, out_size, out_array);
  Py_DECREF(r);
  return rc;
}

// Common pattern: call impl fn, return int.
int call_to_int(const char* fn, PyObject* args, int* out) {
  PyObject* r = impl_call(fn, args);
  if (!r) return -1;
  *out = static_cast<int>(PyLong_AsLong(r));
  Py_DECREF(r);
  if (PyErr_Occurred()) { set_error(py_error_string()); return -1; }
  return 0;
}

PyObject* handle_obj(void* h) {
  PyObject* o = static_cast<PyObject*>(h);
  Py_INCREF(o);
  return o;
}

}  // namespace

// ---------------------------------------------------------------- lifecycle

// Boot an interpreter when hosted by a non-Python program (reference
// `src/initialize.cc` library init). extra_sys_path may be NULL; pass the
// repo root when mxnet_tpu is not on the default sys.path.
bool g_booted_here = false;

MXTPU_API int MXTpuInit(const char* extra_sys_path) {
  bool booted_here = !Py_IsInitialized();
  if (booted_here) {
    Py_InitializeEx(0);
    g_booted_here = true;
  }
  int rc = 0;
  {
    GILGuard gil;
    if (extra_sys_path && *extra_sys_path) {
      PyObject* sys_path = PySys_GetObject("path");  // borrowed
      PyObject* p = PyUnicode_FromString(extra_sys_path);
      if (sys_path && p) PyList_Insert(sys_path, 0, p);
      Py_XDECREF(p);
    }
    if (runtime_module() == nullptr) {
      set_error(py_error_string());
      rc = -1;
    }
  }
  if (booted_here) {
    // Py_InitializeEx leaves this thread holding the GIL; release it —
    // on success AND failure — so GILGuard can acquire from ANY host
    // thread (incl. an MXTpuInit retry with a corrected sys path)
    PyEval_SaveThread();
  }
  return rc;
}

MXTPU_API const char* MXGetLastError() { return g_last_error.c_str(); }

MXTPU_API int MXGetVersion(int* out) {
  GILGuard gil;
  PyObject* mod = runtime_module();
  if (!mod) { set_error(py_error_string()); return -1; }
  PyObject* v = PyObject_GetAttrString(mod, "__version__");
  if (!v) { set_error(py_error_string()); return -1; }
  // "maj.min.patch" -> 10000*maj + 100*min + patch (reference MXNET_VERSION)
  const char* s = PyUnicode_AsUTF8(v);
  int maj = 0, min = 0, patch = 0;
  if (s) sscanf(s, "%d.%d.%d", &maj, &min, &patch);
  Py_DECREF(v);
  *out = maj * 10000 + min * 100 + patch;
  return 0;
}

MXTPU_API int MXNotifyShutdown() {
  // Drain outstanding device work (reference MXNotifyShutdown waits the
  // engine). Where MXTpuInit booted the interpreter, finalize it too, as
  // a Python process does before it exits: the runtime's own exit hooks
  // release its backends and join its threads, which otherwise race the
  // process's static destructors after main() returns (a segfault after
  // the host's last line, one exit in four with a compile cache on disk).
  // No entry point may be called afterwards.
  int rc = MXNDArrayWaitAll();
  if (g_booted_here && Py_IsInitialized()) {
    g_booted_here = false;
    PyGILState_Ensure();
    if (Py_FinalizeEx() != 0 && rc == 0) rc = -1;
  }
  return rc;
}

MXTPU_API int MXRandomSeed(int seed) {
  GILGuard gil;
  return call_void("random_seed", Py_BuildValue("(i)", seed));
}

MXTPU_API int MXSetNumOMPThreads(int num) {
  omp_set_num_threads(num);
  return 0;
}

MXTPU_API int MXGetGPUCount(int* out) {
  GILGuard gil;
  return call_to_int("device_count", PyTuple_New(0), out);
}

MXTPU_API int MXLibInfoFeatures(const char*** out_names,
                                const int** out_enabled, int* out_size) {
  GILGuard gil;
  static thread_local std::vector<int> enabled;
  PyObject* r = impl_call("lib_info_features", PyTuple_New(0));
  if (!r) return -1;
  PyObject* names = PyTuple_GetItem(r, 0);
  PyObject* flags = PyTuple_GetItem(r, 1);
  int n = 0;
  if (store_strlist(&tls_names, names, &n, out_names) != 0) {
    Py_DECREF(r);
    return -1;
  }
  PyObject* seq = PySequence_Fast(flags, "flags");
  enabled.clear();
  for (Py_ssize_t i = 0; i < PySequence_Fast_GET_SIZE(seq); ++i) {
    enabled.push_back(
        static_cast<int>(PyLong_AsLong(PySequence_Fast_GET_ITEM(seq, i))));
  }
  Py_DECREF(seq);
  Py_DECREF(r);
  *out_enabled = enabled.data();
  *out_size = n;
  return 0;
}

MXTPU_API int MXIsNumpyShape(int* out) {
  GILGuard gil;
  return call_to_int("is_np_shape", PyTuple_New(0), out);
}

MXTPU_API int MXSetIsNumpyShape(int is_np_shape, int* prev) {
  GILGuard gil;
  int p = 0;
  if (call_to_int("set_np_shape", Py_BuildValue("(i)", is_np_shape),
                  &p) != 0) {
    return -1;
  }
  if (prev) *prev = p;
  return 0;
}

// ------------------------------------------------------------------ ndarray

MXTPU_API int MXNDArrayCreate(const int64_t* shape, int ndim,
                              const char* dtype, NDArrayHandle* out) {
  return MXNDArrayCreateEx(shape, ndim, dtype, nullptr, out);
}

MXTPU_API int MXNDArrayCreateEx(const int64_t* shape, int ndim,
                                const char* dtype, const char* ctx,
                                NDArrayHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, py_shape_tuple(shape, ndim));
  PyTuple_SET_ITEM(args, 1,
                   PyUnicode_FromString(dtype ? dtype : "float32"));
  PyTuple_SET_ITEM(args, 2, py_str_or_none(ctx));
  return call_to_handle("ndarray_create", args, out);
}

MXTPU_API int MXNDArrayFree(NDArrayHandle handle) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(handle));
  return 0;
}

MXTPU_API int MXNDArrayGetShape(NDArrayHandle handle, int* out_ndim,
                                int64_t* out_shape, int max_ndim) {
  GILGuard gil;
  PyObject* arr = static_cast<PyObject*>(handle);
  PyObject* shp = PyObject_GetAttrString(arr, "shape");
  if (!shp) { set_error(py_error_string()); return -1; }
  Py_ssize_t n = PyTuple_Size(shp);
  if (n > max_ndim) { Py_DECREF(shp); set_error("shape buffer too small");
    return -1; }
  *out_ndim = static_cast<int>(n);
  for (Py_ssize_t i = 0; i < n; ++i) {
    out_shape[i] = PyLong_AsLongLong(PyTuple_GET_ITEM(shp, i));
  }
  Py_DECREF(shp);
  return 0;
}

MXTPU_API int MXNDArrayGetDType(NDArrayHandle handle, const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("ndarray_dtype",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXNDArrayGetContext(NDArrayHandle handle, const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("ndarray_ctx",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXNDArrayGetStorageType(NDArrayHandle handle,
                                      const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("ndarray_storage_type",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXNDArrayReshape(NDArrayHandle handle, int ndim,
                               const int64_t* dims, NDArrayHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(2);
  PyTuple_SET_ITEM(args, 0, handle_obj(handle));
  PyTuple_SET_ITEM(args, 1, py_shape_tuple(dims, ndim));
  return call_to_handle("ndarray_reshape", args, out);
}

MXTPU_API int MXNDArraySlice(NDArrayHandle handle, int64_t begin,
                             int64_t end, NDArrayHandle* out) {
  GILGuard gil;
  PyObject* args = Py_BuildValue("(OLL)", static_cast<PyObject*>(handle),
                                 static_cast<long long>(begin),
                                 static_cast<long long>(end));
  return call_to_handle("ndarray_slice", args, out);
}

MXTPU_API int MXNDArrayAt(NDArrayHandle handle, int64_t idx,
                          NDArrayHandle* out) {
  GILGuard gil;
  PyObject* args = Py_BuildValue("(OL)", static_cast<PyObject*>(handle),
                                 static_cast<long long>(idx));
  return call_to_handle("ndarray_at", args, out);
}

MXTPU_API int MXNDArrayDetach(NDArrayHandle handle, NDArrayHandle* out) {
  GILGuard gil;
  return call_to_handle(
      "ndarray_detach", PyTuple_Pack(1, static_cast<PyObject*>(handle)),
      out);
}

MXTPU_API int MXNDArrayGetGrad(NDArrayHandle handle, NDArrayHandle* out) {
  GILGuard gil;
  PyObject* r = impl_call("ndarray_grad",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  if (r == Py_None) {
    Py_DECREF(r);
    *out = nullptr;
    return 0;
  }
  *out = r;
  return 0;
}

// Blocking host<->device copies, fp32 (reference MXNDArraySyncCopyFromCPU /
// SyncCopyToCPU, `src/c_api/c_api.cc`). Size is the element count.
MXTPU_API int MXNDArraySyncCopyFromCPU(NDArrayHandle handle,
                                       const float* data, int64_t size) {
  GILGuard gil;
  PyObject* arr = static_cast<PyObject*>(handle);
  PyObject* np = PyImport_ImportModule("numpy");
  if (!np) { set_error(py_error_string()); return -1; }
  // build a numpy array viewing the host buffer, then assign via x[:] = v
  PyObject* mv = PyMemoryView_FromMemory(
      reinterpret_cast<char*>(const_cast<float*>(data)),
      size * static_cast<int64_t>(sizeof(float)), PyBUF_READ);
  PyObject* flat = PyObject_CallMethod(np, "frombuffer", "Os", mv, "float32");
  Py_DECREF(mv);
  Py_DECREF(np);
  if (!flat) { set_error(py_error_string()); return -1; }
  PyObject* shp = PyObject_GetAttrString(arr, "shape");
  PyObject* view = PyObject_CallMethod(flat, "reshape", "O", shp);
  Py_DECREF(flat);
  Py_DECREF(shp);
  if (!view) { set_error(py_error_string()); return -1; }
  // OWNED copy: jax's CPU backend may alias a numpy buffer zero-copy, and
  // `view` wraps the CALLER'S memory — aliasing it would leave the stored
  // array pointing into a buffer the C host frees/reuses (observed as
  // order-dependent zeros in the round-5 ABI tests)
  PyObject* shaped = PyObject_CallMethod(view, "copy", nullptr);
  Py_DECREF(view);
  if (!shaped) { set_error(py_error_string()); return -1; }
  PyObject* slice = PySlice_New(nullptr, nullptr, nullptr);
  int rc = PyObject_SetItem(arr, slice, shaped);
  Py_DECREF(slice);
  Py_DECREF(shaped);
  if (rc != 0) { set_error(py_error_string()); return -1; }
  return 0;
}

MXTPU_API int MXNDArraySyncCopyToCPU(NDArrayHandle handle, float* data,
                                     int64_t size) {
  GILGuard gil;
  PyObject* arr = static_cast<PyObject*>(handle);
  PyObject* host = PyObject_CallMethod(arr, "asnumpy", nullptr);
  if (!host) { set_error(py_error_string()); return -1; }
  PyObject* f32 = PyObject_CallMethod(host, "astype", "s", "float32");
  Py_DECREF(host);
  if (!f32) { set_error(py_error_string()); return -1; }
  PyObject* flat = PyObject_CallMethod(f32, "ravel", nullptr);
  Py_DECREF(f32);
  if (!flat) { set_error(py_error_string()); return -1; }
  Py_buffer view;
  if (PyObject_GetBuffer(flat, &view, PyBUF_CONTIG_RO) != 0) {
    Py_DECREF(flat);
    set_error(py_error_string());
    return -1;
  }
  int64_t n = view.len / static_cast<int64_t>(sizeof(float));
  if (n > size) {
    PyBuffer_Release(&view);
    Py_DECREF(flat);
    set_error("destination buffer too small");
    return -1;
  }
  std::memcpy(data, view.buf, view.len);
  PyBuffer_Release(&view);
  Py_DECREF(flat);
  return 0;
}

MXTPU_API int MXNDArrayWaitToRead(NDArrayHandle handle) {
  GILGuard gil;
  return call_void("ndarray_wait_to_read",
                   PyTuple_Pack(1, static_cast<PyObject*>(handle)));
}

MXTPU_API int MXNDArrayWaitAll() {
  GILGuard gil;
  PyObject* mod = ndarray_module();
  if (!mod) { set_error(py_error_string()); return -1; }
  PyObject* r = PyObject_CallMethod(mod, "waitall", nullptr);
  if (!r) { set_error(py_error_string()); return -1; }
  Py_DECREF(r);
  return 0;
}

MXTPU_API int MXNDArraySave(const char* fname, int num_args,
                            NDArrayHandle* args, const char** keys) {
  GILGuard gil;
  PyObject* a = PyTuple_New(3);
  PyTuple_SET_ITEM(a, 0, PyUnicode_FromString(fname));
  PyTuple_SET_ITEM(a, 1, py_handlelist(args, num_args));
  if (keys) {
    PyTuple_SET_ITEM(a, 2, py_strlist(keys, num_args));
  } else {
    Py_INCREF(Py_None);
    PyTuple_SET_ITEM(a, 2, Py_None);
  }
  return call_void("ndarray_save", a);
}

MXTPU_API int MXNDArrayLoad(const char* fname, int* out_size,
                            NDArrayHandle** out_arr, int* out_name_size,
                            const char*** out_names) {
  GILGuard gil;
  PyObject* r = impl_call("ndarray_load", Py_BuildValue("(s)", fname));
  if (!r) return -1;
  PyObject* names = PyTuple_GetItem(r, 0);
  PyObject* arrays = PyTuple_GetItem(r, 1);
  int rc = store_strlist(&tls_names, names, out_name_size, out_names);
  if (rc == 0) {
    rc = store_handlelist(&tls_handles, arrays, out_size,
                          reinterpret_cast<void***>(out_arr));
  }
  Py_DECREF(r);
  return rc;
}

// ---------------------------------------------------------------- operators

// Invoke a registered operator by name (reference MXImperativeInvokeEx,
// `src/c_api/c_api_ndarray.cc:138`). kwargs_json is a JSON object of
// non-tensor parameters (the reference passes const char** keys/vals from
// its generated frontends; JSON keeps the ABI small). Outputs are returned
// as new handles in out_array (capacity *num_outputs, updated to actual).
MXTPU_API int MXImperativeInvoke(const char* op_name, NDArrayHandle* inputs,
                                 int num_inputs, const char* kwargs_json,
                                 NDArrayHandle* out_array, int* num_outputs) {
  GILGuard gil;
  PyObject* reg = registry_module();
  if (!reg) { set_error(py_error_string()); return -1; }
  PyObject* op = PyObject_CallMethod(reg, "get_op", "s", op_name);
  if (!op) { set_error(py_error_string()); return -1; }
  if (op == Py_None) {
    Py_DECREF(op);
    set_error(std::string("unknown operator: ") + op_name);
    return -1;
  }
  PyObject* args = PyTuple_New(num_inputs);
  for (int i = 0; i < num_inputs; ++i) {
    PyObject* a = static_cast<PyObject*>(inputs[i]);
    Py_INCREF(a);
    PyTuple_SET_ITEM(args, i, a);
  }
  PyObject* kwargs = nullptr;
  if (kwargs_json && *kwargs_json) {
    PyObject* json = PyImport_ImportModule("json");
    if (json) {
      kwargs = PyObject_CallMethod(json, "loads", "s", kwargs_json);
      Py_DECREF(json);
    }
    if (!kwargs) {
      Py_DECREF(args);
      Py_DECREF(op);
      set_error(py_error_string());
      return -1;
    }
  }
  PyObject* res = PyObject_Call(op, args, kwargs);
  Py_DECREF(args);
  Py_XDECREF(kwargs);
  Py_DECREF(op);
  if (!res) { set_error(py_error_string()); return -1; }
  int cap = *num_outputs;
  if (PyTuple_Check(res) || PyList_Check(res)) {
    PyObject* seq = PySequence_Fast(res, "op output");
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (n > cap) {
      Py_DECREF(seq);
      Py_DECREF(res);
      set_error("output buffer too small");
      return -1;
    }
    for (Py_ssize_t i = 0; i < n; ++i) {
      PyObject* o = PySequence_Fast_GET_ITEM(seq, i);
      Py_INCREF(o);
      out_array[i] = o;
    }
    *num_outputs = static_cast<int>(n);
    Py_DECREF(seq);
    Py_DECREF(res);
  } else {
    if (cap < 1) { Py_DECREF(res); set_error("output buffer too small");
      return -1; }
    out_array[0] = res;  // transfer ownership
    *num_outputs = 1;
  }
  return 0;
}

// Registry listing (reference MXListAllOpNames, `src/c_api/c_api.cc`).
// Returned pointers stay valid until the next call on the same thread.
MXTPU_API int MXListAllOpNames(int* out_size, const char*** out_array) {
  GILGuard gil;
  static thread_local StrStore ops_store;
  PyObject* reg = registry_module();
  if (!reg) { set_error(py_error_string()); return -1; }
  PyObject* names = PyObject_CallMethod(reg, "list_ops", nullptr);
  if (!names) { set_error(py_error_string()); return -1; }
  int rc = store_strlist(&ops_store, names, out_size, out_array);
  Py_DECREF(names);
  return rc;
}

// ----------------------------------------------------------------- autograd

MXTPU_API int MXAutogradSetIsRecording(int is_recording, int* prev) {
  GILGuard gil;
  int p = 0;
  if (call_to_int("autograd_set_recording",
                  Py_BuildValue("(i)", is_recording), &p) != 0) {
    return -1;
  }
  if (prev) *prev = p;
  return 0;
}

MXTPU_API int MXAutogradSetIsTraining(int is_training, int* prev) {
  GILGuard gil;
  int p = 0;
  if (call_to_int("autograd_set_training",
                  Py_BuildValue("(i)", is_training), &p) != 0) {
    return -1;
  }
  if (prev) *prev = p;
  return 0;
}

MXTPU_API int MXAutogradIsRecording(int* out) {
  GILGuard gil;
  return call_to_int("autograd_is_recording", PyTuple_New(0), out);
}

MXTPU_API int MXAutogradIsTraining(int* out) {
  GILGuard gil;
  return call_to_int("autograd_is_training", PyTuple_New(0), out);
}

MXTPU_API int MXAutogradMarkVariables(int num_var,
                                      NDArrayHandle* var_handles,
                                      const int* grad_reqs,
                                      NDArrayHandle* grad_handles) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, py_handlelist(var_handles, num_var));
  PyObject* reqs = PyList_New(num_var);
  for (int i = 0; i < num_var; ++i) {
    PyList_SET_ITEM(reqs, i, PyLong_FromLong(grad_reqs ? grad_reqs[i] : 1));
  }
  PyTuple_SET_ITEM(args, 1, reqs);
  PyTuple_SET_ITEM(args, 2, py_handlelist(grad_handles, num_var));
  return call_void("autograd_mark_variables", args);
}

MXTPU_API int MXAutogradBackward(int num_output,
                                 NDArrayHandle* output_handles,
                                 NDArrayHandle* ograd_handles,
                                 int retain_graph) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, py_handlelist(output_handles, num_output));
  if (ograd_handles) {
    PyTuple_SET_ITEM(args, 1, py_handlelist(ograd_handles, num_output));
  } else {
    Py_INCREF(Py_None);
    PyTuple_SET_ITEM(args, 1, Py_None);
  }
  PyTuple_SET_ITEM(args, 2, PyLong_FromLong(retain_graph));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(1));  // train_mode
  return call_void("autograd_backward", args);
}

// ------------------------------------------------------------------- symbol

MXTPU_API int MXSymbolCreateVariable(const char* name, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_create_variable",
                        Py_BuildValue("(s)", name), out);
}

MXTPU_API int MXSymbolCreateAtomicSymbol(const char* op_name, int num_param,
                                         const char** keys,
                                         const char** vals,
                                         SymbolHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, PyUnicode_FromString(op_name));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_param));
  PyTuple_SET_ITEM(args, 2, py_strlist(vals, num_param));
  return call_to_handle("symbol_create_atomic", args, out);
}

MXTPU_API int MXSymbolCompose(SymbolHandle sym, const char* name,
                              int num_args, const char** keys,
                              SymbolHandle* args_h) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_str_or_none(name));
  PyTuple_SET_ITEM(args, 2, py_strlist(keys, num_args));
  PyTuple_SET_ITEM(args, 3, py_handlelist(args_h, num_args));
  return call_void("symbol_compose", args);
}

MXTPU_API int MXSymbolCreateGroup(int num_symbols, SymbolHandle* symbols,
                                  SymbolHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(1);
  PyTuple_SET_ITEM(args, 0, py_handlelist(symbols, num_symbols));
  return call_to_handle("symbol_create_group", args, out);
}

MXTPU_API int MXSymbolGetOutput(SymbolHandle sym, int index,
                                SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle(
      "symbol_get_output",
      Py_BuildValue("(Oi)", static_cast<PyObject*>(sym), index), out);
}

MXTPU_API int MXSymbolGetInternals(SymbolHandle sym, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_get_internals",
                        PyTuple_Pack(1, static_cast<PyObject*>(sym)), out);
}

MXTPU_API int MXSymbolGetName(SymbolHandle sym, const char** out,
                              int* success) {
  GILGuard gil;
  *out = nullptr;
  PyObject* r = impl_call("symbol_get_name",
                          PyTuple_Pack(1, static_cast<PyObject*>(sym)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  if (success) *success = (rc == 0 && *out != nullptr) ? 1 : 0;
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolGetNumOutputs(SymbolHandle sym, int* out) {
  GILGuard gil;
  return call_to_int("symbol_num_outputs",
                     PyTuple_Pack(1, static_cast<PyObject*>(sym)), out);
}

MXTPU_API int MXSymbolListArguments(SymbolHandle sym, int* out_size,
                                    const char*** out_array) {
  GILGuard gil;
  return call_to_strlist("symbol_list_arguments",
                         PyTuple_Pack(1, static_cast<PyObject*>(sym)),
                         out_size, out_array);
}

MXTPU_API int MXSymbolListOutputs(SymbolHandle sym, int* out_size,
                                  const char*** out_array) {
  GILGuard gil;
  return call_to_strlist("symbol_list_outputs",
                         PyTuple_Pack(1, static_cast<PyObject*>(sym)),
                         out_size, out_array);
}

MXTPU_API int MXSymbolListAuxiliaryStates(SymbolHandle sym, int* out_size,
                                          const char*** out_array) {
  GILGuard gil;
  return call_to_strlist("symbol_list_aux",
                         PyTuple_Pack(1, static_cast<PyObject*>(sym)),
                         out_size, out_array);
}

MXTPU_API int MXSymbolInferShape(SymbolHandle sym, int num_args,
                                 const char** keys, const int* ndims,
                                 const int64_t* shape_data, int partial,
                                 int* in_size, const int** in_ndims,
                                 const int64_t** in_data,
                                 int* out_size, const int** out_ndims,
                                 const int64_t** out_data,
                                 int* aux_size, const int** aux_ndims,
                                 const int64_t** aux_data,
                                 int* complete) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_args));
  PyTuple_SET_ITEM(args, 2, py_shapelist(ndims, shape_data, num_args));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(partial));
  PyObject* r = impl_call("symbol_infer_shape", args);
  if (!r) return -1;
  int rc = store_shapelist(&tls_shape_in, PyTuple_GetItem(r, 0), in_size,
                           in_ndims, in_data);
  if (rc == 0) {
    rc = store_shapelist(&tls_shape_out, PyTuple_GetItem(r, 1), out_size,
                         out_ndims, out_data);
  }
  if (rc == 0) {
    rc = store_shapelist(&tls_shape_aux, PyTuple_GetItem(r, 2), aux_size,
                         aux_ndims, aux_data);
  }
  if (rc == 0 && complete) {
    *complete = PyObject_IsTrue(PyTuple_GetItem(r, 3));
  }
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolSaveToJSON(SymbolHandle sym, const char** out_json) {
  GILGuard gil;
  PyObject* r = impl_call("symbol_tojson",
                          PyTuple_Pack(1, static_cast<PyObject*>(sym)));
  if (!r) return -1;
  int rc = ret_string(r, out_json);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolCreateFromJSON(const char* json, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_from_json", Py_BuildValue("(s)", json),
                        out);
}

MXTPU_API int MXSymbolSaveToFile(SymbolHandle sym, const char* fname) {
  GILGuard gil;
  return call_void(
      "symbol_save_file",
      Py_BuildValue("(Os)", static_cast<PyObject*>(sym), fname));
}

MXTPU_API int MXSymbolCreateFromFile(const char* fname, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_load_file", Py_BuildValue("(s)", fname),
                        out);
}

MXTPU_API int MXSymbolCopy(SymbolHandle sym, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_copy",
                        PyTuple_Pack(1, static_cast<PyObject*>(sym)), out);
}

MXTPU_API int MXSymbolGetAttr(SymbolHandle sym, const char* key,
                              const char** out, int* success) {
  GILGuard gil;
  *out = nullptr;
  PyObject* r = impl_call(
      "symbol_get_attr",
      Py_BuildValue("(Os)", static_cast<PyObject*>(sym), key));
  if (!r) return -1;
  int rc = ret_string(r, out);
  if (success) *success = (rc == 0 && *out != nullptr) ? 1 : 0;
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolSetAttr(SymbolHandle sym, const char* key,
                              const char* value) {
  GILGuard gil;
  return call_void(
      "symbol_set_attr",
      Py_BuildValue("(Oss)", static_cast<PyObject*>(sym), key, value));
}

MXTPU_API int MXSymbolPrint(SymbolHandle sym, const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("symbol_print",
                          PyTuple_Pack(1, static_cast<PyObject*>(sym)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolFree(SymbolHandle sym) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(sym));
  return 0;
}

// ----------------------------------------------------------------- executor

MXTPU_API int MXExecutorSimpleBind(SymbolHandle sym, const char* ctx,
                                   const char* grad_req, int num_provided,
                                   const char** keys, const int* ndims,
                                   const int64_t* shape_data,
                                   ExecutorHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(5);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_str_or_none(ctx));
  PyTuple_SET_ITEM(args, 2, py_str_or_none(grad_req));
  PyTuple_SET_ITEM(args, 3, py_strlist(keys, num_provided));
  PyTuple_SET_ITEM(args, 4,
                   py_shapelist(ndims, shape_data, num_provided));
  return call_to_handle("executor_simple_bind", args, out);
}

MXTPU_API int MXExecutorForward(ExecutorHandle exec, int is_train) {
  GILGuard gil;
  return call_void(
      "executor_forward",
      Py_BuildValue("(Oi)", static_cast<PyObject*>(exec), is_train));
}

MXTPU_API int MXExecutorBackward(ExecutorHandle exec, int num_ograds,
                                 NDArrayHandle* ograd_handles) {
  GILGuard gil;
  PyObject* args = PyTuple_New(2);
  PyTuple_SET_ITEM(args, 0, handle_obj(exec));
  if (ograd_handles && num_ograds > 0) {
    PyTuple_SET_ITEM(args, 1, py_handlelist(ograd_handles, num_ograds));
  } else {
    Py_INCREF(Py_None);
    PyTuple_SET_ITEM(args, 1, Py_None);
  }
  return call_void("executor_backward", args);
}

namespace {
int executor_array_group(const char* fn, ExecutorHandle exec,
                         int* out_size, NDArrayHandle** out) {
  PyObject* r = impl_call(fn, PyTuple_Pack(1,
                                           static_cast<PyObject*>(exec)));
  if (!r) return -1;
  int rc = store_handlelist(&tls_handles, r, out_size,
                            reinterpret_cast<void***>(out));
  Py_DECREF(r);
  return rc;
}
}  // namespace

MXTPU_API int MXExecutorOutputs(ExecutorHandle exec, int* out_size,
                                NDArrayHandle** out) {
  GILGuard gil;
  return executor_array_group("executor_outputs", exec, out_size, out);
}

MXTPU_API int MXExecutorArgArrays(ExecutorHandle exec, int* out_size,
                                  NDArrayHandle** out) {
  GILGuard gil;
  return executor_array_group("executor_arg_arrays", exec, out_size, out);
}

MXTPU_API int MXExecutorGradArrays(ExecutorHandle exec, int* out_size,
                                   NDArrayHandle** out) {
  GILGuard gil;
  return executor_array_group("executor_grad_arrays", exec, out_size, out);
}

MXTPU_API int MXExecutorAuxArrays(ExecutorHandle exec, int* out_size,
                                  NDArrayHandle** out) {
  GILGuard gil;
  return executor_array_group("executor_aux_arrays", exec, out_size, out);
}

MXTPU_API int MXExecutorArgNames(ExecutorHandle exec, int* out_size,
                                 const char*** out_array) {
  GILGuard gil;
  return call_to_strlist("executor_arg_names",
                         PyTuple_Pack(1, static_cast<PyObject*>(exec)),
                         out_size, out_array);
}

MXTPU_API int MXExecutorPrint(ExecutorHandle exec, const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("executor_print",
                          PyTuple_Pack(1, static_cast<PyObject*>(exec)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXExecutorFree(ExecutorHandle exec) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(exec));
  return 0;
}

// ------------------------------------------------------------------ kvstore

MXTPU_API int MXKVStoreCreate(const char* type, KVStoreHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(1);
  PyTuple_SET_ITEM(args, 0, py_str_or_none(type));
  return call_to_handle("kvstore_create", args, out);
}

MXTPU_API int MXKVStoreInit(KVStoreHandle kv, int num, const char** keys,
                            NDArrayHandle* vals) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num));
  PyTuple_SET_ITEM(args, 2, py_handlelist(vals, num));
  return call_void("kvstore_init", args);
}

MXTPU_API int MXKVStorePush(KVStoreHandle kv, int num, const char** keys,
                            NDArrayHandle* vals, int priority) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num));
  PyTuple_SET_ITEM(args, 2, py_handlelist(vals, num));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(priority));
  return call_void("kvstore_push", args);
}

MXTPU_API int MXKVStorePull(KVStoreHandle kv, int num, const char** keys,
                            NDArrayHandle* outs, int priority) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num));
  PyTuple_SET_ITEM(args, 2, py_handlelist(outs, num));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(priority));
  return call_void("kvstore_pull", args);
}

MXTPU_API int MXKVStoreGetType(KVStoreHandle kv, const char** out) {
  GILGuard gil;
  PyObject* r = impl_call("kvstore_type",
                          PyTuple_Pack(1, static_cast<PyObject*>(kv)));
  if (!r) return -1;
  int rc = ret_string(r, out);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXKVStoreGetRank(KVStoreHandle kv, int* out) {
  GILGuard gil;
  return call_to_int("kvstore_rank",
                     PyTuple_Pack(1, static_cast<PyObject*>(kv)), out);
}

MXTPU_API int MXKVStoreGetGroupSize(KVStoreHandle kv, int* out) {
  GILGuard gil;
  return call_to_int("kvstore_group_size",
                     PyTuple_Pack(1, static_cast<PyObject*>(kv)), out);
}

MXTPU_API int MXKVStoreBarrier(KVStoreHandle kv) {
  GILGuard gil;
  return call_void("kvstore_barrier",
                   PyTuple_Pack(1, static_cast<PyObject*>(kv)));
}

MXTPU_API int MXKVStoreGetNumDeadNode(KVStoreHandle kv, int node_id,
                                      int* out) {
  GILGuard gil;
  (void)node_id;  // single-view liveness (reference queries per node id)
  return call_to_int("kvstore_num_dead_node",
                     PyTuple_Pack(1, static_cast<PyObject*>(kv)), out);
}

MXTPU_API int MXKVStoreSetGradientCompression(KVStoreHandle kv,
                                              int num_params,
                                              const char** keys,
                                              const char** vals) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_params));
  PyTuple_SET_ITEM(args, 2, py_strlist(vals, num_params));
  return call_void("kvstore_set_gradient_compression", args);
}

MXTPU_API int MXKVStoreFree(KVStoreHandle kv) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(kv));
  return 0;
}

// ------------------------------------------------------------------- dataio

MXTPU_API int MXListDataIters(int* out_size, const char*** out_array) {
  GILGuard gil;
  return call_to_strlist("list_data_iters", PyTuple_New(0), out_size,
                         out_array);
}

MXTPU_API int MXDataIterCreateIter(const char* name, int num_param,
                                   const char** keys, const char** vals,
                                   DataIterHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, PyUnicode_FromString(name));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_param));
  PyTuple_SET_ITEM(args, 2, py_strlist(vals, num_param));
  return call_to_handle("dataiter_create", args, out);
}

MXTPU_API int MXDataIterNext(DataIterHandle iter, int* out) {
  GILGuard gil;
  return call_to_int("dataiter_next",
                     PyTuple_Pack(1, static_cast<PyObject*>(iter)), out);
}

MXTPU_API int MXDataIterBeforeFirst(DataIterHandle iter) {
  GILGuard gil;
  return call_void("dataiter_before_first",
                   PyTuple_Pack(1, static_cast<PyObject*>(iter)));
}

MXTPU_API int MXDataIterGetData(DataIterHandle iter, NDArrayHandle* out) {
  GILGuard gil;
  return call_to_handle("dataiter_get_data",
                        PyTuple_Pack(1, static_cast<PyObject*>(iter)),
                        out);
}

MXTPU_API int MXDataIterGetLabel(DataIterHandle iter, NDArrayHandle* out) {
  GILGuard gil;
  return call_to_handle("dataiter_get_label",
                        PyTuple_Pack(1, static_cast<PyObject*>(iter)),
                        out);
}

MXTPU_API int MXDataIterGetPadNum(DataIterHandle iter, int* out) {
  GILGuard gil;
  return call_to_int("dataiter_get_pad",
                     PyTuple_Pack(1, static_cast<PyObject*>(iter)), out);
}

MXTPU_API int MXDataIterFree(DataIterHandle iter) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(iter));
  return 0;
}

// ----------------------------------------------------------------- recordio

MXTPU_API int MXRecordIOWriterCreate(const char* uri, RecordIOHandle* out) {
  GILGuard gil;
  return call_to_handle("recordio_writer_create",
                        Py_BuildValue("(s)", uri), out);
}

MXTPU_API int MXRecordIOWriterWriteRecord(RecordIOHandle handle,
                                          const char* buf, int64_t size) {
  GILGuard gil;
  PyObject* args = PyTuple_New(2);
  PyTuple_SET_ITEM(args, 0, handle_obj(handle));
  PyTuple_SET_ITEM(args, 1,
                   PyBytes_FromStringAndSize(buf,
                                             static_cast<Py_ssize_t>(size)));
  return call_void("recordio_writer_write", args);
}

MXTPU_API int MXRecordIOWriterTell(RecordIOHandle handle, int64_t* out) {
  GILGuard gil;
  PyObject* r = impl_call("recordio_writer_tell",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  *out = PyLong_AsLongLong(r);
  Py_DECREF(r);
  if (PyErr_Occurred()) { set_error(py_error_string()); return -1; }
  return 0;
}

MXTPU_API int MXRecordIOWriterFree(RecordIOHandle handle) {
  GILGuard gil;
  call_void("recordio_close",
            PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  Py_XDECREF(static_cast<PyObject*>(handle));
  return 0;
}

MXTPU_API int MXRecordIOReaderCreate(const char* uri, RecordIOHandle* out) {
  GILGuard gil;
  return call_to_handle("recordio_reader_create",
                        Py_BuildValue("(s)", uri), out);
}

MXTPU_API int MXRecordIOReaderReadRecord(RecordIOHandle handle,
                                         const char** out_buf,
                                         int64_t* out_size) {
  GILGuard gil;
  PyObject* r = impl_call("recordio_reader_read",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  if (r == Py_None) {
    Py_DECREF(r);
    *out_buf = nullptr;
    *out_size = -1;  // end of file
    return 0;
  }
  char* b = nullptr;
  Py_ssize_t n = 0;
  if (PyBytes_AsStringAndSize(r, &b, &n) != 0) {
    Py_DECREF(r);
    set_error(py_error_string());
    return -1;
  }
  tls_bytes.assign(b, static_cast<size_t>(n));
  Py_DECREF(r);
  *out_buf = tls_bytes.data();
  *out_size = static_cast<int64_t>(tls_bytes.size());
  return 0;
}

MXTPU_API int MXRecordIOReaderSeek(RecordIOHandle handle, int64_t pos) {
  GILGuard gil;
  return call_void(
      "recordio_reader_seek",
      Py_BuildValue("(OL)", static_cast<PyObject*>(handle),
                    static_cast<long long>(pos)));
}

MXTPU_API int MXRecordIOReaderTell(RecordIOHandle handle, int64_t* out) {
  GILGuard gil;
  PyObject* r = impl_call("recordio_reader_tell",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  *out = PyLong_AsLongLong(r);
  Py_DECREF(r);
  if (PyErr_Occurred()) { set_error(py_error_string()); return -1; }
  return 0;
}

MXTPU_API int MXRecordIOReaderFree(RecordIOHandle handle) {
  GILGuard gil;
  call_void("recordio_close",
            PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  Py_XDECREF(static_cast<PyObject*>(handle));
  return 0;
}

// ------------------------------------------------------------------ predict

MXTPU_API int MXPredCreate(const char* symbol_json, const void* param_bytes,
                           int64_t param_size, const char* ctx,
                           int num_input, const char** input_keys,
                           const int* input_ndims,
                           const int64_t* input_shape_data,
                           PredictorHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(5);
  PyTuple_SET_ITEM(args, 0, PyUnicode_FromString(symbol_json));
  if (param_bytes && param_size > 0) {
    PyTuple_SET_ITEM(
        args, 1,
        PyBytes_FromStringAndSize(static_cast<const char*>(param_bytes),
                                  static_cast<Py_ssize_t>(param_size)));
  } else {
    Py_INCREF(Py_None);
    PyTuple_SET_ITEM(args, 1, Py_None);
  }
  PyTuple_SET_ITEM(args, 2, py_str_or_none(ctx));
  PyTuple_SET_ITEM(args, 3, py_strlist(input_keys, num_input));
  PyTuple_SET_ITEM(args, 4,
                   py_shapelist(input_ndims, input_shape_data, num_input));
  return call_to_handle("pred_create", args, out);
}

MXTPU_API int MXPredSetInput(PredictorHandle pred, const char* name,
                             const float* data, int64_t size) {
  GILGuard gil;
  PyObject* bytes = PyBytes_FromStringAndSize(
      reinterpret_cast<const char*>(data),
      static_cast<Py_ssize_t>(size * sizeof(float)));
  if (!bytes) { set_error(py_error_string()); return -1; }
  PyObject* r = PyObject_CallMethod(static_cast<PyObject*>(pred),
                                    "set_input", "sO", name, bytes);
  Py_DECREF(bytes);
  if (!r) { set_error(py_error_string()); return -1; }
  Py_DECREF(r);
  return 0;
}

MXTPU_API int MXPredForward(PredictorHandle pred) {
  GILGuard gil;
  PyObject* r = PyObject_CallMethod(static_cast<PyObject*>(pred),
                                    "forward", nullptr);
  if (!r) { set_error(py_error_string()); return -1; }
  Py_DECREF(r);
  return 0;
}

MXTPU_API int MXPredGetOutputShape(PredictorHandle pred, int index,
                                   const int64_t** out_shape,
                                   int* out_ndim) {
  GILGuard gil;
  static thread_local std::vector<int64_t> shape_store;
  PyObject* r = PyObject_CallMethod(static_cast<PyObject*>(pred),
                                    "output_shape", "i", index);
  if (!r) { set_error(py_error_string()); return -1; }
  PyObject* seq = PySequence_Fast(r, "shape");
  if (!seq) { Py_DECREF(r); set_error(py_error_string()); return -1; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
  shape_store.clear();
  for (Py_ssize_t i = 0; i < n; ++i) {
    shape_store.push_back(
        PyLong_AsLongLong(PySequence_Fast_GET_ITEM(seq, i)));
  }
  Py_DECREF(seq);
  Py_DECREF(r);
  *out_shape = shape_store.data();
  *out_ndim = static_cast<int>(n);
  return 0;
}

MXTPU_API int MXPredGetOutput(PredictorHandle pred, int index, float* data,
                              int64_t size) {
  GILGuard gil;
  PyObject* r = PyObject_CallMethod(static_cast<PyObject*>(pred),
                                    "output", "i", index);
  if (!r) { set_error(py_error_string()); return -1; }
  char* b = nullptr;
  Py_ssize_t n = 0;
  if (PyBytes_AsStringAndSize(r, &b, &n) != 0) {
    Py_DECREF(r);
    set_error(py_error_string());
    return -1;
  }
  if (n > static_cast<Py_ssize_t>(size * sizeof(float))) {
    Py_DECREF(r);
    set_error("output buffer too small");
    return -1;
  }
  std::memcpy(data, b, static_cast<size_t>(n));
  Py_DECREF(r);
  return 0;
}

MXTPU_API int MXPredReshape(PredictorHandle pred, int num_input,
                            const char** input_keys, const int* input_ndims,
                            const int64_t* input_shape_data) {
  GILGuard gil;
  PyObject* keys = py_strlist(input_keys, num_input);
  PyObject* shapes = py_shapelist(input_ndims, input_shape_data, num_input);
  PyObject* r = PyObject_CallMethod(static_cast<PyObject*>(pred),
                                    "reshape", "OO", keys, shapes);
  Py_DECREF(keys);
  Py_DECREF(shapes);
  if (!r) { set_error(py_error_string()); return -1; }
  Py_DECREF(r);
  return 0;
}

MXTPU_API int MXPredFree(PredictorHandle pred) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(pred));
  return 0;
}

// ----------------------------------------------------------------- profiler

MXTPU_API int MXSetProfilerState(const char* state) {
  GILGuard gil;
  return call_void("profiler_set_state", Py_BuildValue("(s)", state));
}

MXTPU_API int MXSetProfilerConfig(int num_params, const char** keys,
                                  const char** vals) {
  GILGuard gil;
  PyObject* args = PyTuple_New(2);
  PyTuple_SET_ITEM(args, 0, py_strlist(keys, num_params));
  PyTuple_SET_ITEM(args, 1, py_strlist(vals, num_params));
  return call_void("profiler_set_config", args);
}

MXTPU_API int MXDumpProfile(int finished) {
  GILGuard gil;
  return call_void("profiler_dump", Py_BuildValue("(i)", finished));
}

// =================================================================
// Round-5 surface: binding-codegen introspection, cached ops, monitor
// callbacks, kvstore updater/pushpull, Ex/64 variants, profiler tail.
// Reference names: c_api.h:1076 (ListAtomicSymbolCreators), :1090
// (GetAtomicSymbolInfo), :2205 (SetMonitorCallback), :1280 (CachedOp).
// =================================================================

namespace {
// extra TLS string stores: GetAtomicSymbolInfo returns three string
// lists that must stay valid simultaneously
thread_local StrStore tls_names2;
thread_local StrStore tls_names3;
// creator handles: interned op-name strings, owned for process lifetime
std::vector<PyObject*>* g_creators = nullptr;
}  // namespace

MXTPU_API int MXSymbolListAtomicSymbolCreators(int* out_size,
                                               AtomicSymbolCreator** out) {
  GILGuard gil;
  static thread_local std::vector<void*> creator_store;
  if (!g_creators) {
    // impl_call may yield the GIL: build into a LOCAL vector and only
    // install it if no other thread won the race meanwhile
    PyObject* r = impl_call("atomic_symbol_creators", PyTuple_New(0));
    if (!r) return -1;
    auto* built = new std::vector<PyObject*>();
    PyObject* seq = PySequence_Fast(r, "creator list");
    if (!seq) {
      delete built;
      Py_DECREF(r);
      set_error(py_error_string());
      return -1;
    }
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    for (Py_ssize_t i = 0; i < n; ++i) {
      PyObject* s = PySequence_Fast_GET_ITEM(seq, i);
      Py_INCREF(s);
      built->push_back(s);
    }
    Py_DECREF(seq);
    Py_DECREF(r);
    if (!g_creators) {   // GIL held from here on: safe check-and-set
      g_creators = built;
    } else {
      for (PyObject* s : *built) Py_DECREF(s);
      delete built;
    }
  }
  creator_store.assign(g_creators->begin(), g_creators->end());
  *out_size = static_cast<int>(creator_store.size());
  *out = reinterpret_cast<AtomicSymbolCreator*>(creator_store.data());
  return 0;
}

MXTPU_API int MXSymbolGetAtomicSymbolName(AtomicSymbolCreator creator,
                                          const char** name) {
  GILGuard gil;
  const char* c = PyUnicode_AsUTF8(static_cast<PyObject*>(creator));
  if (!c) { set_error(py_error_string()); return -1; }
  *name = c;  // creator strings are immortal (g_creators)
  return 0;
}

MXTPU_API int MXSymbolGetAtomicSymbolInfo(
    AtomicSymbolCreator creator, const char** name,
    const char** description, int* num_args, const char*** arg_names,
    const char*** arg_type_infos, const char*** arg_descriptions,
    const char** key_var_num_args, const char** return_type) {
  GILGuard gil;
  static thread_local std::string s_name, s_desc, s_kv, s_ret;
  PyObject* r = impl_call(
      "atomic_symbol_info",
      PyTuple_Pack(1, static_cast<PyObject*>(creator)));
  if (!r) return -1;
  int rc = 0;
  const char* c;
  c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 0));
  s_name = c ? c : "";
  c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 1));
  s_desc = c ? c : "";
  c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 5));
  s_kv = c ? c : "";
  c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 6));
  s_ret = c ? c : "";
  if (name) *name = s_name.c_str();
  if (description) *description = s_desc.c_str();
  if (key_var_num_args) *key_var_num_args = s_kv.c_str();
  if (return_type) *return_type = s_ret.c_str();
  int n1 = 0, n2 = 0, n3 = 0;
  rc = store_strlist(&tls_names, PyTuple_GetItem(r, 2), &n1, arg_names);
  if (rc == 0) {
    rc = store_strlist(&tls_names2, PyTuple_GetItem(r, 3), &n2,
                       arg_type_infos);
  }
  if (rc == 0) {
    rc = store_strlist(&tls_names3, PyTuple_GetItem(r, 4), &n3,
                       arg_descriptions);
  }
  if (num_args) *num_args = n1;
  Py_DECREF(r);
  return rc;
}

// -------------------------------------------------------- symbol extras

MXTPU_API int MXSymbolInferType(SymbolHandle sym, int num_args,
                                const char** keys, const char** types,
                                int partial, int* in_size,
                                const char*** in_types, int* out_size,
                                const char*** out_types, int* aux_size,
                                const char*** aux_types, int* complete) {
  GILGuard gil;
  PyObject* args = PyTuple_New(4);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_args));
  PyTuple_SET_ITEM(args, 2, py_strlist(types, num_args));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(partial));
  PyObject* r = impl_call("symbol_infer_type", args);
  if (!r) return -1;
  int rc = store_strlist(&tls_names, PyTuple_GetItem(r, 0), in_size,
                         in_types);
  if (rc == 0) {
    rc = store_strlist(&tls_names2, PyTuple_GetItem(r, 1), out_size,
                       out_types);
  }
  if (rc == 0) {
    rc = store_strlist(&tls_names3, PyTuple_GetItem(r, 2), aux_size,
                       aux_types);
  }
  if (rc == 0 && complete) {
    *complete = PyObject_IsTrue(PyTuple_GetItem(r, 3));
  }
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXSymbolInferTypePartial(SymbolHandle sym, int num_args,
                                       const char** keys,
                                       const char** types, int* in_size,
                                       const char*** in_types,
                                       int* out_size,
                                       const char*** out_types,
                                       int* aux_size,
                                       const char*** aux_types,
                                       int* complete) {
  return MXSymbolInferType(sym, num_args, keys, types, 1, in_size,
                           in_types, out_size, out_types, aux_size,
                           aux_types, complete);
}

MXTPU_API int MXSymbolGetChildren(SymbolHandle sym, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_get_children",
                        PyTuple_Pack(1, static_cast<PyObject*>(sym)), out);
}

MXTPU_API int MXSymbolRemoveAmpCast(SymbolHandle sym, SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("symbol_remove_amp_cast",
                        PyTuple_Pack(1, static_cast<PyObject*>(sym)), out);
}

// 64/Ex shape variants: this ABI's canonical shapes are ALREADY int64
// (header preamble); the variants alias the canonical entry so bindings
// generated against the reference names link unchanged.
MXTPU_API int MXSymbolInferShapeEx(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int partial, int* in_size,
    const int** in_ndims, const int64_t** in_data, int* out_size,
    const int** out_ndims, const int64_t** out_data, int* aux_size,
    const int** aux_ndims, const int64_t** aux_data, int* complete) {
  return MXSymbolInferShape(sym, num_args, keys, ndims, shape_data,
                            partial, in_size, in_ndims, in_data, out_size,
                            out_ndims, out_data, aux_size, aux_ndims,
                            aux_data, complete);
}

MXTPU_API int MXSymbolInferShape64(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int partial, int* in_size,
    const int** in_ndims, const int64_t** in_data, int* out_size,
    const int** out_ndims, const int64_t** out_data, int* aux_size,
    const int** aux_ndims, const int64_t** aux_data, int* complete) {
  return MXSymbolInferShape(sym, num_args, keys, ndims, shape_data,
                            partial, in_size, in_ndims, in_data, out_size,
                            out_ndims, out_data, aux_size, aux_ndims,
                            aux_data, complete);
}

MXTPU_API int MXSymbolInferShapePartial(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int* in_size, const int** in_ndims,
    const int64_t** in_data, int* out_size, const int** out_ndims,
    const int64_t** out_data, int* aux_size, const int** aux_ndims,
    const int64_t** aux_data, int* complete) {
  return MXSymbolInferShape(sym, num_args, keys, ndims, shape_data, 1,
                            in_size, in_ndims, in_data, out_size,
                            out_ndims, out_data, aux_size, aux_ndims,
                            aux_data, complete);
}

MXTPU_API int MXSymbolInferShapePartial64(
    SymbolHandle sym, int num_args, const char** keys, const int* ndims,
    const int64_t* shape_data, int* in_size, const int** in_ndims,
    const int64_t** in_data, int* out_size, const int** out_ndims,
    const int64_t** out_data, int* aux_size, const int** aux_ndims,
    const int64_t** aux_data, int* complete) {
  return MXSymbolInferShape(sym, num_args, keys, ndims, shape_data, 1,
                            in_size, in_ndims, in_data, out_size,
                            out_ndims, out_data, aux_size, aux_ndims,
                            aux_data, complete);
}

// ---------------------------------------------------------- executor

MXTPU_API int MXExecutorSetMonitorCallback(ExecutorHandle exec,
                                           ExecutorMonitorCallback cb,
                                           void* cb_data) {
  GILGuard gil;
  return call_void(
      "executor_set_monitor",
      Py_BuildValue("(OKKi)", static_cast<PyObject*>(exec),
                    (unsigned long long)(uintptr_t)cb,
                    (unsigned long long)(uintptr_t)cb_data, 0));
}

MXTPU_API int MXExecutorSetMonitorCallbackEX(ExecutorHandle exec,
                                             ExecutorMonitorCallback cb,
                                             void* cb_data,
                                             int monitor_all) {
  GILGuard gil;
  return call_void(
      "executor_set_monitor",
      Py_BuildValue("(OKKi)", static_cast<PyObject*>(exec),
                    (unsigned long long)(uintptr_t)cb,
                    (unsigned long long)(uintptr_t)cb_data, monitor_all));
}

MXTPU_API int MXExecutorReshape(int partial_shaping, int allow_up_sizing,
                                const char* ctx, int num_provided,
                                const char** keys, const int* ndims,
                                const int64_t* shape_data,
                                ExecutorHandle shared_exec,
                                ExecutorHandle* out) {
  GILGuard gil;
  (void)partial_shaping; (void)allow_up_sizing; (void)ctx;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, handle_obj(shared_exec));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_provided));
  PyTuple_SET_ITEM(args, 2,
                   py_shapelist(ndims, shape_data, num_provided));
  return call_to_handle("executor_reshape", args, out);
}

MXTPU_API int MXExecutorReshapeEx(int partial_shaping, int allow_up_sizing,
                                  const char* ctx, int num_provided,
                                  const char** keys, const int* ndims,
                                  const int64_t* shape_data,
                                  ExecutorHandle shared_exec,
                                  ExecutorHandle* out) {
  return MXExecutorReshape(partial_shaping, allow_up_sizing, ctx,
                           num_provided, keys, ndims, shape_data,
                           shared_exec, out);
}

MXTPU_API int MXExecutorGetOptimizedSymbol(ExecutorHandle exec,
                                           SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle("executor_optimized_symbol",
                        PyTuple_Pack(1, static_cast<PyObject*>(exec)),
                        out);
}

MXTPU_API int MXExecutorSimpleBindEx(SymbolHandle sym, const char* ctx,
                                     const char* grad_req,
                                     int num_provided, const char** keys,
                                     const int* ndims,
                                     const int64_t* shape_data,
                                     ExecutorHandle* out) {
  return MXExecutorSimpleBind(sym, ctx, grad_req, num_provided, keys,
                              ndims, shape_data, out);
}

MXTPU_API int MXExecutorSimpleBindEx64(SymbolHandle sym, const char* ctx,
                                       const char* grad_req,
                                       int num_provided,
                                       const char** keys, const int* ndims,
                                       const int64_t* shape_data,
                                       ExecutorHandle* out) {
  return MXExecutorSimpleBind(sym, ctx, grad_req, num_provided, keys,
                              ndims, shape_data, out);
}

// ---------------------------------------------------------- cached op

MXTPU_API int MXCreateCachedOp(SymbolHandle sym, CachedOpHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_strlist(nullptr, 0));
  PyTuple_SET_ITEM(args, 2, py_strlist(nullptr, 0));
  return call_to_handle("cached_op_create", args, out);
}

MXTPU_API int MXCreateCachedOpEx(SymbolHandle sym, int num_flags,
                                 const char** keys, const char** vals,
                                 CachedOpHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(3);
  PyTuple_SET_ITEM(args, 0, handle_obj(sym));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num_flags));
  PyTuple_SET_ITEM(args, 2, py_strlist(vals, num_flags));
  return call_to_handle("cached_op_create", args, out);
}

MXTPU_API int MXInvokeCachedOp(CachedOpHandle handle, int num_inputs,
                               NDArrayHandle* inputs, int* num_outputs,
                               NDArrayHandle** outputs) {
  GILGuard gil;
  PyObject* args = PyTuple_New(2);
  PyTuple_SET_ITEM(args, 0, handle_obj(handle));
  PyTuple_SET_ITEM(args, 1, py_handlelist(inputs, num_inputs));
  PyObject* r = impl_call("cached_op_invoke", args);
  if (!r) return -1;
  int rc = store_handlelist(&tls_handles, r, num_outputs, outputs);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXInvokeCachedOpEx(CachedOpHandle handle, int num_inputs,
                                 NDArrayHandle* inputs, int* num_outputs,
                                 NDArrayHandle** outputs,
                                 const int** out_stypes) {
  static thread_local std::vector<int> stypes;
  int rc = MXInvokeCachedOp(handle, num_inputs, inputs, num_outputs,
                            outputs);
  if (rc == 0 && out_stypes) {
    stypes.assign(*num_outputs, 0);  // dense
    *out_stypes = stypes.data();
  }
  return rc;
}

MXTPU_API int MXFreeCachedOp(CachedOpHandle handle) {
  GILGuard gil;
  Py_XDECREF(static_cast<PyObject*>(handle));
  return 0;
}

// ---------------------------------------------------------- autograd

MXTPU_API int MXAutogradBackwardEx(int num_output,
                                   NDArrayHandle* output_handles,
                                   NDArrayHandle* ograd_handles,
                                   int num_variables,
                                   NDArrayHandle* var_handles,
                                   int retain_graph, int create_graph,
                                   int is_train, NDArrayHandle** grad_handles,
                                   int** grad_stypes) {
  GILGuard gil;
  static thread_local std::vector<int> stypes;
  PyObject* args = PyTuple_New(6);
  PyTuple_SET_ITEM(args, 0, py_handlelist(output_handles, num_output));
  if (ograd_handles) {
    PyTuple_SET_ITEM(args, 1, py_handlelist(ograd_handles, num_output));
  } else {
    Py_INCREF(Py_None);
    PyTuple_SET_ITEM(args, 1, Py_None);
  }
  PyTuple_SET_ITEM(args, 2, py_handlelist(var_handles, num_variables));
  PyTuple_SET_ITEM(args, 3, PyLong_FromLong(retain_graph));
  PyTuple_SET_ITEM(args, 4, PyLong_FromLong(create_graph));
  PyTuple_SET_ITEM(args, 5, PyLong_FromLong(is_train));
  PyObject* r = impl_call("autograd_backward_ex", args);
  if (!r) return -1;
  int n = 0;
  int rc = store_handlelist(&tls_handles, r, &n, grad_handles);
  if (rc == 0 && grad_stypes) {
    stypes.assign(n, 0);
    *grad_stypes = stypes.data();
  }
  Py_DECREF(r);
  return rc;
}

// ----------------------------------------------------------- kvstore

MXTPU_API int MXKVStoreIsWorkerNode(int* out) {
  *out = 1;  // every process is a worker on a TPU mesh (SURVEY §3.5)
  return 0;
}

MXTPU_API int MXKVStoreIsServerNode(int* out) {
  *out = 0;
  return 0;
}

MXTPU_API int MXKVStoreIsSchedulerNode(int* out) {
  *out = 0;
  return 0;
}

MXTPU_API int MXKVStoreSetBarrierBeforeExit(KVStoreHandle kv,
                                            int do_barrier) {
  (void)kv; (void)do_barrier;  // exit barrier rides jax.distributed
  return 0;
}

MXTPU_API int MXKVStoreRunServer(KVStoreHandle kv, void* controller,
                                 void* cb_data) {
  (void)kv; (void)controller; (void)cb_data;
  set_error("no server role on a TPU mesh: dist_tpu_sync reduces over "
            "ICI collectives (SURVEY §3.5); workers call train directly");
  return -1;
}

MXTPU_API int MXKVStoreSendCommmandToServers(KVStoreHandle kv, int head,
                                             const char* body) {
  (void)kv; (void)head; (void)body;  // no servers to command
  return 0;
}

MXTPU_API int MXKVStoreSetUpdater(KVStoreHandle kv, MXKVStoreUpdater cb,
                                  void* cb_data) {
  GILGuard gil;
  return call_void(
      "kvstore_set_updater",
      Py_BuildValue("(OKK)", static_cast<PyObject*>(kv),
                    (unsigned long long)(uintptr_t)cb,
                    (unsigned long long)(uintptr_t)cb_data));
}

MXTPU_API int MXKVStoreSetUpdaterEx(KVStoreHandle kv, MXKVStoreUpdater cb,
                                    MXKVStoreStrUpdater str_cb,
                                    void* cb_data) {
  (void)str_cb;  // string-keyed callbacks route through the int path
  return MXKVStoreSetUpdater(kv, cb, cb_data);
}

MXTPU_API int MXKVStorePushPull(KVStoreHandle kv, int num,
                                const char** keys, NDArrayHandle* ins,
                                NDArrayHandle* outs, int priority) {
  GILGuard gil;
  PyObject* args = PyTuple_New(5);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num));
  PyTuple_SET_ITEM(args, 2, py_handlelist(ins, num));
  PyTuple_SET_ITEM(args, 3, py_handlelist(outs, num));
  PyTuple_SET_ITEM(args, 4, PyLong_FromLong(priority));
  return call_void("kvstore_pushpull", args);
}

MXTPU_API int MXKVStorePushPullEx(KVStoreHandle kv, int num,
                                  const char** keys, NDArrayHandle* ins,
                                  NDArrayHandle* outs, int priority) {
  return MXKVStorePushPull(kv, num, keys, ins, outs, priority);
}

MXTPU_API int MXKVStorePullRowSparse(KVStoreHandle kv, int num,
                                     const char** keys,
                                     NDArrayHandle* outs,
                                     NDArrayHandle* row_ids,
                                     int priority) {
  GILGuard gil;
  PyObject* args = PyTuple_New(5);
  PyTuple_SET_ITEM(args, 0, handle_obj(kv));
  PyTuple_SET_ITEM(args, 1, py_strlist(keys, num));
  PyTuple_SET_ITEM(args, 2, py_handlelist(outs, num));
  PyTuple_SET_ITEM(args, 3, py_handlelist(row_ids, num));
  PyTuple_SET_ITEM(args, 4, PyLong_FromLong(priority));
  return call_void("kvstore_pull_row_sparse", args);
}

MXTPU_API int MXKVStorePullRowSparseEx(KVStoreHandle kv, int num,
                                       const char** keys,
                                       NDArrayHandle* outs,
                                       NDArrayHandle* row_ids,
                                       int priority) {
  return MXKVStorePullRowSparse(kv, num, keys, outs, row_ids, priority);
}

// string-keyed "Ex" aliases: this ABI's canonical keys are ALREADY
// strings (header preamble)
MXTPU_API int MXKVStoreInitEx(KVStoreHandle kv, int num, const char** keys,
                              NDArrayHandle* vals) {
  return MXKVStoreInit(kv, num, keys, vals);
}

MXTPU_API int MXKVStorePushEx(KVStoreHandle kv, int num, const char** keys,
                              NDArrayHandle* vals, int priority) {
  return MXKVStorePush(kv, num, keys, vals, priority);
}

MXTPU_API int MXKVStorePullEx(KVStoreHandle kv, int num, const char** keys,
                              NDArrayHandle* outs, int priority) {
  return MXKVStorePull(kv, num, keys, outs, priority);
}

// ----------------------------------------------------------- ndarray

MXTPU_API int MXNDArrayCreateNone(NDArrayHandle* out) {
  GILGuard gil;
  return call_to_handle("ndarray_create_none", PyTuple_New(0), out);
}

MXTPU_API int MXNDArrayWaitToWrite(NDArrayHandle handle) {
  GILGuard gil;
  return call_void("ndarray_wait_to_write",
                   PyTuple_Pack(1, static_cast<PyObject*>(handle)));
}

MXTPU_API int MXNDArraySaveRawBytes(NDArrayHandle handle, size_t* out_size,
                                    const char** out_buf) {
  GILGuard gil;
  static thread_local std::string buf;
  PyObject* r = impl_call("ndarray_save_raw_bytes",
                          PyTuple_Pack(1, static_cast<PyObject*>(handle)));
  if (!r) return -1;
  char* data = nullptr;
  Py_ssize_t n = 0;
  if (PyBytes_AsStringAndSize(r, &data, &n) != 0) {
    Py_DECREF(r);
    set_error(py_error_string());
    return -1;
  }
  buf.assign(data, n);
  Py_DECREF(r);
  *out_size = buf.size();
  *out_buf = buf.data();
  return 0;
}

MXTPU_API int MXNDArrayLoadFromRawBytes(const void* buf, size_t size,
                                        NDArrayHandle* out) {
  GILGuard gil;
  PyObject* args = PyTuple_New(1);
  PyTuple_SET_ITEM(args, 0, PyBytes_FromStringAndSize(
      static_cast<const char*>(buf), size));
  return call_to_handle("ndarray_load_from_raw_bytes", args, out);
}

MXTPU_API int MXNDArrayLoadFromBuffer(const void* buf, size_t size,
                                      int* out_size, NDArrayHandle** out,
                                      int* out_name_size,
                                      const char*** out_names) {
  GILGuard gil;
  PyObject* args = PyTuple_New(1);
  PyTuple_SET_ITEM(args, 0, PyBytes_FromStringAndSize(
      static_cast<const char*>(buf), size));
  PyObject* r = impl_call("ndarray_load_from_buffer", args);
  if (!r) return -1;
  int rc = store_strlist(&tls_names, PyTuple_GetItem(r, 0),
                         out_name_size, out_names);
  if (rc == 0) {
    rc = store_handlelist(&tls_handles, PyTuple_GetItem(r, 1), out_size,
                          out);
  }
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXNDArraySyncCopyFromNDArray(NDArrayHandle dst,
                                           NDArrayHandle src, int i) {
  GILGuard gil;
  (void)i;
  return call_void("ndarray_sync_copy_from",
                   PyTuple_Pack(2, static_cast<PyObject*>(dst),
                                static_cast<PyObject*>(src)));
}

MXTPU_API int MXNDArrayGetGradState(NDArrayHandle handle, int* out) {
  GILGuard gil;
  return call_to_int("ndarray_grad_state",
                     PyTuple_Pack(1, static_cast<PyObject*>(handle)), out);
}

MXTPU_API int MXNDArraySetGradState(NDArrayHandle handle, int state) {
  GILGuard gil;
  return call_void(
      "ndarray_set_grad_state",
      Py_BuildValue("(Oi)", static_cast<PyObject*>(handle), state));
}

MXTPU_API int MXShallowCopyNDArray(NDArrayHandle src, NDArrayHandle* out) {
  GILGuard gil;
  return call_to_handle("shallow_copy_ndarray",
                        PyTuple_Pack(1, static_cast<PyObject*>(src)), out);
}

MXTPU_API int MXShallowCopySymbol(SymbolHandle src, SymbolHandle* out) {
  GILGuard gil;
  PyObject* o = static_cast<PyObject*>(src);
  Py_INCREF(o);  // symbols are immutable graphs: share the object
  *out = o;
  return 0;
}

// int64/Ex aliases over the canonical (already-64-bit) entries
MXTPU_API int MXNDArrayGetShapeEx(NDArrayHandle handle, int* out_ndim,
                                  int64_t* out_shape, int max_ndim) {
  return MXNDArrayGetShape(handle, out_ndim, out_shape, max_ndim);
}

MXTPU_API int MXNDArrayGetShape64(NDArrayHandle handle, int* out_ndim,
                                  int64_t* out_shape, int max_ndim) {
  return MXNDArrayGetShape(handle, out_ndim, out_shape, max_ndim);
}

MXTPU_API int MXNDArrayGetShapeEx64(NDArrayHandle handle, int* out_ndim,
                                    int64_t* out_shape, int max_ndim) {
  return MXNDArrayGetShape(handle, out_ndim, out_shape, max_ndim);
}

MXTPU_API int MXNDArrayReshape64(NDArrayHandle handle, int ndim,
                                 const int64_t* dims, int reverse,
                                 NDArrayHandle* out) {
  (void)reverse;
  return MXNDArrayReshape(handle, ndim, dims, out);
}

MXTPU_API int MXNDArraySlice64(NDArrayHandle handle, int64_t begin,
                               int64_t end, NDArrayHandle* out) {
  return MXNDArraySlice(handle, begin, end, out);
}

MXTPU_API int MXNDArrayAt64(NDArrayHandle handle, int64_t idx,
                            NDArrayHandle* out) {
  return MXNDArrayAt(handle, idx, out);
}

MXTPU_API int MXNDArrayCreateEx64(const int64_t* shape, int ndim,
                                  const char* dtype, const char* ctx,
                                  int delay_alloc, NDArrayHandle* out) {
  (void)delay_alloc;  // XLA allocates lazily regardless
  return MXNDArrayCreateEx(shape, ndim, dtype, ctx, out);
}

MXTPU_API int MXImperativeInvokeEx(const char* op_name,
                                   NDArrayHandle* inputs, int num_inputs,
                                   const char* kwargs_json,
                                   NDArrayHandle* out_array,
                                   int* num_outputs,
                                   const int** out_stypes) {
  static thread_local std::vector<int> stypes;
  int rc = MXImperativeInvoke(op_name, inputs, num_inputs, kwargs_json,
                              out_array, num_outputs);
  if (rc == 0 && out_stypes) {
    stypes.assign(*num_outputs, 0);  // dense
    *out_stypes = stypes.data();
  }
  return rc;
}

// ------------------------------------------------------ misc / profiler

MXTPU_API int MXStorageEmptyCache(const char* ctx) {
  GILGuard gil;
  return call_void("storage_empty_cache",
                   Py_BuildValue("(s)", ctx ? ctx : ""));
}

MXTPU_API int MXEngineSetBulkSize(int bulk_size, int* prev_bulk_size) {
  GILGuard gil;
  return call_to_int("engine_set_bulk_size",
                     Py_BuildValue("(i)", bulk_size), prev_bulk_size);
}

MXTPU_API int MXRandomSeedContext(int seed, const char* ctx) {
  GILGuard gil;
  return call_void("random_seed_context",
                   Py_BuildValue("(is)", seed, ctx ? ctx : ""));
}

MXTPU_API int MXLoadLib(const char* path, unsigned verbose) {
  GILGuard gil;
  (void)verbose;
  return call_void("load_lib", Py_BuildValue("(s)", path));
}

MXTPU_API int MXProfilePause(int paused) {
  GILGuard gil;
  return call_void("profiler_pause", Py_BuildValue("(i)", paused));
}

MXTPU_API int MXProcessProfilePause(int paused, int profile_process) {
  (void)profile_process;
  return MXProfilePause(paused);
}

MXTPU_API int MXSetProcessProfilerState(int state, int profile_process) {
  GILGuard gil;
  (void)profile_process;
  return call_void("profiler_set_state",
                   Py_BuildValue("(s)", state ? "run" : "stop"));
}

MXTPU_API int MXSetProcessProfilerConfig(int num_params, const char** keys,
                                         const char** vals,
                                         KVStoreHandle kv) {
  (void)kv;
  return MXSetProfilerConfig(num_params, keys, vals);
}

MXTPU_API int MXDumpProcessProfile(int finished, int profile_process,
                                   KVStoreHandle kv) {
  (void)profile_process; (void)kv;
  return MXDumpProfile(finished);
}

MXTPU_API int MXAggregateProfileStatsPrint(const char** out_str, int reset) {
  GILGuard gil;
  PyObject* r = impl_call("profiler_aggregate_stats",
                          Py_BuildValue("(isss)", reset, "table", "total",
                                        ""));
  if (!r) return -1;
  int rc = ret_string(r, out_str);
  Py_DECREF(r);
  return rc;
}

MXTPU_API int MXAggregateProfileStatsPrintEx(const char** out_str,
                                             int reset, int format,
                                             int sort_by, int ascending) {
  (void)format; (void)sort_by; (void)ascending;
  return MXAggregateProfileStatsPrint(out_str, reset);
}

// ------------------------------------------------- subgraph / data iter

MXTPU_API int MXGenBackendSubgraph(SymbolHandle sym, const char* backend,
                                   SymbolHandle* out) {
  GILGuard gil;
  return call_to_handle(
      "gen_backend_subgraph",
      Py_BuildValue("(Os)", static_cast<PyObject*>(sym), backend), out);
}

MXTPU_API int MXOptimizeForBackend(SymbolHandle sym, const char* backend,
                                   SymbolHandle* out) {
  return MXGenBackendSubgraph(sym, backend, out);
}

MXTPU_API int MXDataIterGetIterInfo(const char* iter_name,
                                    const char** name,
                                    const char** description,
                                    int* num_args,
                                    const char*** arg_names,
                                    const char*** arg_type_infos,
                                    const char*** arg_descriptions) {
  GILGuard gil;
  static thread_local std::string s_name, s_desc;
  PyObject* r = impl_call("dataiter_info",
                          Py_BuildValue("(s)", iter_name));
  if (!r) return -1;
  const char* c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 0));
  s_name = c ? c : "";
  c = PyUnicode_AsUTF8(PyTuple_GetItem(r, 1));
  s_desc = c ? c : "";
  if (name) *name = s_name.c_str();
  if (description) *description = s_desc.c_str();
  int n1 = 0;
  int rc = store_strlist(&tls_names, PyTuple_GetItem(r, 2), &n1,
                         arg_names);
  if (rc == 0) {
    int n2 = 0;
    rc = store_strlist(&tls_names2, PyTuple_GetItem(r, 3), &n2,
                       arg_type_infos);
  }
  if (rc == 0) {
    int n3 = 0;
    rc = store_strlist(&tls_names3, PyTuple_GetItem(r, 4), &n3,
                       arg_descriptions);
  }
  if (num_args) *num_args = n1;
  Py_DECREF(r);
  return rc;
}
