/* C-host training: build LeNet through the symbol ABI, bind an executor,
 * train on synthetic data with SGD via MXImperativeInvoke, and assert the
 * loss drops. This is the "a C host can train a model" proof the reference
 * C ABI gives its language bindings (c_api_executor.cc + the Scala/C++
 * trainers built on it).
 *
 * Also exercises: kvstore init/push/pull (the dist-training client path),
 * NDArray save/load, symbol JSON save, executor introspection.
 *
 * Usage: train_lenet <repo_root> [export_dir]
 * Prints C_API_TRAIN_OK on success. */
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include "mxtpu_c.h"

#define CHECK(x)                                                      \
  do {                                                                \
    if ((x) != 0) {                                                   \
      fprintf(stderr, "FAIL %s:%d: %s\n", __FILE__, __LINE__,         \
              MXGetLastError());                                      \
      return 1;                                                       \
    }                                                                 \
  } while (0)

/* Compose op(inputs...) with string params into a fresh symbol. */
static int make_op(const char* op, const char* name, int num_param,
                   const char** pk, const char** pv, int num_in,
                   SymbolHandle* in, SymbolHandle* out) {
  const char* empty_keys[8] = {0};
  if (MXSymbolCreateAtomicSymbol(op, num_param, pk, pv, out) != 0) return -1;
  return MXSymbolCompose(*out, name, num_in, empty_keys, in);
}

int main(int argc, char** argv) {
  CHECK(MXTpuInit(argc > 1 ? argv[1] : NULL));
  MXRandomSeed(7);

  /* ---- LeNet-ish: conv-pool-conv-pool-fc-fc-softmax on 8x1x12x12 ---- */
  SymbolHandle data, label, c1, a1, p1, fl, fc1, a2, fc2, net;
  CHECK(MXSymbolCreateVariable("data", &data));
  CHECK(MXSymbolCreateVariable("softmax_label", &label));

  {
    const char* k[] = {"num_filter", "kernel"};
    const char* v[] = {"8", "(3, 3)"};
    SymbolHandle in[] = {data};
    CHECK(make_op("Convolution", "conv1", 2, k, v, 1, in, &c1));
  }
  {
    const char* k[] = {"act_type"};
    const char* v[] = {"tanh"};
    SymbolHandle in[] = {c1};
    CHECK(make_op("Activation", "act1", 1, k, v, 1, in, &a1));
  }
  {
    const char* k[] = {"pool_type", "kernel", "stride"};
    const char* v[] = {"max", "(2, 2)", "(2, 2)"};
    SymbolHandle in[] = {a1};
    CHECK(make_op("Pooling", "pool1", 3, k, v, 1, in, &p1));
  }
  {
    SymbolHandle in[] = {p1};
    CHECK(make_op("Flatten", "flat", 0, NULL, NULL, 1, in, &fl));
  }
  {
    const char* k[] = {"num_hidden"};
    const char* v[] = {"32"};
    SymbolHandle in[] = {fl};
    CHECK(make_op("FullyConnected", "fc1", 1, k, v, 1, in, &fc1));
  }
  {
    const char* k[] = {"act_type"};
    const char* v[] = {"relu"};
    SymbolHandle in[] = {fc1};
    CHECK(make_op("Activation", "act2", 1, k, v, 1, in, &a2));
  }
  {
    const char* k[] = {"num_hidden"};
    const char* v[] = {"10"};
    SymbolHandle in[] = {a2};
    CHECK(make_op("FullyConnected", "fc2", 1, k, v, 1, in, &fc2));
  }
  {
    SymbolHandle in[] = {fc2, label};
    CHECK(make_op("SoftmaxOutput", "softmax", 0, NULL, NULL, 2, in, &net));
  }

  /* symbol introspection */
  int n_args = 0;
  const char** arg_names = NULL;
  CHECK(MXSymbolListArguments(net, &n_args, &arg_names));
  if (n_args < 8) {
    fprintf(stderr, "expected >=8 arguments, got %d\n", n_args);
    return 1;
  }
  const char* json = NULL;
  CHECK(MXSymbolSaveToJSON(net, &json));
  if (strstr(json, "conv1") == NULL) {
    fprintf(stderr, "symbol json missing node\n");
    return 1;
  }

  /* shape inference through the ABI */
  {
    const char* keys[] = {"data", "softmax_label"};
    int ndims[] = {4, 1};
    int64_t shapes[] = {8, 1, 12, 12, 8};
    int in_sz, out_sz, aux_sz, complete;
    const int *in_nd, *out_nd, *aux_nd;
    const int64_t *in_d, *out_d, *aux_d;
    CHECK(MXSymbolInferShape(net, 2, keys, ndims, shapes, 0, &in_sz,
                             &in_nd, &in_d, &out_sz, &out_nd, &out_d,
                             &aux_sz, &aux_nd, &aux_d, &complete));
    if (!complete || out_sz != 1 || out_nd[0] != 2 || out_d[0] != 8 ||
        out_d[1] != 10) {
      fprintf(stderr, "infer_shape wrong: complete=%d out=(%lld,%lld)\n",
              complete, (long long)out_d[0], (long long)out_d[1]);
      return 1;
    }
  }

  /* ---- bind ---- */
  ExecutorHandle exec;
  {
    const char* keys[] = {"data", "softmax_label"};
    int ndims[] = {4, 1};
    int64_t shapes[] = {8, 1, 12, 12, 8};
    CHECK(MXExecutorSimpleBind(net, "cpu", "write", 2, keys, ndims, shapes,
                               &exec));
  }
  int n_exec_args = 0;
  NDArrayHandle* args_arr = NULL;
  CHECK(MXExecutorArgArrays(exec, &n_exec_args, &args_arr));
  /* keep private copies: the tls pointer array is reused by later calls */
  NDArrayHandle arg_h[32];
  for (int i = 0; i < n_exec_args; ++i) arg_h[i] = args_arr[i];
  const char** exec_arg_names = NULL;
  int n_names = 0;
  CHECK(MXExecutorArgNames(exec, &n_names, &exec_arg_names));
  char names_copy[32][64];
  for (int i = 0; i < n_names; ++i) {
    strncpy(names_copy[i], exec_arg_names[i], 63);
    names_copy[i][63] = 0;
  }

  /* ---- init params (uniform +-0.3), fixed synthetic batch ---- */
  srand(13);
  float data_buf[8 * 1 * 12 * 12], label_buf[8];
  for (int i = 0; i < 8 * 144; ++i) {
    data_buf[i] = (float)rand() / (float)RAND_MAX - 0.5f;
  }
  for (int i = 0; i < 8; ++i) label_buf[i] = (float)(i % 10);

  for (int i = 0; i < n_exec_args; ++i) {
    if (strcmp(names_copy[i], "data") == 0) {
      CHECK(MXNDArraySyncCopyFromCPU(arg_h[i], data_buf, 8 * 144));
    } else if (strcmp(names_copy[i], "softmax_label") == 0) {
      CHECK(MXNDArraySyncCopyFromCPU(arg_h[i], label_buf, 8));
    } else {
      int nd = 0;
      int64_t shp[8];
      CHECK(MXNDArrayGetShape(arg_h[i], &nd, shp, 8));
      int64_t sz = 1;
      for (int j = 0; j < nd; ++j) sz *= shp[j];
      float* w = (float*)malloc(sizeof(float) * (size_t)sz);
      for (int64_t j = 0; j < sz; ++j) {
        w[j] = 0.6f * ((float)rand() / (float)RAND_MAX - 0.5f);
      }
      CHECK(MXNDArraySyncCopyFromCPU(arg_h[i], w, sz));
      free(w);
    }
  }

  /* ---- kvstore round-trip on one weight (dist-client path) ---- */
  {
    KVStoreHandle kv;
    CHECK(MXKVStoreCreate("local", &kv));
    const char* t = NULL;
    CHECK(MXKVStoreGetType(kv, &t));
    int rank = -1, size = 0;
    CHECK(MXKVStoreGetRank(kv, &rank));
    CHECK(MXKVStoreGetGroupSize(kv, &size));
    if (strcmp(t, "local") != 0 || rank != 0 || size != 1) {
      fprintf(stderr, "kvstore meta wrong\n");
      return 1;
    }
    const char* kk[] = {"w0"};
    NDArrayHandle vv[] = {arg_h[1]};
    CHECK(MXKVStoreInit(kv, 1, kk, vv));
    CHECK(MXKVStorePush(kv, 1, kk, vv, 0));
    CHECK(MXKVStorePull(kv, 1, kk, vv, 0));
    CHECK(MXKVStoreBarrier(kv));
    CHECK(MXKVStoreFree(kv));
  }

  /* ---- training loop: forward / backward / sgd_update ---- */
  float first_loss = -1.0f, last_loss = -1.0f;
  for (int epoch = 0; epoch < 30; ++epoch) {
    CHECK(MXExecutorForward(exec, 1));
    CHECK(MXExecutorBackward(exec, 0, NULL));

    int n_out = 0;
    NDArrayHandle* outs = NULL;
    CHECK(MXExecutorOutputs(exec, &n_out, &outs));
    NDArrayHandle prob = outs[0];

    float p[8 * 10];
    CHECK(MXNDArraySyncCopyToCPU(prob, p, 80));
    float loss = 0.0f;
    for (int i = 0; i < 8; ++i) {
      float pi = p[i * 10 + (int)label_buf[i]];
      loss += -logf(pi > 1e-8f ? pi : 1e-8f);
    }
    loss /= 8.0f;
    if (epoch == 0) first_loss = loss;
    last_loss = loss;
    CHECK(MXNDArrayFree(prob));

    int n_grads = 0;
    NDArrayHandle* grads_tls = NULL;
    CHECK(MXExecutorGradArrays(exec, &n_grads, &grads_tls));
    NDArrayHandle grad_h[32];
    for (int i = 0; i < n_grads; ++i) grad_h[i] = grads_tls[i];

    for (int i = 0; i < n_exec_args; ++i) {
      if (strcmp(names_copy[i], "data") == 0 ||
          strcmp(names_copy[i], "softmax_label") == 0 ||
          grad_h[i] == NULL) {
        continue;
      }
      NDArrayHandle io[2] = {arg_h[i], grad_h[i]};
      NDArrayHandle upd[2];
      int n_upd = 2;
      CHECK(MXImperativeInvoke("sgd_update", io, 2, "{\"lr\": 0.1}", upd,
                               &n_upd));
      /* write the updated weight back into the bound buffer */
      int nd = 0;
      int64_t shp[8];
      CHECK(MXNDArrayGetShape(upd[0], &nd, shp, 8));
      int64_t sz = 1;
      for (int j = 0; j < nd; ++j) sz *= shp[j];
      float* w = (float*)malloc(sizeof(float) * (size_t)sz);
      CHECK(MXNDArraySyncCopyToCPU(upd[0], w, sz));
      CHECK(MXNDArraySyncCopyFromCPU(arg_h[i], w, sz));
      free(w);
      for (int u = 0; u < n_upd; ++u) MXNDArrayFree(upd[u]);
    }
    for (int i = 0; i < n_grads; ++i) {
      if (grad_h[i]) MXNDArrayFree(grad_h[i]);
    }
  }

  printf("loss %.4f -> %.4f\n", first_loss, last_loss);
  if (!(last_loss < 0.6f * first_loss)) {
    fprintf(stderr, "loss did not drop enough\n");
    return 1;
  }

  /* ---- save params + symbol for the predict host ---- */
  {
    NDArrayHandle save_h[32];
    const char* save_k[32];
    char key_store[32][80];
    int n_save = 0;
    for (int i = 0; i < n_exec_args; ++i) {
      if (strcmp(names_copy[i], "data") == 0 ||
          strcmp(names_copy[i], "softmax_label") == 0) {
        continue;
      }
      snprintf(key_store[n_save], 80, "arg:%s", names_copy[i]);
      save_k[n_save] = key_store[n_save];
      save_h[n_save] = arg_h[i];
      ++n_save;
    }
    const char* outdir = argc > 2 ? argv[2] : "/tmp";
    char params_path[512], sym_path[512];
    snprintf(params_path, sizeof(params_path), "%s/lenet_capi.params",
             outdir);
    snprintf(sym_path, sizeof(sym_path), "%s/lenet_capi-symbol.json",
             outdir);
    CHECK(MXNDArraySave(params_path, n_save, save_h, save_k));
    CHECK(MXSymbolSaveToFile(net, sym_path));

    /* reload round-trip */
    int n_loaded = 0, n_lnames = 0;
    NDArrayHandle* loaded = NULL;
    const char** lnames = NULL;
    CHECK(MXNDArrayLoad(params_path, &n_loaded, &loaded,
                        &n_lnames, &lnames));
    if (n_loaded != n_save || n_lnames != n_save) {
      fprintf(stderr, "save/load count mismatch\n");
      return 1;
    }
    for (int i = 0; i < n_loaded; ++i) MXNDArrayFree(loaded[i]);
  }

  for (int i = 0; i < n_exec_args; ++i) MXNDArrayFree(arg_h[i]);
  CHECK(MXExecutorFree(exec));
  MXSymbolFree(net);

  CHECK(MXNotifyShutdown());
  printf("C_API_TRAIN_OK\n");
  return 0;
}
