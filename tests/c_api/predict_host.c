/* C-host inference: load an exported model (-symbol.json + .params file
 * CONTENT) through the predict ABI and run a forward pass — the reference
 * deployment story (c_predict_api.cc MXPredCreate/SetInput/Forward/
 * GetOutput; example/image-classification/predict-cpp).
 *
 * Usage: predict_host <repo_root> <symbol.json path> <params path>
 * Prints C_API_PREDICT_OK on success. */
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

static char* slurp(const char* path, long* out_len) {
  FILE* f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  char* buf = (char*)malloc((size_t)n + 1);
  if (fread(buf, 1, (size_t)n, f) != (size_t)n) {
    fclose(f);
    free(buf);
    return NULL;
  }
  fclose(f);
  buf[n] = 0;
  if (out_len) *out_len = n;
  return buf;
}

int main(int argc, char** argv) {
  if (argc < 4) {
    fprintf(stderr, "usage: predict_host <repo> <symbol.json> <params>\n");
    return 2;
  }
  CHECK(MXTpuInit(argv[1]));

  long json_len = 0, param_len = 0;
  char* json = slurp(argv[2], &json_len);
  char* params = slurp(argv[3], &param_len);
  if (!json || !params) {
    fprintf(stderr, "cannot read model files\n");
    return 1;
  }

  PredictorHandle pred;
  {
    const char* keys[] = {"data", "softmax_label"};
    int ndims[] = {4, 1};
    int64_t shapes[] = {2, 1, 12, 12, 2};
    CHECK(MXPredCreate(json, params, param_len, "cpu", 2, keys, ndims,
                       shapes, &pred));
  }

  /* deterministic input */
  float input[2 * 1 * 12 * 12];
  for (int i = 0; i < 2 * 144; ++i) {
    input[i] = sinf(0.05f * (float)i);
  }
  CHECK(MXPredSetInput(pred, "data", input, 2 * 144));
  CHECK(MXPredForward(pred));

  const int64_t* oshape = NULL;
  int ondim = 0;
  CHECK(MXPredGetOutputShape(pred, 0, &oshape, &ondim));
  if (ondim != 2 || oshape[0] != 2 || oshape[1] != 10) {
    fprintf(stderr, "bad output shape (%d dims)\n", ondim);
    return 1;
  }

  float out[2 * 10];
  CHECK(MXPredGetOutput(pred, 0, out, 20));
  /* softmax rows must each sum to 1 */
  for (int r = 0; r < 2; ++r) {
    float s = 0.0f;
    for (int c = 0; c < 10; ++c) s += out[r * 10 + c];
    if (fabsf(s - 1.0f) > 1e-3f) {
      fprintf(stderr, "row %d prob sum %.4f\n", r, s);
      return 1;
    }
  }

  /* reshape to a new batch size and run again */
  {
    const char* keys[] = {"data", "softmax_label"};
    int ndims[] = {4, 1};
    int64_t shapes[] = {4, 1, 12, 12, 4};
    CHECK(MXPredReshape(pred, 2, keys, ndims, shapes));
    float big[4 * 144];
    memset(big, 0, sizeof(big));
    CHECK(MXPredSetInput(pred, "data", big, 4 * 144));
    CHECK(MXPredForward(pred));
    CHECK(MXPredGetOutputShape(pred, 0, &oshape, &ondim));
    if (oshape[0] != 4) {
      fprintf(stderr, "reshape failed\n");
      return 1;
    }
  }

  CHECK(MXPredFree(pred));
  free(json);
  free(params);
  CHECK(MXNotifyShutdown());
  printf("C_API_PREDICT_OK\n");
  return 0;
}
