/* Standalone C host driving the runtime through the flat ABI — the
 * language-binding scenario the reference's c_api.h exists for (a Scala/R/
 * Julia frontend is "this program", mechanically generated). Built and run
 * by tests/test_c_api.py. */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#include "mxtpu_c.h"

#define CHECK(stmt)                                                   \
  do {                                                                \
    if ((stmt) != 0) {                                                \
      fprintf(stderr, "FAIL %s: %s\n", #stmt, MXGetLastError());      \
      return 1;                                                       \
    }                                                                 \
  } while (0)

int main(int argc, char** argv) {
  const char* repo = argc > 1 ? argv[1] : ".";
  CHECK(MXTpuInit(repo));

  int version = 0;
  CHECK(MXGetVersion(&version));
  printf("version=%d\n", version);

  int n_ops = 0;
  const char** names = NULL;
  CHECK(MXListAllOpNames(&n_ops, &names));
  printf("n_ops=%d\n", n_ops);
  if (n_ops < 400) {
    fprintf(stderr, "expected a populated op registry\n");
    return 1;
  }

  int64_t shape[2] = {2, 3};
  NDArrayHandle x = NULL;
  CHECK(MXNDArrayCreate(shape, 2, "float32", &x));

  float host[6] = {-2.0f, -1.0f, 0.0f, 1.0f, 2.0f, 3.0f};
  CHECK(MXNDArraySyncCopyFromCPU(x, host, 6));

  NDArrayHandle outs[4];
  int n_out = 4;
  CHECK(MXImperativeInvoke("relu", &x, 1, NULL, outs, &n_out));
  if (n_out != 1) {
    fprintf(stderr, "relu should have one output\n");
    return 1;
  }

  int ndim = 0;
  int64_t oshape[8];
  CHECK(MXNDArrayGetShape(outs[0], &ndim, oshape, 8));
  if (ndim != 2 || oshape[0] != 2 || oshape[1] != 3) {
    fprintf(stderr, "bad output shape\n");
    return 1;
  }

  float back[6];
  CHECK(MXNDArraySyncCopyToCPU(outs[0], back, 6));
  float want[6] = {0.0f, 0.0f, 0.0f, 1.0f, 2.0f, 3.0f};
  for (int i = 0; i < 6; ++i) {
    if (back[i] != want[i]) {
      fprintf(stderr, "relu mismatch at %d: %f != %f\n", i, back[i], want[i]);
      return 1;
    }
  }

  /* kwargs path: sum over axis 1 */
  n_out = 4;
  NDArrayHandle souts[4];
  CHECK(MXImperativeInvoke("sum", &x, 1, "{\"axis\": 1}", souts, &n_out));
  float sums[2];
  CHECK(MXNDArraySyncCopyToCPU(souts[0], sums, 2));
  if (sums[0] != -3.0f || sums[1] != 6.0f) {
    fprintf(stderr, "sum mismatch: %f %f\n", sums[0], sums[1]);
    return 1;
  }

  CHECK(MXNDArrayWaitAll());
  CHECK(MXNDArrayFree(x));
  CHECK(MXNDArrayFree(outs[0]));
  CHECK(MXNDArrayFree(souts[0]));
  CHECK(MXNotifyShutdown());
  printf("C_API_HOST_OK\n");
  return 0;
}
