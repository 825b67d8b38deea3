"""AllReduce bandwidth measurement over the device mesh.

Role parity: reference ``tools/bandwidth/measure.py`` (per-batch
communication-cost benchmark across kvstore types, perf.md:263). The
TPU-native comm backend is one in-graph XLA AllReduce over ICI
(SURVEY §5.8), so what this tool measures is a jitted ``lax.psum`` over the
``dp`` mesh axis, swept over tensor sizes, reporting achieved algorithmic
bandwidth ``2*(n-1)/n * bytes / t`` (ring-allreduce bytes actually moved).

Run on a pod for real ICI numbers; on a dev box it exercises the same code
path over the virtual CPU mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/bandwidth/measure.py --sizes 1,16,64 --repeat 5
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def measure(size_mb, mesh, repeat):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = mesh.devices.size
    elems = int(size_mb * (1 << 20) // 4)
    x = jnp.asarray(np.random.rand(n, elems).astype(np.float32))

    @jax.jit
    def allreduce(v):
        f = jax.shard_map(lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P("dp"))
        return f(v)

    np.asarray(allreduce(x))  # compile + warm
    t0 = time.time()
    for _ in range(repeat):
        out = allreduce(x)
    np.asarray(out)  # D2H sync bounds the span
    dt = (time.time() - t0) / repeat
    moved = 2 * (n - 1) / n * elems * 4  # ring-allreduce traffic per chip
    return dt, moved / dt / 1e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,4,16,64,256",
                    help="per-replica tensor sizes in MB")
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line per size")
    args = ap.parse_args()

    import jax
    from mxnet_tpu import parallel

    n = len(jax.devices())
    mesh = parallel.make_mesh(dp=n)
    print("devices: %d x %s" % (n, jax.devices()[0].platform),
          file=sys.stderr)
    for mb in (float(v) for v in args.sizes.split(",")):
        dt, gbs = measure(mb, mesh, args.repeat)
        if args.json:
            print(json.dumps({"size_mb": mb, "time_ms": round(dt * 1e3, 3),
                              "algo_bw_GBps": round(gbs, 2)}))
        else:
            print("size %8.1f MB  |  %8.3f ms  |  %7.2f GB/s algorithmic"
                  % (mb, dt * 1e3, gbs), flush=True)


if __name__ == "__main__":
    main()
