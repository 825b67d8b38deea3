"""Long-context attention via sequence parallelism (ring attention).

Demonstrates the framework's long-sequence scaling path (SURVEY §5.7 marks
this beyond-reference): queries/keys/values are sharded along the sequence
axis of an ``sp`` mesh; K/V blocks rotate around the ring with
``ppermute`` while every chip accumulates its query block's softmax
online — peak activation memory per chip is O(seq/sp) instead of O(seq),
and the attention matmuls stay on the MXU at full tile size.

On a pod, sp=16 puts a 512K-token context within per-chip HBM. This demo
runs the same code path on the virtual CPU mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python ring_attention_demo.py --seq 4096
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--causal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import parallel

    n = len(jax.devices())
    sp = n
    mesh = parallel.make_mesh(dp=1, sp=sp)
    print("mesh: sp=%d over %s" % (sp, jax.devices()[0].platform))

    rng = np.random.RandomState(0)
    shape = (1, args.heads, args.seq, args.dim)
    q = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5)

    t0 = time.time()
    out = parallel.ring_attention_sharded(q, k, v, mesh,
                                          causal=args.causal)
    out_h = np.asarray(out)
    t_ring = time.time() - t0
    print("ring attention: seq=%d, %d-way sequence parallel, %.2fs "
          "(first call includes compile)" % (args.seq, sp, t_ring))
    print("per-chip K/V block: %d tokens (%.1f%% of full sequence)"
          % (args.seq // sp, 100.0 / sp))

    # dense oracle on one device (only feasible at demo sizes)
    scale = 1.0 / np.sqrt(args.dim)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) * scale
    if args.causal:
        s = np.where(np.tril(np.ones((args.seq, args.seq), bool)), s,
                     -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    ref = np.einsum("bhqk,bhkd->bhqd", e / e.sum(-1, keepdims=True),
                    np.asarray(v))
    err = np.abs(out_h - ref).max()
    print("max |ring - dense| = %.2e" % err)
    assert err < 2e-4, "ring attention diverges from dense oracle"
    print("OK")


if __name__ == "__main__":
    main()
