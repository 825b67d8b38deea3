"""2-process distributed kvstore worker — the check_diff invariants of
reference `tests/nightly/dist_sync_kvstore.py:25`, run over the
jax.distributed CPU backend by `tools/launch.py --launcher local`.

Each process: init -> push(rank-dependent value) -> pull -> assert the
pulled value equals the cross-worker sum, several rounds; then a jitted
global-mesh psum step (the ShardedTrainer collective path) and a barrier.
Exit code 0 on success in every process.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import jax

import numpy as np


def main():
    coord = os.environ["MXTPU_COORDINATOR"]
    nproc = int(os.environ["MXTPU_NUM_PROCESSES"])
    rank = int(os.environ["MXTPU_PROCESS_ID"])

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    mx.parallel.initialize(coordinator_address=coord, num_processes=nproc,
                           process_id=rank)
    assert jax.process_count() == nproc, jax.process_count()

    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == nproc
    assert kv.rank == rank

    shape = (3, 3)
    kv.init("3", nd.ones(shape))
    expected_sum = nproc * (nproc + 1) // 2

    # check_diff rounds: push rank-scaled values, expect the global sum
    for it in range(1, 4):
        kv.push("3", nd.ones(shape) * (rank + 1) * it)
        out = nd.zeros(shape)
        kv.pull("3", out=out)
        expect = np.full(shape, expected_sum * it, np.float32)
        np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-6,
                                   err_msg="iter %d rank %d" % (it, rank))

    # pushpull fused path
    val = nd.ones(shape) * (rank + 1)
    kv.pushpull("3", val, out=val)
    np.testing.assert_allclose(val.asnumpy(),
                               np.full(shape, expected_sum, np.float32))

    # multi-key list API
    kv.init(["a", "b"], [nd.zeros((2,)), nd.zeros((2,))])
    kv.push(["a", "b"], [nd.ones((2,)) * (rank + 1), nd.ones((2,))])
    outs = [nd.zeros((2,)), nd.zeros((2,))]
    kv.pull(["a", "b"], out=outs)
    np.testing.assert_allclose(outs[0].asnumpy(),
                               np.full((2,), expected_sum, np.float32))
    np.testing.assert_allclose(outs[1].asnumpy(),
                               np.full((2,), nproc, np.float32))

    # the jitted collective path a ShardedTrainer step uses: psum of
    # per-process gradients over the global mesh
    from jax.sharding import Mesh, PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map
    from jax.experimental import multihost_utils
    devs = [[d for d in jax.devices() if d.process_index == p][0]
            for p in range(nproc)]
    mesh = Mesh(np.array(devs), ("dp",))
    grad = np.full((4,), float(rank + 1), np.float32)[None]
    gshard = multihost_utils.host_local_array_to_global_array(
        grad, mesh, P("dp"))
    step = jax.jit(shard_map(lambda g: jax.lax.psum(g, "dp"), mesh=mesh,
                             in_specs=P("dp"), out_specs=P()))
    summed = step(gshard)
    local = np.asarray(multihost_utils.global_array_to_host_local_array(
        summed, mesh, P()))[0]
    np.testing.assert_allclose(local, np.full((4,), expected_sum,
                                              np.float32))

    # ---- ordering invariant: push before init must raise ----
    from mxnet_tpu.base import MXNetError
    try:
        kv.push("never_inited", nd.ones((2,)))
        raise AssertionError("push before init did not raise")
    except MXNetError:
        pass

    # ---- row_sparse pull (reference dist_sync_kvstore.py row_sparse
    # invariants): every rank pulls a DIFFERENT row subset ----
    from mxnet_tpu.ndarray import sparse as sp
    kv.init("rs", nd.ones((nproc * 2, 3)))
    kv.push("rs", nd.ones((nproc * 2, 3)) * (rank + 1))
    rows = np.array([rank, rank + nproc], np.int64)
    out_rs = sp.row_sparse_array(
        (np.zeros((2, 3), np.float32), rows), shape=(nproc * 2, 3))
    kv.row_sparse_pull("rs", out=out_rs, row_ids=nd.array(rows))
    np.testing.assert_allclose(
        np.asarray(out_rs.data.asnumpy()),
        np.full((2, 3), expected_sum, np.float32),
        err_msg="row_sparse_pull rank %d" % rank)
    np.testing.assert_array_equal(
        np.sort(out_rs.indices.asnumpy()), np.sort(rows))

    # ---- compressed push (2bit threshold, error feedback) ----
    kv2 = mx.kv.create("dist_sync")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("c", nd.zeros((4,)))
    for _ in range(2):
        # every worker pushes 2.0 -> quantizes to +0.5 regardless of the
        # accumulated residual; store = sum over workers = nproc * 0.5
        kv2.push("c", nd.ones((4,)) * 2.0)
        outc = nd.zeros((4,))
        kv2.pull("c", out=outc)
        np.testing.assert_allclose(outc.asnumpy(),
                                   np.full((4,), nproc * 0.5, np.float32),
                                   rtol=1e-6)
    # negative values quantize to -threshold
    kv2.push("c", nd.ones((4,)) * -5.0)
    outc = nd.zeros((4,))
    kv2.pull("c", out=outc)
    np.testing.assert_allclose(outc.asnumpy(),
                               np.full((4,), nproc * -0.5, np.float32),
                               rtol=1e-6)

    assert kv.num_dead_node == 0
    kv.barrier()
    print("rank %d OK" % rank, flush=True)


if __name__ == "__main__":
    main()
    sys.exit(0)
