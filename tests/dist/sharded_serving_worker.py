#!/usr/bin/env python
"""Subprocess worker for the sharded-serving tier-1 tests.

One OS process == one "replica restart": the driver runs this worker
twice against the same artifact directory — scenario ``export``
compiles the sharded decode lane on a forced 8-device CPU host
platform, serves a few greedy steps, and writes the ``.mxa``; scenario
``restart`` is a genuinely fresh process (nothing warm, no in-process
caches) that loads the artifact and must serve the SAME tokens with
**zero** compiles. In-process restart tests can't prove that — this
worker exists so the zero-compile claim is made across a real process
boundary, the way a production replica restarts.

Protocol (env, like tests/dist/planner_worker.py):
    SHARDED_SCENARIO  export | restart
    SHARDED_DIR       artifact directory (shared between the two runs)
    SHARDED_OUT       path to write the JSON result

The env block below MUST run before jax is imported anywhere.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402
import jax  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.models.moe_transformer import moe_lm_tiny  # noqa: E402
from mxnet_tpu.serving.sharded import ShardedDecodeEngine  # noqa: E402

SLOTS, SEQ = 8, 32


def _net():
    # both processes seed identically, so params — and therefore the
    # greedy trajectory — must match bit-for-bit across the restart
    mx.random.seed(0)
    np.random.seed(0)
    net = moe_lm_tiny(n_experts=8)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))
    return net


def _drive(eng, steps=4):
    slot = eng.cache.acquire()
    tok = eng.prefill(slot, np.arange(1, 9, dtype=np.int32))
    tokens = np.zeros(SLOTS, np.int32)
    temps = np.zeros(SLOTS, np.float32)
    tokens[slot] = tok
    out = [int(tok)]
    for _ in range(steps):
        nxt = eng.decode_step(tokens, temps)
        eng.cache.advance([slot])
        tokens[slot] = nxt[slot]
        out.append(int(nxt[slot]))
    eng.cache.release(slot)
    return out


def main():
    scenario = os.environ["SHARDED_SCENARIO"]
    art = os.environ["SHARDED_DIR"]
    out_path = os.environ["SHARDED_OUT"]
    eng = ShardedDecodeEngine(_net(), num_slots=SLOTS, max_seq=SEQ,
                              chunk=0, name="worker_%s" % scenario)
    res = {"scenario": scenario, "devices": len(jax.devices()),
           "plan": str(eng.plan), "mesh": eng.mesh_info()["axes"]}
    if scenario == "export":
        res["tokens"] = _drive(eng)
        header = eng.export_artifacts(art)
        res["families"] = header["extra"]["families"]
        res["fingerprint_mesh"] = header["fingerprint"]["mesh"]
        res["decode_misses"] = eng.compile_stats()["decode"]["misses"]
    elif scenario == "restart":
        res["loaded"] = eng.load_artifacts(art)
        res["tokens"] = _drive(eng)
        res["compiles"] = sum(v["misses"]
                              for v in eng.compile_stats().values())
    else:
        raise SystemExit("unknown SHARDED_SCENARIO %r" % scenario)
    with open(out_path, "w") as f:
        json.dump(res, f)


if __name__ == "__main__":
    main()
