"""Sharded multi-chip serving benchmark: ISSUE-16's acceptance drill.

The claim under test: an MoE model **provably infeasible on one chip**
(by the serving planner's own feasibility math — the reason string is
recorded, not hand-waved) serves live ``/generate`` traffic through the
gateway on a planned mesh, with the sharded lane keeping every invariant
the single-chip lane has:

- ``decode misses == 1`` across prefills, slot churn, and the whole
  HTTP traffic run (membership churn compiles nothing);
- restart from the sharded ``.mxa``: a fresh engine loads machine code
  for its exact mesh and serves with **zero** compiles;
- simulated chip-host loss: :class:`ShardedReplica` re-plans onto the
  surviving pool, the stale 8-chip artifact is *refused* (typed
  fallback, ``cachedop.pcache.fallback`` row — never silently
  installed), and the re-formed lane serves with one fresh compile.

Throughput is reported as tokens/s/chip next to the single-chip
engine's tokens/s on the SAME geometry — on the CPU oracle all
"devices" share one socket, so the ratio is workload-shape signal, not
a speedup claim (``cpu_caveat`` is stamped; counters and assertions are
the portable result).

Writes ``SHARDED_SERVING.json`` (stamped via benchmark/_artifact.py).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# geometry: small enough that 3 engine builds fit a CI round, big
# enough that the expert stack dominates the memory model
SLOTS, SEQ, EXPERTS = 8, 64, 8
DECODE_STEPS = 32


def _force_devices(n):
    """Force an ``n``-device CPU host platform. Must run before jax
    initializes — a no-op (with a loud note) when jax is already up."""
    if "jax" in sys.modules:
        return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n
        ).strip()


def _net(name_seed=0):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.models.moe_transformer import moe_lm_tiny

    mx.random.seed(name_seed)
    np.random.seed(name_seed)
    net = moe_lm_tiny(n_experts=EXPERTS)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))  # resolve deferred shapes
    return net


def _kv_bytes(net):
    import numpy as np
    return (2 * net.num_layers * SLOTS * SEQ * net.num_heads *
            net.head_dim * np.dtype("float32").itemsize)


def _decode_loop(eng, steps):
    """All slots busy, ``steps`` fused decode steps; returns tokens/s."""
    import numpy as np
    slots = []
    for i in range(SLOTS):
        s = eng.cache.acquire()
        eng.prefill(s, np.arange(1 + i, 9 + i, dtype=np.int32))
        slots.append(s)
    tokens = np.zeros(SLOTS, np.int32)
    temps = np.zeros(SLOTS, np.float32)
    eng.decode_step(tokens, temps)   # settle the fused program
    eng.cache.advance(slots)
    t0 = time.perf_counter()
    for _ in range(steps):
        tokens = eng.decode_step(tokens, temps)
        eng.cache.advance(slots)
    dt = time.perf_counter() - t0
    for s in slots:
        eng.cache.release(s)
    return SLOTS * steps / dt


def bench_sharded_serving(decode_steps=DECODE_STEPS, keep_dirs=False):
    import numpy as np
    import jax

    from mxnet_tpu import pcache
    from mxnet_tpu.parallel import planner
    from mxnet_tpu.serving.generation import DecodeEngine, \
        GenerationScheduler
    from mxnet_tpu.serving.gateway import Gateway
    from mxnet_tpu.serving.server import ModelServer
    from mxnet_tpu.serving.sharded import ShardedDecodeEngine, \
        ShardedReplica

    n_dev = len(jax.devices())
    out = {"devices": n_dev,
           "config": {"slots": SLOTS, "seq": SEQ, "experts": EXPERTS,
                      "decode_steps": decode_steps}}

    # ---- the infeasibility claim, by the planner's own math -----------
    net = _net()
    profile = net.profile(SLOTS, seq=SEQ)
    kv = _kv_bytes(net)
    single = planner.ShardingPlan()
    single_need = single.serving_memory_per_device(profile, kv_bytes=kv)
    min_need = planner.min_serving_memory_per_device(n_dev, profile,
                                                     kv_bytes=kv)
    budget = int(max(single_need * 0.6, min_need * 1.05))
    reason = single.serving_feasible(profile, hbm_bytes=budget,
                                     kv_bytes=kv)
    if not reason:
        raise SystemExit("budget %d does not exclude the single-chip "
                         "placement — bench config broke" % budget)
    out["feasibility"] = {
        "hbm_budget_bytes": budget,
        "single_chip_bytes": single_need,
        "single_chip_infeasible_reason": reason,
        "min_sharded_bytes": min_need,
        "kv_arena_bytes": kv,
    }

    # ---- the sharded lane --------------------------------------------
    t0 = time.perf_counter()
    eng = ShardedDecodeEngine(net, hbm_bytes=budget, num_slots=SLOTS,
                              max_seq=SEQ, chunk=0, name="bench_sharded")
    p = eng.plan
    out["plan"] = {"str": str(p), "dp": p.dp, "pp": p.pp, "ep": p.ep,
                   "sp": p.sp,
                   "bytes_per_device": p.serving_memory_per_device(
                       profile, kv_bytes=kv),
                   "mesh": eng.mesh_info()["axes"]}
    tok_s = _decode_loop(eng, decode_steps)
    out["sharded"] = {
        "build_plus_compile_s": round(time.perf_counter() - t0, 2),
        "tokens_per_sec": round(tok_s, 2),
        "tokens_per_sec_per_chip": round(tok_s / n_dev, 2),
        "decode_misses": eng.compile_stats()["decode"]["misses"],
    }
    if out["sharded"]["decode_misses"] != 1:
        raise SystemExit("sharded lane recompiled: %r"
                         % eng.compile_stats())

    # ---- single-chip ceiling (same geometry, device 0) ---------------
    ceiling = _net()
    eng1 = DecodeEngine(ceiling, num_slots=SLOTS, max_seq=SEQ, chunk=0,
                        name="bench_single")
    tok1_s = _decode_loop(eng1, decode_steps)
    out["single_chip_ceiling"] = {
        "tokens_per_sec": round(tok1_s, 2),
        "decode_misses": eng1.compile_stats()["decode"]["misses"],
        "note": "same model REPLICATED on one device — the placement "
                "the feasibility math proves cannot hold the real "
                "model; CPU oracle shares one socket across 'chips'",
    }
    out["per_chip_vs_single_ratio"] = round(tok_s / n_dev / tok1_s, 3)
    eng1.close()

    # ---- live /generate through the gateway --------------------------
    sched = GenerationScheduler(eng)
    srv = ModelServer(None, port=0, generator=sched).start()
    gw = Gateway(replicas=[srv.url], scrape_ms=0)
    gw.start()
    try:
        gw.scrape_once()
        rep = gw.replicas()[0]
        if rep.chips != n_dev:
            raise SystemExit("gateway scraped chips=%r, want %d"
                             % (rep.chips, n_dev))
        import urllib.request
        reqs, new_tokens = 4, 8
        t0 = time.perf_counter()
        got_tokens = 0
        for i in range(reqs):
            body = json.dumps({"prompt": [1 + i, 2 + i, 3 + i],
                               "max_new_tokens": new_tokens}).encode()
            raw = urllib.request.urlopen(urllib.request.Request(
                gw.url + "/generate", data=body), timeout=120).read()
            lines = [json.loads(l) for l in raw.splitlines() if l.strip()]
            if len(lines) == 1 and "tokens" in lines[0]:
                toks = lines[0]["tokens"]          # non-streamed body
            else:                                  # NDJSON token stream
                toks = [l["token"] for l in lines if "token" in l]
            if len(toks) != new_tokens:
                raise SystemExit("gateway /generate returned %d tokens, "
                                 "want %d: %r" % (len(toks), new_tokens,
                                                  lines[-1:]))
            got_tokens += len(toks)
        dt = time.perf_counter() - t0
        out["gateway"] = {
            "requests": reqs,
            "tokens_per_sec": round(got_tokens / dt, 2),
            "replica_chips": rep.chips,
            "replica_mesh": rep.mesh,
            "decode_misses_after_traffic":
                eng.compile_stats()["decode"]["misses"],
        }
        if out["gateway"]["decode_misses_after_traffic"] != 1:
            raise SystemExit("HTTP traffic recompiled the decode step: "
                             "%r" % eng.compile_stats())
    finally:
        gw.close()
        srv.stop()
        sched.close()

    # ---- AOT restart: zero compiles off the sharded .mxa -------------
    art_dir = tempfile.mkdtemp(prefix="sharded_serving_aot_")
    try:
        eng.export_artifacts(art_dir)
        eng.close()
        restart = _net()
        t0 = time.perf_counter()
        eng2 = ShardedDecodeEngine(restart, hbm_bytes=budget,
                                   num_slots=SLOTS, max_seq=SEQ, chunk=0,
                                   name="bench_restart")
        loaded = eng2.load_artifacts(art_dir)
        load_s = time.perf_counter() - t0
        tok2_s = _decode_loop(eng2, decode_steps)
        compiles = sum(v["misses"]
                       for v in eng2.compile_stats().values())
        out["aot_restart"] = {
            "executables_loaded": loaded,
            "build_plus_load_s": round(load_s, 2),
            "compiles": compiles,
            "tokens_per_sec_per_chip": round(tok2_s / n_dev, 2),
        }
        if compiles != 0:
            raise SystemExit("sharded AOT restart compiled: %r"
                             % eng2.compile_stats())
        eng2.close()

        # ---- chip-host loss: re-plan on the surviving pool ------------
        fb0 = pcache.stats().get("aot_fallbacks", 0)
        lossy = _net()
        repl = ShardedReplica(
            lossy, hbm_bytes=budget, artifacts_dir=art_dir,
            engine_kwargs={"num_slots": SLOTS, "max_seq": SEQ,
                           "chunk": 0},
            name="bench_replica")
        t0 = time.perf_counter()
        report = repl.replan(devices=jax.devices()[:n_dev // 2])
        replan_s = time.perf_counter() - t0
        tok3_s = _decode_loop(repl.engine, decode_steps)
        out["host_loss"] = {
            "from_plan": report["from"]["plan"],
            "to_plan": report["to"]["plan"],
            "surviving_devices": report["to"]["n_devices"],
            "replan_s": round(replan_s, 2),
            "stale_artifact_refused":
                pcache.stats().get("aot_fallbacks", 0) > fb0,
            "decode_misses": repl.engine.compile_stats()["decode"][
                "misses"],
            "tokens_per_sec_per_chip": round(
                tok3_s / report["to"]["n_devices"], 2),
        }
        if not out["host_loss"]["stale_artifact_refused"]:
            raise SystemExit("8-chip artifact silently installed into "
                             "the re-planned lane")
        if out["host_loss"]["decode_misses"] != 1:
            raise SystemExit("re-planned lane recompiled: %r"
                             % repl.engine.compile_stats())
        repl.close()
    finally:
        if not keep_dirs:
            shutil.rmtree(art_dir, ignore_errors=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=DECODE_STEPS)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "SHARDED_SERVING.json"))
    ap.add_argument("--json-only", action="store_true",
                    help="print the artifact to stdout, write no file")
    args = ap.parse_args()
    _force_devices(args.devices)

    artifact = {"metric": "sharded_serving_tokens_per_sec_per_chip",
                "unit": "tokens/s"}
    artifact.update(bench_sharded_serving(decode_steps=args.decode_steps))
    artifact["value"] = artifact["sharded"]["tokens_per_sec_per_chip"]
    from benchmark._artifact import stamp
    artifact = stamp(artifact, platform="cpu")  # oracle by construction
    if args.json_only:
        print(json.dumps(artifact))
        return
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "metric": artifact["metric"],
        "value": artifact["value"],
        "plan": artifact["plan"]["str"],
        "single_chip_infeasible":
            bool(artifact["feasibility"]["single_chip_infeasible_reason"]),
        "aot_restart_compiles": artifact["aot_restart"]["compiles"],
        "host_loss_replanned": artifact["host_loss"]["to_plan"],
    }))


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main()
