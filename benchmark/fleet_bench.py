"""Fleet-serving benchmark: swap latency, canary rollback, isolation.

The committed ``benchmark/FLEET.json`` artifact is the CPU-oracle run
(``"platform"`` recorded inside); rerun on a TPU host for chip numbers.
Three experiments over in-process models through ``ModelRegistry``:

- ``version_swap``: 4 client threads hammer a model while ``promote()``
  flips v1 -> v2. Reports the flip+drain wall time, the request count
  landed during the swap, the failed-request count (the zero-drop
  contract), and XLA compiles issued during the swap (0 — both ladders
  prewarm at load).
- ``canary_rollback``: v2 rolls out as a 50% canary with the
  ``fleet.rollout`` chaos point armed at a 100% fault rate. Reports
  faults burned before detection, detection-to-rollback latency, and the
  baseline lane's success rate + p99 while the canary melted (the
  guarded-rollout contract: baseline unaffected).
- ``isolation``: three models served concurrently, one faulting at 100%.
  Reports per-model success rates and the healthy models' latency — the
  bulkhead contract is ``isolation_ok: true`` (healthy models at 100%).

Usage::

    python benchmark/fleet_bench.py            # full run + write FLEET.json
    python benchmark/fleet_bench.py --quick    # fewer requests (smoke)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import mxnet_tpu as mx  # noqa: E402  (registers the NDArray surface)
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.cached_op import cache_stats  # noqa: E402
from mxnet_tpu.resilience import chaos  # noqa: E402
from mxnet_tpu.serving import ModelRegistry  # noqa: E402

D_IN, D_HID = 128, 256
BUCKETS = (1, 2, 4, 8)


def _model(scale):
    rng = np.random.default_rng(0)
    W1 = nd.array(rng.standard_normal((D_IN, D_HID)).astype("float32"))
    W2 = nd.array(rng.standard_normal((D_HID, D_IN)).astype("float32"))

    def fn(x):
        return nd.dot(nd.relu(nd.dot(x, W1)), W2) * float(scale)
    return fn


def _boom(x):
    raise RuntimeError("injected: model faulting at 100%")


def _pctl(vals, q):
    if not vals:
        return 0.0
    vals = sorted(vals)
    import math
    return vals[min(len(vals) - 1,
                    max(0, math.ceil(q / 100.0 * len(vals)) - 1))]


def bench_version_swap(n_clients=4, seconds=2.0):
    reg = ModelRegistry(name="bench_swap")
    warm = np.zeros((1, D_IN), "float32")
    reg.load("m", "v1", source=_model(1), buckets=BUCKETS, warmup=warm)
    reg.load("m", "v2", source=_model(2), buckets=BUCKETS, warmup=warm)
    misses_before = cache_stats()["misses"]
    results, errors = [], []
    stop = threading.Event()

    def client(k):
        i = 0
        x = np.ones(D_IN, "float32")
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                reg.predict(x, request_id="c%d-%d" % (k, i))
                results.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — counted, never expected
                errors.append(repr(e))
            i += 1

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(n_clients)]
    for t in threads:
        t.start()
    time.sleep(seconds / 2)
    t0 = time.perf_counter()
    reg.promote("m", "v2")
    swap_s = time.perf_counter() - t0
    time.sleep(seconds / 2)
    stop.set()
    for t in threads:
        t.join(10)
    out = {
        "clients": n_clients,
        "requests_total": len(results) + len(errors),
        "failed_requests": len(errors),
        "swap_ms": swap_s * 1e3,
        "compiles_during_swap": cache_stats()["misses"] - misses_before,
        "p50_ms": _pctl(results, 50) * 1e3,
        "p99_ms": _pctl(results, 99) * 1e3,
        "zero_drop": not errors,
    }
    reg.close()
    return out


def bench_canary_rollback(n_requests=400, fraction=0.5, min_samples=20):
    chaos.clear()
    reg = ModelRegistry(name="bench_canary")
    warm = np.zeros((1, D_IN), "float32")
    reg.load("m", "v1", source=_model(1), buckets=BUCKETS, warmup=warm)
    reg.load("m", "v2", source=_model(2), buckets=BUCKETS, warmup=warm)
    controller = reg.start_canary("m", "v2", fraction=fraction,
                                  min_samples=min_samples)
    chaos.arm("fleet.rollout", "fatal", every=1)   # 100% canary fault rate
    base_lat, canary_faults = [], 0
    t_start = time.perf_counter()
    t_rollback = None
    x = np.ones(D_IN, "float32")
    for i in range(n_requests):
        t0 = time.perf_counter()
        try:
            _, mv = reg.predict(x, model="m", request_id="req-%05d" % i)
            if mv.version == "v1":
                base_lat.append(time.perf_counter() - t0)
        except Exception:  # noqa: BLE001 — the injected canary fault
            canary_faults += 1
        if t_rollback is None and controller.decision is not None:
            t_rollback = time.perf_counter()
    chaos.clear()
    # after rollback the remainder of the run is 100% baseline: the tail
    # of base_lat IS the post-rollback behaviour
    decision = dict(controller.decision or {})
    st = reg.stats()["models"]["m"]
    out = {
        "requests": n_requests,
        "canary_fraction": fraction,
        "min_samples": min_samples,
        "faults_before_rollback": canary_faults,
        "detect_to_rollback_ms": decision.get("detect_ms"),
        "rollback_reason": decision.get("reason"),
        "wall_to_rollback_ms": ((t_rollback - t_start) * 1e3
                                if t_rollback else None),
        "rolled_back": st["versions"].get("v2") == "rolled_back",
        "baseline_requests": len(base_lat),
        "baseline_success_rate": 1.0,   # any baseline error would raise
        "baseline_p50_ms": _pctl(base_lat, 50) * 1e3,
        "baseline_p99_ms": _pctl(base_lat, 99) * 1e3,
    }
    reg.close()
    return out


def bench_isolation(n_per_model=200):
    reg = ModelRegistry(name="bench_iso")
    warm = np.zeros((1, D_IN), "float32")
    reg.load("good_a", "v1", source=_model(1), buckets=BUCKETS, warmup=warm)
    reg.load("good_b", "v1", source=_model(2), buckets=BUCKETS, warmup=warm)
    reg.load("bad", "v1", source=_boom, jit=False)
    stats = {m: {"ok": 0, "fail": 0, "lat": []}
             for m in ("good_a", "good_b", "bad")}

    def client(model):
        x = np.ones(D_IN, "float32")
        st = stats[model]
        for i in range(n_per_model):
            t0 = time.perf_counter()
            try:
                reg.predict(x, model=model,
                            request_id="%s-%d" % (model, i))
                st["ok"] += 1
                st["lat"].append(time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — expected only on "bad"
                st["fail"] += 1

    threads = [threading.Thread(target=client, args=(m,), daemon=True)
               for m in stats]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    h = reg.healthz()
    out = {"requests_per_model": n_per_model, "models": {}}
    for m, st in stats.items():
        total = st["ok"] + st["fail"]
        out["models"][m] = {
            "success_rate": st["ok"] / float(total) if total else 0.0,
            "p50_ms": _pctl(st["lat"], 50) * 1e3,
            "p99_ms": _pctl(st["lat"], 99) * 1e3,
            "health": h[m]["status"],
        }
    out["isolation_ok"] = all(
        out["models"][m]["success_rate"] == 1.0 and
        out["models"][m]["health"] == "ok"
        for m in ("good_a", "good_b"))
    reg.close()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "FLEET.json"))
    args = ap.parse_args()
    import jax
    platform = jax.devices()[0].platform

    swap = bench_version_swap(seconds=1.0 if args.quick else 2.0)
    canary = bench_canary_rollback(
        n_requests=120 if args.quick else 400,
        min_samples=10 if args.quick else 20)
    iso = bench_isolation(n_per_model=50 if args.quick else 200)

    from benchmark._artifact import stamp
    artifact = stamp({
        "bench": "fleet",
        "platform": platform,
        "quick": args.quick,
        "version_swap": swap,
        "canary_rollback": canary,
        "isolation": iso,
    }, platform=platform)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps(artifact, indent=2))
    ok = (swap["zero_drop"] and canary["rolled_back"]
          and iso["isolation_ok"])
    print("\nFLEET bench %s -> %s" % ("OK" if ok else "FAILED", args.out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
