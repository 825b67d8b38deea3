"""Generation-serving benchmark: v2 (prefix cache, chunked prefill,
speculative decoding) vs the PR 7 continuous-batching baseline.

Writes ``benchmark/GENERATION.json``. The committed artifact is the
CPU-oracle run (``"platform"`` recorded inside, ``cpu_caveat`` stamped);
rerun on a TPU host for chip numbers. The PR 7 artifact is kept at
``benchmark/GENERATION_pr7.json`` and ``tools/bench_diff.py --gate``
compares the two (tokens/s up-is-good, TTFT down-is-good,
hit/acceptance rates informational) — the bench-regression check CI
runs.

Sections:

- ``continuous`` / ``naive`` — the PR 7 protocol unchanged (prefix
  cache, chunking, and speculation OFF), so the baseline comparison is
  apples-to-apples continuous batching.
- ``prefix_cache`` — a shared-system-prompt workload served cold
  (prefix cache off) and warm (cache primed): hit rate, fraction of
  prefill tokens skipped (must be >= 90%), bitwise-equal greedy outputs,
  throughput + TTFT both ways.
- ``chunked_prefill`` — live chat streams decoding while a multi-k-token
  prompt admits: p99/max inter-token latency of the live streams with
  monolithic prefill vs ``MXNET_GEN_PREFILL_CHUNK``-sized chunks.
- ``speculative`` — draft-then-verify greedy decoding vs the plain
  path: acceptance rate, tokens/s delta, token-exactness. The CPU
  oracle drafts with the target's own weights (worst-case draft cost,
  best-case agreement); chip deployments use a small distilled draft.

Usage::

    python benchmark/generation_bench.py            # write GENERATION.json
    python benchmark/generation_bench.py --quick    # smoke sizes
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

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.models import TransformerLM  # noqa: E402
from mxnet_tpu.serving import GenerationMetrics, ServingError  # noqa: E402
from mxnet_tpu.serving.generation import (DecodeEngine,  # noqa: E402
                                          GenerationScheduler, PrefixCache)

VOCAB = 256


def _pct(vals, q):
    vals = sorted(vals)
    if not vals:
        return 0.0
    import math
    return vals[min(len(vals) - 1,
                    max(0, math.ceil(q / 100.0 * len(vals)) - 1))]


def build_model(units=64, layers=2, heads=4, max_len=256, seed=0):
    np.random.seed(seed)
    net = TransformerLM(VOCAB, units=units, num_layers=layers,
                        num_heads=heads, max_len=max_len)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))
    return net


def make_workload(n_requests, rng):
    """Mixed-length prompts + budgets: the traffic shape continuous
    batching exists for (uniform workloads hide the join/leave win)."""
    return [
        (rng.integers(0, VOCAB, size=int(rng.integers(4, 25))).tolist(),
         int(rng.integers(8, 33)))
        for _ in range(n_requests)
    ]


# ---------------------------------------------------------------------------
# PR 7 protocol: continuous batching vs naive re-prefill (v2 features OFF)
# ---------------------------------------------------------------------------

def bench_continuous(net, workload, slots):
    metrics = GenerationMetrics()
    eng = DecodeEngine(net, num_slots=slots, max_seq=128,
                       ladder=(8, 16, 32), chunk=0, prefix_cache=False,
                       name="genbench")
    sched = GenerationScheduler(eng, metrics=metrics,
                                max_queue_size=len(workload))
    try:
        # warm every compile outside the measured window (ladder + decode)
        # — but record the split: compile_s is the cold-start cost a
        # restart pays, first-class in the artifact (ROADMAP item 4)
        t_warm0 = time.perf_counter()
        for rung_prompt in (4, 9, 17):
            sched.submit(list(range(1, rung_prompt + 1)),
                         max_new_tokens=2).result(timeout=600)
        compile_s = time.perf_counter() - t_warm0
        t0 = time.perf_counter()
        reqs = [sched.submit(p, max_new_tokens=m) for p, m in workload]
        ttfts, n_tokens = [], 0
        for r in reqs:
            toks = r.result(timeout=600)
            n_tokens += len(toks)
            ttfts.append(r.first_token_t - r.enqueue_t)
        wall = time.perf_counter() - t0
        return {
            "tokens": n_tokens,
            "compile_s": round(compile_s, 3),
            "wall_s": round(wall, 3),
            "tokens_s": round(n_tokens / wall, 2),
            "ttft_ms": {"p50": round(_pct(ttfts, 50) * 1e3, 2),
                        "p99": round(_pct(ttfts, 99) * 1e3, 2)},
            "avg_step_occupancy": round(
                metrics.snapshot()["avg_step_occupancy"], 2),
            "compiles": {k: eng.compile_stats()[k]
                         for k in ("decode", "prefill")},
        }
    finally:
        sched.close()
        eng.close()


def bench_naive(net, workload):
    """Sequential, cache-free: every token pays a full-prefix forward."""
    # warm the prefix-length compiles that the loop will hit (XLA compiles
    # per shape; naive decoding sweeps prompt_len..prompt_len+budget)
    lens = set()
    for p, m in workload:
        lens.update(range(len(p), len(p) + m))
    for L in sorted(lens):
        net(nd.array(np.zeros((1, L), "int32")))
    # TTFT is client-observed under the SAME traffic as the continuous
    # run: every request "arrives" at t0, and a sequential server makes
    # later requests wait behind earlier ones end-to-end
    t0 = time.perf_counter()
    ttfts, n_tokens = [], 0
    for prompt, budget in workload:
        toks = list(prompt)
        for i in range(budget):
            logits = net(nd.array(np.asarray(toks, "int32")[None]))
            nxt = int(logits.asnumpy()[0, -1].argmax())
            toks.append(nxt)
            if i == 0:
                ttfts.append(time.perf_counter() - t0)
            n_tokens += 1
    wall = time.perf_counter() - t0
    return {
        "tokens": n_tokens,
        "wall_s": round(wall, 3),
        "tokens_s": round(n_tokens / wall, 2),
        "ttft_ms": {"p50": round(_pct(ttfts, 50) * 1e3, 2),
                    "p99": round(_pct(ttfts, 99) * 1e3, 2)},
    }


# ---------------------------------------------------------------------------
# (a) prefix cache: shared-system-prompt workload
# ---------------------------------------------------------------------------

def bench_prefix(net, n_requests, slots, sys_len=120, block=8):
    """Every request = one shared system prompt + a short unique user
    suffix — the traffic shape prefix caching exists for. Cold pass
    (cache off) and warm pass (cache primed by one request) must produce
    BITWISE-equal greedy streams; the warm pass must skip >= 90% of
    prefill tokens."""
    rng = np.random.default_rng(11)
    system = rng.integers(0, VOCAB, size=sys_len).tolist()
    workload = [
        (system + rng.integers(0, VOCAB,
                               size=int(rng.integers(4, 9))).tolist(),
         int(rng.integers(8, 17)))
        for _ in range(n_requests)
    ]
    total_prompt_tokens = sum(len(p) for p, _ in workload)

    def run(prefix_cache, prime):
        eng = DecodeEngine(net, num_slots=slots, max_seq=256,
                           ladder=(8, 16, 32, 64, 128), chunk=block,
                           prefix_cache=prefix_cache, name="genbench.px")
        sched = GenerationScheduler(eng, max_queue_size=len(workload) + 1)
        try:
            # warm compiles (and optionally the prefix cache) outside the
            # measured window; publishing is async, so land it first
            sched.submit(system + [1, 2, 3],
                         max_new_tokens=2).result(timeout=600)
            eng.prefix_flush()
            if not prime and prefix_cache:
                prefix_cache.clear()
            t0 = time.perf_counter()
            reqs = [sched.submit(p, max_new_tokens=m)
                    for p, m in workload]
            outs, ttfts, n_tokens = [], [], 0
            for r in reqs:
                toks = r.result(timeout=600)
                outs.append(toks)
                n_tokens += len(toks)
                ttfts.append(r.first_token_t - r.enqueue_t)
            wall = time.perf_counter() - t0
            stats = sched.stats()
            return {
                "outs": outs,
                "tokens_s": round(n_tokens / wall, 2),
                "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 2),
                "tokens_saved": stats["prefix_tokens_saved"],
                "hits": stats["prefix_hits"],
            }
        finally:
            sched.close()
            eng.close()

    cold = run(False, prime=False)
    warm = run(PrefixCache(block=block, name="genbench.px"), prime=True)
    skipped_pct = warm["tokens_saved"] / float(total_prompt_tokens)
    return {
        "workload": {"requests": n_requests, "system_prompt_len": sys_len,
                     "user_suffix_len": "4-8", "block": block,
                     "prompt_tokens_total": total_prompt_tokens},
        "cold_tokens_s": cold["tokens_s"],
        "warm_tokens_s": warm["tokens_s"],
        "warm_speedup": round(warm["tokens_s"] /
                              max(cold["tokens_s"], 1e-9), 2),
        "cold_ttft_p50_ms": cold["ttft_p50_ms"],
        "warm_ttft_p50_ms": warm["ttft_p50_ms"],
        "hits": warm["hits"],
        "hit_rate": round(warm["hits"] / float(n_requests), 3),
        "tokens_saved": warm["tokens_saved"],
        "prefill_tokens_skipped_pct": round(skipped_pct, 4),
        "outputs_bitwise_equal": cold["outs"] == warm["outs"],
    }


# ---------------------------------------------------------------------------
# (b) chunked prefill: live streams vs a long-prompt admit
# ---------------------------------------------------------------------------

def _stream_arrivals(sched, prompt, budget, arrivals, reqs):
    req = sched.submit(prompt, max_new_tokens=budget)
    reqs.append(req)
    times = []
    try:
        for _ in req.tokens(timeout=600):
            # time.monotonic, matching GenerationRequest timestamps (the
            # window filter compares against req.first_token_t)
            times.append(time.monotonic())
    except ServingError:
        pass   # cancelled once the measurement window closed
    finally:
        arrivals.append(times)


def _gaps_in_window(arrivals, t0, t1):
    """Inter-token gaps of each stream whose interval overlaps
    [t0, t1] — the live-stream latency WHILE the long prompt is in
    flight, which is exactly the window monolithic prefill wrecks
    (whole-stream percentiles dilute one multi-second stall across
    hundreds of steady-state tokens)."""
    gaps = []
    for times in arrivals:
        for prev, now in zip(times, times[1:]):
            if now >= t0 and prev <= t1:
                gaps.append(now - prev)
    return gaps


def bench_chunked(long_len, chunk, n_streams=3, stream_budget=None):
    """``n_streams`` chat requests decode continuously; mid-run a
    ``long_len``-token prompt admits. Monolithic prefill freezes every
    live stream for the whole prompt; chunked prefill bounds the stall
    to one chunk per iteration. Reported: live-stream inter-token p99 /
    max over the window the long prompt is in flight (admit ->
    first token)."""
    max_seq = 1
    while max_seq < long_len + 64:
        max_seq <<= 1
    net = build_model(max_len=max_seq, seed=3)
    rng = np.random.default_rng(5)
    # streams must outlive the whole admit window on any host speed:
    # budget generously and CANCEL them once the long prompt lands
    # (retiring early would leave the gap window empty)
    stream_budget = stream_budget or max(256, long_len)

    def run(use_chunk):
        eng = DecodeEngine(
            net, num_slots=n_streams + 1, max_seq=max_seq,
            ladder=(16, 32, 64, long_len) if not use_chunk
            else (16, 32, 64),
            chunk=chunk if use_chunk else 0, prefix_cache=False,
            name="genbench.ck")
        sched = GenerationScheduler(eng, max_queue_size=8)
        try:
            long_prompt = rng.integers(0, VOCAB, size=long_len).tolist()
            # warm every program (incl. the long rung / chunk rungs) so
            # the measured stall is prefill COMPUTE, not its compile
            sched.submit(long_prompt, max_new_tokens=2).result(timeout=900)
            arrivals, stream_reqs, threads = [], [], []
            for i in range(n_streams):
                t = threading.Thread(
                    target=_stream_arrivals,
                    args=(sched, rng.integers(0, VOCAB, size=12).tolist(),
                          stream_budget, arrivals, stream_reqs))
                t.start()
                threads.append(t)
            time.sleep(0.3)  # streams live and decoding
            t0 = time.monotonic()
            long_req = sched.submit(long_prompt, max_new_tokens=4)
            long_toks = long_req.result(timeout=900)
            long_ttft = long_req.first_token_t - long_req.enqueue_t
            for r in stream_reqs:
                r.cancel()
            for t in threads:
                t.join(timeout=900)
            assert len(long_toks) == 4
            gaps = _gaps_in_window(arrivals, t0, long_req.first_token_t)
            assert gaps, "live streams produced no tokens in the window"
            return {
                "inter_token_p99_ms": round(_pct(gaps, 99) * 1e3, 2),
                "inter_token_max_ms": round(max(gaps) * 1e3, 2),
                "gaps_in_window": len(gaps),
                "long_ttft_ms": round(long_ttft * 1e3, 2),
            }
        finally:
            sched.close()
            eng.close()

    mono = run(False)
    chunked = run(True)
    return {
        "long_prompt_len": long_len,
        "chunk": chunk,
        "live_streams": n_streams,
        "monolithic": mono,
        "chunked": chunked,
        "inter_token_p99_improvement": round(
            mono["inter_token_p99_ms"] /
            max(chunked["inter_token_p99_ms"], 1e-9), 2),
        "inter_token_max_improvement": round(
            mono["inter_token_max_ms"] /
            max(chunked["inter_token_max_ms"], 1e-9), 2),
    }


# ---------------------------------------------------------------------------
# (c) speculative decoding
# ---------------------------------------------------------------------------

def bench_speculative(net, n_requests, slots, k=4):
    """Greedy chat workload with and without the draft-then-verify fast
    path. The CPU oracle self-drafts (draft == target weights): worst
    case for the tokens/s delta — a real deployment's draft is a
    distilled model at a fraction of the target's cost — and best case
    for acceptance, so the portable signals are token-exactness and the
    acceptance accounting."""
    rng = np.random.default_rng(17)
    workload = [
        (rng.integers(0, VOCAB, size=int(rng.integers(6, 20))).tolist(),
         int(rng.integers(16, 33)))
        for _ in range(n_requests)
    ]
    draft = build_model(seed=0)   # same seed => same weights (self-draft)

    def run(draft_model):
        from mxnet_tpu.serving.generation import SpeculativeDecoder
        eng = DecodeEngine(net, num_slots=slots, max_seq=128,
                           ladder=(8, 16, 32), chunk=0, prefix_cache=False,
                           name="genbench.sp")
        spec = SpeculativeDecoder(eng, draft_model, k=k) \
            if draft_model is not None else None
        sched = GenerationScheduler(eng, max_queue_size=len(workload),
                                    speculative=spec)
        try:
            sched.submit(list(range(1, 10)),
                         max_new_tokens=2).result(timeout=600)
            t0 = time.perf_counter()
            reqs = [sched.submit(p, max_new_tokens=m)
                    for p, m in workload]
            outs, n_tokens = [], 0
            for r in reqs:
                toks = r.result(timeout=600)
                outs.append(toks)
                n_tokens += len(toks)
            wall = time.perf_counter() - t0
            st = sched.stats()
            out = {
                "outs": outs,
                "tokens_s": round(n_tokens / wall, 2),
            }
            if draft_model is not None:
                sp = st["speculative"]
                out["acceptance_rate"] = round(sp["acceptance_rate"], 3)
                out["rounds"] = sp["rounds"]
                out["verify_compile_misses"] = sp["verify"]["misses"]
            return out
        finally:
            sched.close()
            if spec is not None:
                spec.close()
            eng.close()

    plain = run(None)
    spec = run(draft)
    return {
        "k": k,
        "draft": "self (target weights) — CPU oracle worst-case cost",
        "acceptance_rate": spec["acceptance_rate"],
        "verify_compile_misses": spec["verify_compile_misses"],
        "tokens_s_plain": plain["tokens_s"],
        "tokens_s_spec": spec["tokens_s"],
        "tokens_s_delta_pct": round(
            (spec["tokens_s"] - plain["tokens_s"]) /
            max(plain["tokens_s"], 1e-9) * 100.0, 1),
        "token_exact": plain["outs"] == spec["outs"],
    }


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--long-prompt", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "GENERATION.json"))
    args = ap.parse_args()
    n_requests = args.requests or (6 if args.quick else 16)
    long_len = args.long_prompt or (512 if args.quick else 3584)

    import jax
    platform = jax.devices()[0].platform
    net = build_model()
    workload = make_workload(n_requests, np.random.default_rng(7))

    print("== continuous batching (%d requests, %d slots) =="
          % (n_requests, args.slots))
    cont = bench_continuous(net, workload, args.slots)
    print(json.dumps(cont, indent=2))
    print("== naive sequential re-prefill ==")
    naive = bench_naive(net, workload)
    print(json.dumps(naive, indent=2))
    print("== prefix cache (shared system prompt) ==")
    prefix = bench_prefix(net, max(n_requests - 4, 4), args.slots)
    print(json.dumps(prefix, indent=2))
    print("== chunked prefill (%d-token admit vs %d live streams) =="
          % (long_len, 3))
    chunked = bench_chunked(long_len, args.chunk)
    print(json.dumps(chunked, indent=2))
    print("== speculative decoding ==")
    spec = bench_speculative(net, max(n_requests // 2, 4), args.slots)
    print(json.dumps(spec, indent=2))

    # acceptance gates (the criteria the artifact certifies)
    assert cont["compiles"]["decode"]["misses"] == 1, \
        "membership churn must compile nothing"
    assert prefix["outputs_bitwise_equal"], \
        "prefix-hit greedy outputs must match cold prefill bitwise"
    assert prefix["prefill_tokens_skipped_pct"] >= 0.90, \
        "shared-system-prompt workload must skip >= 90% of prefill tokens"
    assert spec["token_exact"], \
        "speculative greedy decoding must be token-exact"
    assert spec["verify_compile_misses"] <= 1, \
        "ONE fused verify program must serve every membership"
    assert chunked["chunked"]["inter_token_p99_ms"] < \
        chunked["monolithic"]["inter_token_p99_ms"], \
        "chunked prefill must improve live-stream p99 inter-token latency"

    out = {
        "platform": platform,
        "model": {"vocab": VOCAB, "units": net.units,
                  "layers": net.num_layers, "heads": net.num_heads},
        "workload": {"requests": n_requests,
                     "prompt_len": "4-24", "max_new_tokens": "8-32",
                     "temperature": 0.0},
        "slots": args.slots,
        "continuous": cont,
        "naive": naive,
        "speedup_tokens_s": round(cont["tokens_s"] / naive["tokens_s"], 2),
        "ttft_p50_ratio": round(
            naive["ttft_ms"]["p50"] / max(cont["ttft_ms"]["p50"], 1e-9), 2),
        "prefix_cache": prefix,
        "chunked_prefill": chunked,
        "speculative": spec,
        "decode_compile_misses": cont["compiles"]["decode"]["misses"],
        "cpu_caveat": (
            "XLA-CPU oracle: the continuous/naive protocol and all three "
            "v2 sections run the same tiny model on one host. Portable "
            "signals: compile counts, bitwise/token-exactness flags, "
            "hit/skip/acceptance rates, and the chunked-vs-monolithic "
            "inter-token ratio. Absolute tokens/s and the speculative "
            "delta are NOT chip numbers — on chip the draft would be a "
            "distilled fraction-of-target-cost model, and re-prefill "
            "baselines additionally pay per-length compiles"),
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print("wrote %s (speedup %.2fx, prefix skip %.1f%%, spec acceptance "
          "%.2f)" % (args.out, out["speedup_tokens_s"],
                     prefix["prefill_tokens_skipped_pct"] * 100.0,
                     spec["acceptance_rate"]))


if __name__ == "__main__":
    main()
