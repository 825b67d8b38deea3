"""Generation serving v2 tests — copy-on-admit prefix cache, chunked
prefill lanes, speculative decoding (ISSUE 14).

Acceptance criteria covered on the CPU oracle:
(a) prefix-cache correctness: a hit path produces BITWISE-equal arena
    content and greedy streams vs a cold prefill, refcounts block
    eviction of in-use slabs, LRU eviction respects the byte budget,
    and a forced hash-chain collision degrades to a miss;
(b) chunked prefill: long prompts interleave with decode iterations
    (live streams keep emitting while a long prompt prefills) and the
    result is token-exact vs the monolithic path;
(c) speculative decoding: greedy streams are token-exact vs the plain
    scheduler with ANY draft (an adversarial random draft and a
    self-draft), acceptance accounting is sane, and the verify program
    compiles ONCE;
(d) deadline-aware admission (the prefill-starvation fix), kvcache
    hwm/slots_peak/fragmentation stats, fleet gen_lane policy, and the
    bench_diff directions for the new GENERATION.json fields.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import TransformerLM, transformer_lm_tiny
from mxnet_tpu.serving import DeadlineExceeded, ServingError
from mxnet_tpu.serving.generation import (DecodeEngine, GenerationScheduler,
                                          PrefixCache, SlotKVCache,
                                          SpeculativeDecoder)
from mxnet_tpu.serving.generation import prefix_cache as _pc_mod

VOCAB = 64


@pytest.fixture(scope="module")
def tiny_lm():
    np.random.seed(0)
    net = transformer_lm_tiny(vocab_size=VOCAB)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))
    return net


@pytest.fixture(scope="module")
def draft_lm():
    """A structurally different, independently initialized draft — the
    adversarial case: near-zero agreement with the target, so the
    token-exactness guarantee cannot hide behind acceptance."""
    np.random.seed(123)
    net = TransformerLM(VOCAB, units=32, num_layers=1, num_heads=2,
                        max_len=256)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))
    return net


def _ref_greedy(net, prompt, n):
    """Independent reference: greedy token i via ONE full forward over
    the prefix (mathematically identical to per-token re-prefill)."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        logits = net(nd.array(np.asarray(seq, "int32")[None]))
        t = int(logits.asnumpy()[0, -1].argmax())
        out.append(t)
        seq.append(t)
    return out


def _engine(net, slots=4, max_seq=64, ladder=(8, 16, 32), **kw):
    return DecodeEngine(net, num_slots=slots, max_seq=max_seq,
                        ladder=ladder, **kw)


# ---------------------------------------------------------------------------
# (a) prefix cache
# ---------------------------------------------------------------------------

def test_prefix_hit_bitwise_equals_cold(tiny_lm):
    """The headline invariant: a prefix-cache hit installs BITWISE the
    same arena content a cold chunked prefill computes, the first-token
    logits path samples the same token, and the greedy continuation is
    bitwise the same stream."""
    pc = PrefixCache(block=8, name="px.bw")
    eng = _engine(tiny_lm, chunk=8, prefix_cache=pc, name="px.bw")
    try:
        prompt = np.random.default_rng(1).integers(
            1, VOCAB, size=21).astype("int32")
        s_cold = eng.cache.acquire()
        _, tok_cold = eng.prefill_chunks(s_cold, prompt, 0)
        eng.prefix_store(s_cold, prompt)

        s_hit = eng.cache.acquire()
        skipped = eng.prefix_admit(s_hit, prompt)
        assert skipped == 16  # largest block multiple <= n-1
        _, tok_hit = eng.prefill_chunks(s_hit, prompt, skipped)
        assert tok_hit == tok_cold

        k = eng.cache.k_arena.asnumpy()
        v = eng.cache.v_arena.asnumpy()
        n = len(prompt)
        assert np.array_equal(k[:, s_cold, :n], k[:, s_hit, :n])
        assert np.array_equal(v[:, s_cold, :n], v[:, s_hit, :n])

        toks = np.zeros(eng.num_slots, np.int32)
        temps = np.zeros(eng.num_slots, np.float32)
        toks[s_cold], toks[s_hit] = tok_cold, tok_hit
        a, b = [tok_cold], [tok_hit]
        for _ in range(6):
            out = eng.decode_step(toks, temps)
            eng.cache.advance([s_cold, s_hit])
            toks[s_cold], toks[s_hit] = out[s_cold], out[s_hit]
            a.append(int(out[s_cold]))
            b.append(int(out[s_hit]))
        assert a == b
        assert a == _ref_greedy(tiny_lm, prompt, 7)
    finally:
        eng.close()


def test_prefix_stats_and_profiler_rows(tiny_lm):
    from mxnet_tpu import profiler
    pc = PrefixCache(block=4, name="px.rows")
    eng = _engine(tiny_lm, chunk=4, prefix_cache=pc, name="px.rows")
    sched = GenerationScheduler(eng, retry_policy=False, name="px.rows")
    try:
        prompt = list(range(1, 14))
        sched.submit(prompt, max_new_tokens=3).result(timeout=120)
        eng.prefix_flush()   # publishing is async; land it before resubmit
        sched.submit(prompt, max_new_tokens=3).result(timeout=120)
        st = pc.stats()
        assert st["hits"] == 1 and st["insertions"] >= 3
        assert st["tokens_saved"] == 12
        assert st["hit_rate"] == 0.5
        rows = profiler.get_aggregate_stats()
        for key in ("hits", "misses", "tokens_saved", "evictions"):
            assert "generation.prefix.px.rows.%s" % key in rows
        sst = sched.stats()
        assert sst["prefix_hits"] == 1
        assert sst["prefix_tokens_saved"] == 12
    finally:
        sched.close()
        eng.close()


def test_prefix_refcount_blocks_eviction():
    """An acquired (in-copy) slab survives eviction pressure; releasing
    it makes it evictable again."""
    pc = PrefixCache(block=2, capacity_mb=1, name="px.ref")
    slab = np.zeros((2, 1, 2, 2, 64), "float32")  # 2 KiB per k+v pair
    pc.insert([1, 2], slab, slab)
    hit = pc.lookup([1, 2, 3])
    assert hit is not None
    entry, plen = hit
    assert plen == 2 and entry.refs == 1
    # flood far past the 1 MiB budget while the entry is held
    big = np.zeros((2, 1, 2, 64, 512), "float32")  # 512 KiB per pair
    for i in range(6):
        pc.insert([10 + i, 20 + i], big, big)
    assert pc.stats()["evictions"] > 0
    hit2 = pc.lookup([1, 2, 99])
    assert hit2 is not None                    # still resident
    pc.release(hit2[0])
    pc.release(entry)
    # with refs=0 the next pressure wave may evict it
    for i in range(6):
        pc.insert([50 + i, 60 + i], big, big)
    assert pc.lookup([1, 2, 3]) is None
    assert pc.stats()["bytes"] <= pc.capacity_bytes
    pc.close()


def test_prefix_lru_eviction_under_pressure():
    pc = PrefixCache(block=2, capacity_mb=1, name="px.lru")
    big = np.zeros((2, 1, 2, 64, 256), "float32")  # 256 KiB per pair
    for i in range(8):
        pc.insert([i, i + 100], big, big)
    st = pc.stats()
    assert st["evictions"] >= 4
    assert st["bytes"] <= pc.capacity_bytes
    # oldest entries gone, newest present
    assert pc.lookup([0, 100, 1]) is None
    assert pc.lookup([7, 107, 1]) is not None
    pc.close()


def test_prefix_hash_chain_collision_safety(monkeypatch):
    """Force every prefix onto one hash value: the stored token run must
    reject the look-alike and count a collision instead of serving
    another prompt's K/V."""
    monkeypatch.setattr(_pc_mod, "_hash_chain",
                        lambda tokens: [7] * len(tokens))
    pc = PrefixCache(block=2, name="px.col")
    slab = np.ones((1, 1, 2, 1, 4), "float32")
    pc.insert([1, 2], slab, slab)
    assert pc.lookup([3, 4, 5]) is None          # same key, other tokens
    assert pc.stats()["collisions"] == 1
    hit = pc.lookup([1, 2, 9])                   # the real owner still hits
    assert hit is not None and hit[1] == 2
    pc.release(hit[0])
    pc.close()


def test_prefix_block_granularity_disabled_for_short_prompts(tiny_lm):
    """Prompts shorter than one block never touch the cache (the
    back-compat guarantee for the default-on knob)."""
    pc = PrefixCache(block=32, name="px.short")
    eng = _engine(tiny_lm, prefix_cache=pc, name="px.short")
    sched = GenerationScheduler(eng, retry_policy=False)
    try:
        sched.submit([1, 2, 3], max_new_tokens=2).result(timeout=120)
        sched.submit([1, 2, 3], max_new_tokens=2).result(timeout=120)
        st = pc.stats()
        assert st["hits"] == 0 and st["entries"] == 0
    finally:
        sched.close()
        eng.close()


# ---------------------------------------------------------------------------
# (b) chunked prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_token_exact(tiny_lm):
    """Chunked admission (multiple iterations per prompt) produces the
    reference greedy stream."""
    eng = _engine(tiny_lm, chunk=4, prefix_cache=False, name="ck.exact")
    sched = GenerationScheduler(eng, retry_policy=False)
    try:
        rng = np.random.default_rng(2)
        for L in (5, 11, 19, 30):
            prompt = rng.integers(1, VOCAB, size=L).tolist()
            got = sched.submit(prompt, max_new_tokens=6).result(timeout=120)
            assert got == _ref_greedy(tiny_lm, prompt, 6)
        assert sched.metrics.snapshot()["prefill_chunks"] > 0
    finally:
        sched.close()
        eng.close()


def test_chunked_prefill_interleaves_with_decode(tiny_lm):
    """While a long prompt chunks through prefill, live streams keep
    receiving tokens — the freeze chunking exists to fix."""
    import threading
    eng = _engine(tiny_lm, slots=2, max_seq=64, chunk=4,
                  prefix_cache=False, name="ck.live")
    sched = GenerationScheduler(eng, retry_policy=False)
    try:
        arrivals = []

        def consume(req):
            import time as _t
            for _ in req.tokens(timeout=120):
                arrivals.append(_t.monotonic())

        live = sched.submit(list(range(1, 6)), max_new_tokens=40)
        t = threading.Thread(target=consume, args=(live,))
        t.start()
        while len(arrivals) < 3:   # stream demonstrably decoding
            pass
        long_prompt = np.random.default_rng(3).integers(
            1, VOCAB, size=40).tolist()
        long_req = sched.submit(long_prompt, max_new_tokens=2)
        long_req.result(timeout=120)
        t.join(timeout=120)
        # tokens arrived WHILE the long prompt was prefilling (>= 10
        # chunk iterations between admit and its first token)
        during = [a for a in arrivals
                  if long_req.admitted_t < a < long_req.first_token_t]
        assert len(during) >= 3, (len(during), len(arrivals))
        assert long_req.tokens_out[:2] == \
            _ref_greedy(tiny_lm, long_prompt, 2)
        assert sched.metrics.snapshot()["prefill_chunks"] >= 9
    finally:
        sched.close()
        eng.close()


def test_chunked_admits_prompts_beyond_ladder(tiny_lm):
    """With chunking on, the prompt bound is the arena (max_seq - 1),
    not the monolithic prefill ladder."""
    eng = _engine(tiny_lm, chunk=8, ladder=(8, 16), max_seq=64,
                  prefix_cache=False, name="ck.long")
    sched = GenerationScheduler(eng, retry_policy=False)
    try:
        prompt = np.random.default_rng(4).integers(
            1, VOCAB, size=40).tolist()   # > ladder max (16)
        got = sched.submit(prompt, max_new_tokens=4).result(timeout=120)
        assert got == _ref_greedy(tiny_lm, prompt, 4)
        with pytest.raises(ServingError):
            sched.submit([1] * 64, max_new_tokens=2)  # >= max_seq
    finally:
        sched.close()
        eng.close()


def test_deadline_aware_admission_prevents_starvation(tiny_lm):
    """Regression for the FIFO starvation bug: a burst of budget-heavy
    deadline-less prompts ahead of a short deadline-bearing chat request
    must not expire it in queue — EDF admits the deadline first."""
    eng = _engine(tiny_lm, slots=1, prefix_cache=False, name="edf")
    sched = GenerationScheduler(eng, retry_policy=False)
    try:
        hog = sched.submit([1, 2, 3], max_new_tokens=30)   # occupies slot
        longs = [sched.submit([5] * 8, max_new_tokens=30)
                 for _ in range(3)]                         # FIFO-ahead
        chat = sched.submit([9, 8, 7], max_new_tokens=2,
                            timeout_ms=60000.0)
        assert chat.result(timeout=120)                     # not expired
        hog.result(timeout=120)
        for r in longs:
            r.result(timeout=120)
        assert chat.finish_reason == "length"
        # EDF admitted the deadline-bearing request the moment the hog's
        # slot freed — BEFORE any of the FIFO-ahead deadline-less longs
        # started (under plain FIFO it would have sat behind 3 x 30-token
        # sequences on the single slot)
        assert chat.done_t < min(r.first_token_t for r in longs)
    finally:
        sched.close()
        eng.close()


# ---------------------------------------------------------------------------
# (c) speculative decoding
# ---------------------------------------------------------------------------

def test_speculative_token_exact_adversarial_draft(tiny_lm, draft_lm):
    """Token-exactness with a draft that almost never agrees: every
    emitted token is the target's own greedy choice."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, VOCAB, size=int(n)).tolist()
               for n in (4, 7, 12, 15)]

    eng = _engine(tiny_lm, prefix_cache=False, name="sp.adv")
    sched = GenerationScheduler(eng, retry_policy=False,
                                draft_model=draft_lm)
    try:
        reqs = [sched.submit(p, max_new_tokens=8) for p in prompts]
        outs = [r.result(timeout=120) for r in reqs]
        for p, got in zip(prompts, outs):
            assert got == _ref_greedy(tiny_lm, p, 8)
        st = sched.stats()["speculative"]
        assert st["rounds"] > 0
        assert st["verify"]["misses"] <= 1       # ONE fused verify program
        assert st["acceptance_rate"] < 0.5       # genuinely adversarial
    finally:
        sched.close()
        eng.close()


def test_speculative_self_draft_accepts_everything(tiny_lm):
    """Draft == target weights: every proposal is accepted, each round
    emits k+1 tokens, and the stream is still the reference greedy."""
    np.random.seed(0)
    clone = transformer_lm_tiny(vocab_size=VOCAB)
    clone.initialize(mx.init.Xavier())
    clone(nd.array(np.zeros((1, 8), "int32")))

    eng = _engine(tiny_lm, prefix_cache=False, name="sp.self")
    spec = SpeculativeDecoder(eng, clone, k=3)
    sched = GenerationScheduler(eng, retry_policy=False, speculative=spec)
    try:
        prompt = list(range(1, 9))
        got = sched.submit(prompt, max_new_tokens=9).result(timeout=120)
        assert got == _ref_greedy(tiny_lm, prompt, 9)
        st = spec.stats()
        assert st["acceptance_rate"] == 1.0
        # 1 prefill token + 8 decode tokens at k=3 (4/round) -> 2 rounds
        assert st["rounds"] == 2
        snap = sched.metrics.snapshot()
        assert snap["spec_acceptance_rate"] == 1.0
        assert snap["tokens_out"] == 8   # decode tokens (prefill separate)
    finally:
        sched.close()
        spec.close()
        eng.close()


def test_speculative_mixed_temperature_falls_back(tiny_lm, draft_lm):
    """A sampling request in the batch disables the speculative path for
    those iterations (greedy exactness can't cover sampling) — both
    requests still complete, and greedy-only iterations still
    speculate."""
    eng = _engine(tiny_lm, prefix_cache=False, name="sp.mix")
    sched = GenerationScheduler(eng, retry_policy=False,
                                draft_model=draft_lm)
    try:
        sampled = sched.submit([1, 2, 3, 4], max_new_tokens=12,
                               temperature=0.9)
        greedy = sched.submit([9, 8, 7], max_new_tokens=12)
        assert len(sampled.result(timeout=120)) == 12
        assert greedy.result(timeout=120)
        st = sched.stats()
        assert st["completed"] == 2
    finally:
        sched.close()
        eng.close()


def test_speculative_eos_and_budget_trim(tiny_lm):
    """EOS inside an accepted run stops the stream AT the EOS token and
    budget caps multi-token rounds exactly."""
    np.random.seed(0)
    clone = transformer_lm_tiny(vocab_size=VOCAB)
    clone.initialize(mx.init.Xavier())
    clone(nd.array(np.zeros((1, 8), "int32")))
    prompt = list(range(1, 9))
    ref = _ref_greedy(tiny_lm, prompt, 12)

    eng = _engine(tiny_lm, prefix_cache=False, name="sp.eos")
    sched = GenerationScheduler(eng, retry_policy=False,
                                draft_model=clone)
    try:
        # budget trim: ask for 6 (not a multiple of k+1)
        got = sched.submit(prompt, max_new_tokens=6).result(timeout=120)
        assert got == ref[:6]
        # EOS trim: use the reference's 4th token as eos_id
        got = sched.submit(prompt, max_new_tokens=12,
                           eos_id=ref[3]).result(timeout=120)
        first_eos = ref.index(ref[3])
        assert got == ref[:first_eos + 1]
    finally:
        sched.close()
        eng.close()


def test_speculative_rejects_short_draft(tiny_lm):
    """A draft whose max_len cannot cover the target arena depth fails
    at construction (the mirror arena would be silently clamped and
    crash mid-flight at the draft's edge, failing every live request)."""
    np.random.seed(9)
    short = TransformerLM(VOCAB, units=32, num_layers=1, num_heads=2,
                          max_len=32)
    short.initialize(mx.init.Xavier())
    eng = _engine(tiny_lm, max_seq=64, prefix_cache=False, name="sp.short")
    try:
        with pytest.raises(ValueError, match="max_len"):
            SpeculativeDecoder(eng, short, k=2)
    finally:
        eng.close()


def test_speculative_churn_compiles_nothing(tiny_lm, draft_lm):
    """Membership churn across speculative rounds: ONE decode program,
    ONE verify program, ONE draft decode program — joins/leaves change
    data only."""
    eng = _engine(tiny_lm, slots=3, prefix_cache=False, name="sp.churn")
    spec = SpeculativeDecoder(eng, draft_lm, k=2)
    sched = GenerationScheduler(eng, retry_policy=False, speculative=spec)
    try:
        rng = np.random.default_rng(8)
        reqs = []
        for i in range(7):    # > slots: continuous join/leave
            reqs.append(sched.submit(
                rng.integers(1, VOCAB, size=int(rng.integers(3, 12))
                             ).tolist(),
                max_new_tokens=int(rng.integers(3, 9))))
        for r in reqs:
            r.result(timeout=120)
        # all-greedy traffic speculates every iteration, so the plain
        # decode program may never even compile (<= 1 either way)
        assert eng.compile_stats()["decode"]["misses"] <= 1
        assert spec.stats()["verify"]["misses"] == 1
        assert spec.draft.compile_stats()["decode"]["misses"] == 1
    finally:
        sched.close()
        spec.close()
        eng.close()


# ---------------------------------------------------------------------------
# (d) satellites: kvcache stats, fleet lane policy, bench_diff directions
# ---------------------------------------------------------------------------

def test_kvcache_hwm_and_fragmentation_stats():
    from mxnet_tpu import profiler
    c = SlotKVCache(num_slots=4, num_layers=1, max_seq=32, num_heads=2,
                    head_dim=4, name="hwmcache")
    try:
        a, b = c.acquire(), c.acquire()
        c.set_length(a, 10)
        c.set_length(b, 6)
        st = c.stats()
        assert st["hwm"] == 16 and st["slots_peak"] == 2
        assert st["fragmentation"] == pytest.approx(1 - 16 / 64.0)
        c.release(a)
        st = c.stats()
        assert st["hwm"] == 16            # high-water mark survives release
        assert st["tokens_cached"] == 6
        c.advance([b])
        assert c.stats()["hwm"] == 16     # still below peak
        rows = profiler.get_aggregate_stats()
        assert "generation.kvcache.hwmcache.hwm" in rows
        assert "generation.kvcache.hwmcache.slots_peak" in rows
    finally:
        c.close()


def test_fleet_gen_lane_policy(tiny_lm):
    """A ModelVersion declared gen_lane='prefill' retires requests after
    the first token and publishes the prompt K/V; a decode lane on the
    SAME prefix cache admits with a hit — the disaggregation handoff."""
    from mxnet_tpu.serving.fleet import ModelRegistry
    pc = PrefixCache(block=4, name="lane.px")
    pre_eng = _engine(tiny_lm, chunk=4, prefix_cache=pc, name="lane.pre")
    dec_eng = _engine(tiny_lm, chunk=4, prefix_cache=pc, name="lane.dec")
    pre = GenerationScheduler(pre_eng, retry_policy=False, name="lane.pre")
    dec = GenerationScheduler(dec_eng, retry_policy=False, name="lane.dec")
    reg = ModelRegistry(name="lanereg")
    try:
        mv_pre = reg.load("lm", "prefill", generator=pre,
                          gen_lane="prefill")
        mv_dec = reg.load("lm", "decode", generator=dec, gen_lane="decode")
        assert mv_pre.health()["gen_lane"] == "prefill"
        assert mv_dec.health()["gen_lane"] == "decode"

        prompt = list(range(1, 14))
        req = pre.submit(prompt, max_new_tokens=16)
        toks = req.result(timeout=120)
        assert req.finish_reason == "prefill" and len(toks) == 1
        assert pre_eng.cache.in_use == 0          # slot released at once
        pre_eng.prefix_flush()   # the handoff barrier: publish landed
        assert pc.stats()["insertions"] >= 1
        assert pre.metrics.snapshot()["retired_prefill"] == 1

        got = dec.submit(prompt, max_new_tokens=4).result(timeout=120)
        assert got == _ref_greedy(tiny_lm, prompt, 4)
        assert dec.stats()["prefix_hits"] == 1
        assert dec.stats()["decode_lane_misses"] == 0
        assert toks[0] == got[0]                  # same first token
    finally:
        reg.close()
        pc.close()


def test_scheduler_lane_validation(tiny_lm):
    eng = _engine(tiny_lm, prefix_cache=False, name="lane.bad")
    try:
        with pytest.raises(ServingError):
            GenerationScheduler(eng, retry_policy=False,
                                lane_policy="bogus")
        s = GenerationScheduler(eng, retry_policy=False)
        assert s.lane_policy == "mixed"
        s.set_lane_policy("decode")
        assert s.stats()["lane"] == "decode"
        s.close()
    finally:
        eng.close()


def test_bench_diff_generation_directions(tmp_path):
    """The GENERATION.json v2 fields gate correctly: tokens/s up-is-good,
    TTFT/inter-token down-is-good, hit/acceptance rates informational."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.bench_diff import diff, direction_for, HIGHER, LOWER, INFO

    assert direction_for("prefix_cache.warm_tokens_s") == HIGHER
    assert direction_for("prefix_cache.tokens_saved") == HIGHER
    assert direction_for("prefix_cache.hit_rate") == INFO
    assert direction_for("speculative.acceptance_rate") == INFO
    assert direction_for("chunked_prefill.chunked.inter_token_p99_ms") \
        == LOWER
    assert direction_for("continuous.ttft_ms.p99") == LOWER

    base = {"prefix_cache": {"warm_tokens_s": 100.0, "hit_rate": 1.0},
            "chunked_prefill": {"chunked": {"inter_token_p99_ms": 10.0}}}
    # hit_rate halves (workload mix) but nothing gated regresses
    cand = {"prefix_cache": {"warm_tokens_s": 101.0, "hit_rate": 0.5},
            "chunked_prefill": {"chunked": {"inter_token_p99_ms": 9.0}}}
    verdict = diff(base, cand)
    assert verdict["status"] == "ok"
    assert any(d["metric"] == "prefix_cache.hit_rate"
               for d in verdict["drifts"])
    # a real regression still gates
    cand["chunked_prefill"]["chunked"]["inter_token_p99_ms"] = 20.0
    assert diff(base, cand)["status"] == "regression"


def test_bench_regression_gate_vs_pr7_artifact():
    """CI check: the committed v2 GENERATION.json must not regress the
    committed PR 7 artifact on any shared gated metric (tools/bench_diff
    --gate contract; exit 2 = regression)."""
    from tools.bench_diff import load_artifact, diff
    root = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    pr7 = load_artifact(os.path.join(root, "GENERATION_pr7.json"))
    cur = load_artifact(os.path.join(root, "GENERATION.json"))
    verdict = diff(pr7, cur, tolerance=0.25)  # CPU-oracle noise floor
    assert verdict["compared"] > 0
    assert verdict["status"] == "ok", verdict["regressions"]
    # and the v2 acceptance flags are recorded true in the artifact
    assert cur["prefix_cache"]["outputs_bitwise_equal"] is True
    assert cur["prefix_cache"]["prefill_tokens_skipped_pct"] >= 0.90
    assert cur["speculative"]["token_exact"] is True
    assert cur["decode_compile_misses"] == 1
    assert cur["chunked_prefill"]["chunked"]["inter_token_p99_ms"] < \
        cur["chunked_prefill"]["monolithic"]["inter_token_p99_ms"]


def test_generation_gauge_includes_prefix(tiny_lm):
    from mxnet_tpu.serving import generation as gen
    pc = PrefixCache(block=4, name="gauge.px")
    eng = _engine(tiny_lm, chunk=4, prefix_cache=pc, name="gauge.px")
    sched = GenerationScheduler(eng, retry_policy=False, name="gauge.px")
    try:
        sched.submit(list(range(1, 10)), max_new_tokens=2).result(
            timeout=120)
        eng.prefix_flush()
        g = gen.gauge()
        assert "gauge.px" in g["prefix"]
        assert g["prefix"]["gauge.px"]["insertions"] >= 1
    finally:
        sched.close()
        eng.close()
