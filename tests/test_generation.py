"""Generation serving tests — slotted KV-cache, continuous batching,
streamed tokens (ISSUE 7).

Acceptance criteria covered on the CPU oracle:
(a) decode-output parity: KV-cache tokens == naive full-re-prefill greedy
    decoding exactly on a tiny TransformerLM, per-step logits within
    tolerance at every position;
(b) compile bound: requests joining/leaving the running batch trigger
    ZERO new XLA compiles (CachedOp stats: decode == 1 program, prefill
    bounded by the bucket ladder);
(c) allocator lifecycle (acquire/release/leak), EOS / token-budget
    retirement, ServerBusy backpressure + drain, chaos-injected step
    failure -> retry absorption and breaker/healthz degradation, and the
    HTTP /generate streaming path end-to-end.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import transformer_lm_tiny
from mxnet_tpu.resilience import chaos
from mxnet_tpu.resilience.breaker import CircuitBreaker
from mxnet_tpu.resilience.retry import RetryPolicy
from mxnet_tpu.serving import (DeadlineExceeded, GenerationMetrics,
                               ModelServer, ServerBusy, ServerClosed,
                               ServingError)
from mxnet_tpu.serving.generation import (CacheFull, DecodeEngine,
                                          GenerationScheduler,
                                          PromptTooLong, SlotKVCache)

VOCAB = 64


@pytest.fixture(autouse=True)
def _disarm_chaos():
    chaos.clear()
    yield
    chaos.clear()


@pytest.fixture(scope="module")
def tiny_lm():
    np.random.seed(0)
    net = transformer_lm_tiny(vocab_size=VOCAB)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))  # resolve deferred shapes
    return net


def _engine(net, slots=4, max_seq=64, ladder=(8, 16), **kw):
    return DecodeEngine(net, num_slots=slots, max_seq=max_seq,
                        ladder=ladder, **kw)


@pytest.fixture(scope="module")
def shared_eng(tiny_lm):
    """One compiled engine for every test that doesn't need special
    geometry — the decode/prefill XLA compiles are the expensive part of
    this file, and sharing them keeps tier-1 wall time down. Schedulers
    come and go on top of it (slot state is returned between tests; the
    leak assertions below keep that honest)."""
    eng = _engine(tiny_lm)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def shared_sched(shared_eng):
    sched = GenerationScheduler(shared_eng)
    yield sched
    sched.close()


def _assert_greedy_matches_reprefill(net, prompt, got):
    """Assert ``got`` equals naive full-re-prefill greedy decoding.

    Greedy token i is ``argmax logits(prompt + got[:i])[-1]``; a causal
    model computes the logits of every such prefix in ONE full forward
    over ``prompt + got[:-1]`` (position ``len(prompt)-1+i`` attends
    exactly the prefix re-prefill would run). Mathematically identical to
    the per-token re-prefill loop — the full-forward path stays the
    independent reference — at 1/n the eager-forward cost.
    ``benchmark/generation_bench.py`` runs the genuine sequential loop."""
    assert len(got) >= 1
    seq = list(prompt) + [int(t) for t in got[:-1]]
    logits = net(nd.array(np.asarray(seq, "int32")[None])).asnumpy()[0]
    start = len(prompt) - 1
    want = [int(logits[start + i].argmax()) for i in range(len(got))]
    assert list(got) == want


# ---------------------------------------------------------------------------
# models/transformer.py: incremental-decode forward path (satellite)
# ---------------------------------------------------------------------------

def test_incremental_decode_parity_every_position(tiny_lm):
    """step() logits through the KV cache match the full-prefix forward at
    EVERY position (tolerance), and the greedy tokens match exactly."""
    rng = np.random.default_rng(3)
    B, T = 2, 10
    tokens = rng.integers(0, VOCAB, (B, T)).astype("int32")
    full = tiny_lm(nd.array(tokens)).asnumpy()          # (B, T, V)
    cache = tiny_lm.init_cache(B, max_len=16)
    for t in range(T):
        lengths = nd.array(np.full((B,), t, "int32"))
        logits, cache = tiny_lm.step(nd.array(tokens[:, t:t + 1]),
                                     cache, lengths)
        np.testing.assert_allclose(logits.asnumpy(), full[:, t],
                                   rtol=1e-4, atol=1e-5)
        assert (logits.asnumpy().argmax(-1) == full[:, t].argmax(-1)).all()


def test_prefill_matches_full_forward(tiny_lm):
    """prefill() returns each row's last-VALID-position logits, with
    padded tails masked out of attention entirely."""
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, VOCAB, (2, 10)).astype("int32")
    lens = np.array([6, 10], "int32")
    logits, cache = tiny_lm.prefill(nd.array(tokens), nd.array(lens))
    ref0 = tiny_lm(nd.array(tokens[:1, :6])).asnumpy()[0, -1]
    ref1 = tiny_lm(nd.array(tokens[1:2])).asnumpy()[0, -1]
    np.testing.assert_allclose(logits.asnumpy()[0], ref0,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(logits.asnumpy()[1], ref1,
                               rtol=1e-4, atol=1e-5)
    assert len(cache) == tiny_lm.num_layers
    k, v = cache[0]
    assert k.shape == (2, 10, tiny_lm.num_heads, tiny_lm.head_dim)


def test_prefill_then_step_continues_exactly(tiny_lm):
    """A prefilled cache and a token-by-token cache are interchangeable:
    stepping after prefill equals the full forward on the longer prefix."""
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, VOCAB, (1, 9)).astype("int32")
    # a cache built token-by-token at init_cache capacity accepts step()
    # writes past the prompt (prefill()'s buffers are prompt-sized; the
    # serving arena provides the capacity in production)
    cache16 = tiny_lm.init_cache(1, max_len=16)
    for t in range(8):
        logits, cache16 = tiny_lm.step(
            nd.array(tokens[:, t:t + 1]), cache16,
            nd.array(np.array([t], "int32")))
    logits, _ = tiny_lm.step(nd.array(tokens[:, 8:9]), cache16,
                             nd.array(np.array([8], "int32")))
    ref = tiny_lm(nd.array(tokens)).asnumpy()[0, -1]
    np.testing.assert_allclose(logits.asnumpy()[0], ref,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# ops: seeded sampling (satellite) — determinism eager vs jit vs rerun
# ---------------------------------------------------------------------------

def _logits(rows=4):
    return np.random.default_rng(11).standard_normal(
        (rows, VOCAB)).astype("float32")


def test_sample_greedy_matches_argmax():
    lg = _logits()
    out = nd.sample_greedy(nd.array(lg)).asnumpy()
    assert (out == lg.argmax(-1)).all()
    assert out.dtype == np.int32


def test_sampling_determinism_same_seed_and_jit():
    """Same key => same tokens: across two eager runs AND across
    jit/no-jit (the ops are pure functions of (logits, key))."""
    import jax
    from mxnet_tpu.cached_op import CachedOp
    lg = nd.array(_logits())
    key = nd.array(np.asarray(jax.random.PRNGKey(42)))
    a = nd.sample_temperature(lg, key, temperature=1.0).asnumpy()
    b = nd.sample_temperature(lg, key, temperature=1.0).asnumpy()
    assert (a == b).all()
    op = CachedOp(lambda l, k: nd.sample_temperature(l, k, temperature=1.0))
    c = op(lg, key).asnumpy()
    d = op(lg, key).asnumpy()
    assert (a == c).all() and (c == d).all()
    # a different key moves at least one row (vocab 64, 4 rows: the odds
    # of a full collision are negligible and the draw is deterministic)
    key2 = nd.array(np.asarray(jax.random.PRNGKey(43)))
    e = nd.sample_temperature(lg, key2, temperature=1.0).asnumpy()
    assert not (a == e).all()


def test_temperature_zero_is_greedy_and_top_k_restricts_support():
    import jax
    lg = _logits(rows=1)
    greedy = lg.argmax(-1)
    top2 = set(np.argsort(lg[0])[-2:].tolist())
    for seed in range(20):
        key = nd.array(np.asarray(jax.random.PRNGKey(seed)))
        t0 = nd.sample_temperature(nd.array(lg), key, temperature=0.0)
        assert (t0.asnumpy() == greedy).all()
        tk = nd.sample_top_k(nd.array(lg), key, k=2, temperature=5.0)
        assert int(tk.asnumpy()[0]) in top2


def test_generation_sample_mixed_policies_one_call():
    """Per-row temperatures: 0-rows are greedy, hot rows sample — the
    fused op that lets one compiled decode step serve both."""
    import jax
    lg = _logits(rows=4)
    temps = nd.array(np.array([0.0, 1.0, 0.0, 2.0], "float32"))
    key = nd.array(np.asarray(jax.random.PRNGKey(0)))
    out = nd.generation_sample(nd.array(lg), key, temps).asnumpy()
    greedy = lg.argmax(-1)
    assert out[0] == greedy[0] and out[2] == greedy[2]


# ---------------------------------------------------------------------------
# kvcache: slot allocator lifecycle (acquire/release/leak)
# ---------------------------------------------------------------------------

def test_slot_allocator_lifecycle():
    c = SlotKVCache(num_slots=3, num_layers=2, max_seq=8, num_heads=2,
                    head_dim=4, name="kvcache_lifecycle")
    try:
        slots = [c.acquire() for _ in range(3)]
        assert sorted(slots) == [0, 1, 2]
        assert c.in_use == 3 and c.free_slots == 0
        with pytest.raises(CacheFull):
            c.acquire()
        c.set_length(slots[0], 5)
        assert c.lengths[slots[0]] == 5
        c.advance([slots[0]])
        assert c.lengths[slots[0]] == 6
        c.release(slots[1])
        assert c.free_slots == 1 and c.lengths[slots[1]] == 0
        with pytest.raises(ValueError):   # double release = scheduler bug
            c.release(slots[1])
        with pytest.raises(ValueError):   # advancing a freed slot too
            c.advance([slots[1]])
        st = c.stats()
        assert st["acquires"] == 3 and st["releases"] == 1
        assert st["acquire_failures"] == 1 and st["peak_in_use"] == 3
        assert st["occupancy"] == pytest.approx(2 / 3)
        c.reset()
        assert c.in_use == 0 and c.free_slots == 3
        assert c.lengths.sum() == 0
    finally:
        c.close()


def test_slot_advance_refuses_overflow():
    c = SlotKVCache(num_slots=1, num_layers=1, max_seq=4, num_heads=1,
                    head_dim=2, name="kvcache_overflow")
    try:
        s = c.acquire()
        c.set_length(s, 4)
        with pytest.raises(ValueError):
            c.advance([s])
    finally:
        c.close()


def test_kvcache_occupancy_reaches_profiler_rows():
    from mxnet_tpu import profiler
    c = SlotKVCache(num_slots=2, num_layers=1, max_seq=8, num_heads=1,
                    head_dim=2, name="kvcache_rows")
    try:
        c.acquire()
        rows = profiler.get_aggregate_stats()
        assert rows["generation.kvcache.kvcache_rows.in_use"]["calls"] == 1
        assert rows["generation.kvcache.kvcache_rows.acquires"]["calls"] \
            == 1
    finally:
        c.close()
    # closed caches drop out of the exporter (no pinning)
    rows = profiler.get_aggregate_stats()
    assert "generation.kvcache.kvcache_rows.in_use" not in rows


# ---------------------------------------------------------------------------
# decode parity + compile bound (acceptance a, b)
# ---------------------------------------------------------------------------

def test_generation_greedy_parity_vs_naive_reprefill(tiny_lm, shared_eng,
                                                     shared_sched):
    rng = np.random.default_rng(2)
    for _ in range(2):
        prompt = rng.integers(
            0, VOCAB, size=int(rng.integers(3, 14))).tolist()
        got = shared_sched.submit(prompt, max_new_tokens=6,
                                  temperature=0.0).result(timeout=120)
        assert len(got) == 6
        _assert_greedy_matches_reprefill(tiny_lm, prompt, got)
    assert shared_eng.cache.in_use == 0


def test_membership_churn_compiles_nothing(tiny_lm):
    """Compile count == prefill-ladder rungs + ONE decode step: requests
    joining/leaving the running batch recompile nothing."""
    eng = _engine(tiny_lm, slots=2, ladder=(8, 16))
    sched = GenerationScheduler(eng)
    try:
        rng = np.random.default_rng(7)
        # warm one request through (compiles: 1 prefill rung + 1 decode)
        sched.submit(rng.integers(0, VOCAB, 5).tolist(),
                     max_new_tokens=3).result(timeout=120)
        warm = eng.compile_stats()
        assert warm["decode"]["misses"] == 1
        # now churn: 6 staggered requests, mixed lengths/budgets, through
        # 2 slots — constant join/leave while the batch keeps running
        reqs = []
        for i in range(6):
            n = int(rng.integers(2, 15))
            reqs.append(sched.submit(
                rng.integers(0, VOCAB, n).tolist(),
                max_new_tokens=int(rng.integers(2, 7))))
            time.sleep(0.02)
        for r in reqs:
            r.result(timeout=120)
        st = eng.compile_stats()
        assert st["decode"]["misses"] == 1, st       # ZERO new compiles
        assert st["prefill"]["misses"] <= len(eng.ladder), st
        assert eng.cache.in_use == 0                 # no slot leaks
        assert eng.cache.stats()["peak_in_use"] == 2
    finally:
        sched.close()
        eng.close()


def test_prompt_too_long_rejected_synchronously(tiny_lm):
    eng = _engine(tiny_lm, ladder=(8,))
    sched = GenerationScheduler(eng)
    try:
        with pytest.raises(PromptTooLong):
            sched.submit(list(range(9)))
        with pytest.raises(ServingError):
            sched.submit([])
    finally:
        sched.close()
        eng.close()


# ---------------------------------------------------------------------------
# scheduler: retirement, backpressure, deadlines, drain
# ---------------------------------------------------------------------------

def test_eos_retirement_frees_slot_early(shared_eng, shared_sched):
    prompt = [1, 2, 3, 4, 5]
    ref = shared_sched.submit(prompt, max_new_tokens=8).result(timeout=120)
    eos = ref[2]  # greedy is deterministic: this token WILL reappear
    req = shared_sched.submit(prompt, max_new_tokens=8, eos_id=eos)
    got = req.result(timeout=120)
    stop = ref.index(eos)
    assert got == ref[:stop + 1]          # eos token included, then stop
    assert req.finish_reason == "eos"
    assert shared_eng.cache.in_use == 0


def test_max_tokens_retirement_reason(shared_sched):
    req = shared_sched.submit([1, 2, 3], max_new_tokens=4)
    assert len(req.result(timeout=120)) == 4
    assert req.finish_reason == "length"


def test_max_seq_retirement_at_arena_edge(tiny_lm):
    """A sequence that would outgrow its slot retires with 'max_seq'
    instead of corrupting the arena."""
    eng = _engine(tiny_lm, slots=1, max_seq=12, ladder=(8,))
    sched = GenerationScheduler(eng)
    try:
        req = sched.submit([1, 2, 3, 4], max_new_tokens=50)
        toks = req.result(timeout=120)
        # prefill wrote 4; decode can write positions 4..11 -> 8 steps,
        # the first generated token costs no slot write
        assert req.finish_reason == "max_seq"
        assert len(toks) == 12 - 4 + 1
        assert eng.cache.in_use == 0
    finally:
        sched.close()
        eng.close()


def test_server_busy_backpressure_and_queue_deadline(tiny_lm):
    eng = _engine(tiny_lm, slots=1)
    sched = GenerationScheduler(eng, max_queue_size=1)
    try:
        blocker = sched.submit([1, 2, 3], max_new_tokens=80)
        time.sleep(0.3)                      # let it occupy the only slot
        queued = sched.submit([4, 5, 6], max_new_tokens=2, timeout_ms=1.0)
        with pytest.raises(ServerBusy):
            sched.submit([7, 8, 9], max_new_tokens=2)
        with pytest.raises(DeadlineExceeded):
            queued.result(timeout=120)       # expired while waiting
        # cancelling while still QUEUED drops the entry before it can win
        # a slot and a prefill for a consumer known to be gone
        prefills_before = sched.metrics.snapshot()["prefills"]
        victim = sched.submit([7, 7, 7], max_new_tokens=2)
        victim.cancel()
        with pytest.raises(ServerClosed):
            victim.result(timeout=120)
        assert sched.stats()["cancelled"] == 1
        assert sched.metrics.snapshot()["prefills"] == prefills_before
        blocker.result(timeout=120)
    finally:
        sched.close()
        eng.close()


def test_close_drain_finishes_backlog(tiny_lm):
    eng = _engine(tiny_lm, slots=2, ladder=(8,))
    sched = GenerationScheduler(eng)
    reqs = [sched.submit([i + 1, i + 2], max_new_tokens=3)
            for i in range(4)]
    closer = threading.Thread(target=sched.close, kwargs={"drain": True})
    closer.start()
    for r in reqs:                           # EVERY queued request finishes
        assert len(r.result(timeout=120)) == 3
    closer.join(120)
    with pytest.raises(ServerClosed):
        sched.submit([1, 2])
    eng.close()


def test_cancel_releases_slot_mid_flight(shared_eng):
    """A cancelled consumer (client disconnect) frees its slot at the
    next iteration instead of decoding its whole budget for nobody."""
    sched = GenerationScheduler(shared_eng)
    try:
        req = sched.submit([1, 2, 3], max_new_tokens=500)
        next(req.tokens(timeout=120))        # first token arrived
        req.cancel()
        deadline = time.monotonic() + 30
        while shared_eng.cache.in_use and time.monotonic() < deadline:
            time.sleep(0.02)
        assert shared_eng.cache.in_use == 0  # slot freed well before 500
        with pytest.raises(ServerClosed):
            req.result(timeout=30)
        assert sched.stats()["cancelled"] == 1
        assert len(req.tokens_out) < 500
    finally:
        sched.close()


def test_close_timeout_stranded_request_stays_failed(tiny_lm):
    """A request failed by a close() drain timeout is NOT later
    double-counted as a success by the still-running worker."""
    eng = _engine(tiny_lm, slots=1)
    sched = GenerationScheduler(eng)
    req = sched.submit([1, 2, 3], max_new_tokens=300)
    next(req.tokens(timeout=120))            # mid-flight
    assert sched.close(drain=True, timeout=0.01) is False  # too short
    with pytest.raises(ServerClosed):
        req.result(timeout=30)
    assert req.finish_reason == "error"
    # the worker drains, releases the slot, and never flips the outcome
    deadline = time.monotonic() + 60
    while eng.cache.in_use and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.cache.in_use == 0
    assert req.finish_reason == "error"      # not overwritten to 'length'
    assert sched.stats()["completed"] == 0
    eng.close()


def test_close_no_drain_fails_queued_and_live(tiny_lm):
    eng = _engine(tiny_lm, slots=1)
    sched = GenerationScheduler(eng)
    live = sched.submit([1, 2, 3], max_new_tokens=200)
    time.sleep(0.3)
    queued = sched.submit([4, 5], max_new_tokens=2)
    sched.close(drain=False, timeout=30)
    with pytest.raises(ServerClosed):
        queued.result(timeout=30)
    with pytest.raises(ServerClosed):
        live.result(timeout=30)
    assert eng.cache.in_use == 0             # aborted slots released
    eng.close()


# ---------------------------------------------------------------------------
# chaos -> retry / breaker / healthz (the resilience stack, unchanged)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_transient_step_fault_absorbed_by_retry(tiny_lm, shared_eng):
    pol = RetryPolicy(max_attempts=3, base_delay_ms=0.5, jitter=0.0,
                      name="retry.gen_test", register=False)
    sched = GenerationScheduler(shared_eng, retry_policy=pol)
    try:
        chaos.arm("generation.step", "transient", first=2)
        got = sched.submit([1, 2, 3], max_new_tokens=4,
                           temperature=0.0).result(timeout=120)
        assert len(got) == 4
        _assert_greedy_matches_reprefill(tiny_lm, [1, 2, 3], got)
    finally:
        sched.close()


@pytest.mark.chaos
def test_chaos_fatal_step_fails_live_requests_but_scheduler_survives(
        tiny_lm, shared_eng):
    from mxnet_tpu.resilience.chaos import FatalFault
    sched = GenerationScheduler(shared_eng, retry_policy=False)
    try:
        chaos.arm("generation.step", "fatal", first=1)
        with pytest.raises(FatalFault):
            sched.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
        assert shared_eng.cache.in_use == 0  # failed slots were released
        # the worker did NOT die: the next request completes normally
        got = sched.submit([1, 2, 3], max_new_tokens=4).result(timeout=120)
        assert len(got) == 4
        _assert_greedy_matches_reprefill(tiny_lm, [1, 2, 3], got)
        assert sched.stats()["failed"] == 1
    finally:
        sched.close()


@pytest.mark.chaos
def test_step_fault_trips_breaker_and_degrades_healthz(shared_eng):
    sched = GenerationScheduler(shared_eng, retry_policy=False)
    breaker = CircuitBreaker(failure_threshold=1, recovery_ms=60000,
                             name="gen_test_breaker")
    srv = ModelServer(None, port=0, generator=sched, breaker=breaker,
                      bind_profiler=False).start()
    try:
        chaos.arm("generation.step", "fatal", first=1)
        body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 3,
                           "stream": False}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body))
        assert ei.value.code == 500
        health = json.loads(urllib.request.urlopen(
            srv.url + "/healthz").read())
        assert health["status"] == "degraded"
        assert health["breaker"]["state"] == "open"
        # fast-fail while open: 503 + Retry-After, no device work queued
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body))
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# HTTP /generate: streamed tokens end-to-end
# ---------------------------------------------------------------------------

def _serve(eng, **sched_kw):
    metrics = GenerationMetrics()
    sched = GenerationScheduler(eng, metrics=metrics, **sched_kw)
    return ModelServer(None, port=0, generator=sched).start()


def test_http_generate_streaming_e2e(tiny_lm, shared_eng):
    srv = _serve(shared_eng)
    try:
        body = json.dumps({"prompt": [1, 2, 3, 4, 5],
                           "max_new_tokens": 5,
                           "temperature": 0.0}).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body,
            headers={"X-Request-Id": "gen-e2e-1"}))
        assert resp.status == 200
        assert resp.headers["X-Request-Id"] == "gen-e2e-1"
        assert resp.headers["Content-Type"] == "application/x-ndjson"
        lines = [json.loads(l) for l in resp if l.strip()]
        toks = [l["token"] for l in lines if "token" in l]
        assert len(toks) == 5
        _assert_greedy_matches_reprefill(tiny_lm, [1, 2, 3, 4, 5], toks)
        assert [l["index"] for l in lines if "token" in l] == list(range(5))
        done = lines[-1]
        assert done["done"] is True and done["reason"] == "length"
        assert done["request_id"] == "gen-e2e-1"
        # non-streamed collects the same tokens
        body = json.dumps({"prompt": [1, 2, 3, 4, 5], "max_new_tokens": 5,
                           "stream": False}).encode()
        out = json.loads(urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body)).read())
        assert out["tokens"] == toks and out["reason"] == "length"
        # generation metrics made it to /metrics
        m = json.loads(urllib.request.urlopen(srv.url + "/metrics").read())
        g = m["generation"]
        assert g["ok"] == 2 and g["tokens_out"] >= 8
        assert g["ttft_ms"]["p50"] > 0
        assert g["kvcache"]["num_slots"] == 4
        assert g["compile"]["decode"]["misses"] == 1
    finally:
        srv.stop()


def test_http_generate_error_mapping(shared_eng):
    srv = _serve(shared_eng)
    try:
        # malformed: no prompt
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=b'{"nope": 1}'))
        assert ei.value.code == 400
        # prompt exceeding the ladder -> 400, not 500
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate",
                data=json.dumps({"prompt": list(range(40))}).encode()))
        assert ei.value.code == 400
        # /predict on a generation-only server -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/predict",
                data=json.dumps({"data": [1.0]}).encode()))
        assert ei.value.code == 404
        # mistyped optional fields -> 400, never a dropped connection
        for bad in ({"prompt": [1, 2], "timeout_ms": "soon"},
                    {"prompt": [1, 2], "max_new_tokens": "many"},
                    {"prompt": [1, 2], "eos_id": "stop"}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    srv.url + "/generate", data=json.dumps(bad).encode()))
            assert ei.value.code == 400
    finally:
        srv.stop()


def test_http_streamed_queue_deadline_is_typed_504(tiny_lm):
    """A streamed request that dies BEFORE its first token keeps its
    typed status code: the handler holds the 200 until the first event
    (the review contract — LBs key on status, not on in-band errors)."""
    eng = _engine(tiny_lm, slots=1)
    sched = GenerationScheduler(eng)
    srv = ModelServer(None, port=0, generator=sched,
                      bind_profiler=False).start()
    try:
        blocker = sched.submit([1, 2, 3], max_new_tokens=40)
        time.sleep(0.2)                      # occupy the only slot
        body = json.dumps({"prompt": [4, 5, 6], "max_new_tokens": 2,
                           "timeout_ms": 1.0}).encode()  # stream default
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                srv.url + "/generate", data=body))
        assert ei.value.code == 504
        blocker.result(timeout=120)
    finally:
        srv.stop()


def test_http_generate_streams_incrementally(shared_eng):
    """Tokens arrive before the request finishes — the stream is real,
    not a buffered dump: the first line is readable while the scheduler
    is still decoding the rest."""
    srv = _serve(shared_eng)
    try:
        body = json.dumps({"prompt": [9, 8, 7],
                           "max_new_tokens": 25}).encode()
        resp = urllib.request.urlopen(urllib.request.Request(
            srv.url + "/generate", data=body))
        first = json.loads(resp.readline())
        assert first["index"] == 0
        rest = [json.loads(l) for l in resp if l.strip()]
        assert rest[-1]["done"] is True
        assert len(rest) == 25  # 24 remaining tokens + done line
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# metrics: TTFT + tokens/s/slot percentiles -> /metrics + profiler
# ---------------------------------------------------------------------------

def test_generation_metrics_percentiles_and_profiler_rows():
    from mxnet_tpu import profiler
    m = GenerationMetrics(name="generation_test")
    for ms in (10, 20, 30, 40):
        m.record_ttft(ms / 1e3)
    m.record_prefill(0.01)
    m.record_step(3, 0.05)
    m.record_step(2, 0.05)
    m.record_done(10, "eos", 0.5)       # 9 intervals / 0.5 s = 18 tok/s
    m.record_done(30, "length", 1.0)    # 29 intervals / 1 s = 29 tok/s
    m.record_done(1, "eos", 1e-9)       # zero intervals: records NO rate
    m.record_error()
    snap = m.snapshot()
    assert snap["ttft_ms"]["p50"] == pytest.approx(20.0)
    assert snap["ttft_ms"]["p99"] == pytest.approx(40.0)
    assert snap["tokens_s_per_slot"]["p50"] == pytest.approx(18.0)
    assert snap["tokens_s_per_slot"]["p99"] == pytest.approx(29.0)
    assert snap["decode_tokens_s"] == pytest.approx(5 / 0.1)
    assert snap["retired_eos"] == 2 and snap["retired_length"] == 1
    assert snap["requests"] == 4 and snap["errors"] == 1
    assert snap["avg_step_occupancy"] == pytest.approx(2.5)
    m.bind_profiler()
    try:
        rows = profiler.get_aggregate_stats()
        assert rows["generation_test.requests"]["calls"] == 4
        assert rows["generation_test.tokens"]["calls"] == 5
        assert rows["generation_test.tokens"]["total_ms"] == \
            pytest.approx(100.0)
        assert rows["generation_test.prefills"]["calls"] == 1
    finally:
        m.unbind_profiler()
    rows = profiler.get_aggregate_stats()
    assert "generation_test.requests" not in rows


def test_scheduler_ttft_improves_over_sequential_queueing(shared_eng):
    """With continuous batching, a short request submitted while a long
    one is mid-flight gets its first token WITHOUT waiting for the long
    one to finish (the whole point of iteration-level scheduling)."""
    m = GenerationMetrics()
    sched = GenerationScheduler(shared_eng, metrics=m)
    try:
        long_req = sched.submit([1, 2, 3], max_new_tokens=40)
        time.sleep(0.2)                      # long request is mid-flight
        t0 = time.monotonic()
        short = sched.submit([4, 5, 6], max_new_tokens=2)
        short.result(timeout=120)
        short_wait = time.monotonic() - t0
        long_req.result(timeout=120)
        long_total = long_req.done_t - long_req.enqueue_t
        assert short_wait < long_total       # did not serialize behind it
        assert m.snapshot()["ok"] == 2
    finally:
        sched.close()


def test_model_programs_take_the_weights_as_arguments():
    """The engine's programs must not close over the model's parameters:
    jit bakes a closed-over array into the executable as a constant, so
    every program would carry its own copy of the weights (host memory at
    compile time, the serialized artifact, device memory). Found on the
    chip at 768 wide: 1.5 GB per executable, host out of memory while the
    prefill ladder compiled. Seen here as executable size against
    parameter bytes."""
    from mxnet_tpu.models import TransformerLM
    net = TransformerLM(4096, units=256, num_layers=2, num_heads=4,
                        max_len=64)
    net.initialize(mx.init.Xavier())
    param_bytes = sum(int(np.prod(p.shape)) * 4
                      for p in net.collect_params().values())
    eng = DecodeEngine(net, num_slots=2, max_seq=32, ladder=(8,),
                       prefix_cache=False)
    try:
        slot = eng.cache.acquire()
        eng.prefill(slot, [1, 2, 3])
        eng.decode_step(np.zeros(2, "int32"), np.zeros(2, "float32"))
        for op in (eng._prefill_op, eng._decode_op):
            (rec,) = op.serialize()
            assert len(rec["blob"]) < param_bytes // 8, \
                (len(rec["blob"]), param_bytes)
        # and a weight update reaches the compiled program, no recompile
        before = eng.decode_step(np.zeros(2, "int32"),
                                 np.zeros(2, "float32"))
        head = net.head.weight.data()
        head._data = head._data[::-1]
        after = eng.decode_step(np.zeros(2, "int32"),
                                np.zeros(2, "float32"))
        assert eng.compile_stats()["decode"]["misses"] == 1
        assert before[slot] != after[slot]
    finally:
        eng.close()
