"""End-to-end tracing tests: span recorder, Chrome-trace export, request
and step propagation, profiler session semantics, and trace_summary.

The exporter contract is checked against the Chrome Trace Event Format
(object form: ``ph``/``ts``/``dur`` in microseconds, ``M`` metadata
records) so the dumped ``profile.json`` actually loads in
Perfetto/chrome://tracing; linkage is checked the Dapper way — children
share the root's ``trace_id`` and point at their parent's ``span_id``,
across threads.
"""
import importlib.util
import json
import os
import threading
import time
import urllib.request
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler
from mxnet_tpu.observability import export as obs_export
from mxnet_tpu.observability import tracer as tr


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with a disabled, empty tracer and no
    live profiler session (module-global state must not leak)."""
    tr.tracer.disable()
    tr.tracer.clear()
    tr.tracer.reset_phase_stats()
    tr.tracer.set_capacity(tr.DEFAULT_BUFFER)
    profiler._state["running"] = False
    profiler._state["paused"] = False
    profiler._state["jax_running"] = False
    profiler._state["filename"] = None
    yield
    tr.tracer.disable()
    tr.tracer.clear()
    tr.tracer.reset_phase_stats()
    tr.tracer.set_capacity(tr.DEFAULT_BUFFER)
    profiler._state["running"] = False
    profiler._state["paused"] = False
    profiler._state["jax_running"] = False
    profiler._state["filename"] = None


def _dump(tmp_path, name="profile.json"):
    path = str(tmp_path / name)
    obs_export.dump_chrome_trace(path, tr.events())
    with open(path) as f:
        return json.load(f)


def _spans(doc, name=None):
    out = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    if name is not None:
        out = [e for e in out if e["name"] == name]
    return out


# ---------------------------------------------------------------------------
# tracer core + exporter format
# ---------------------------------------------------------------------------

def test_exported_json_is_valid_chrome_trace(tmp_path):
    tr.enable()
    with tr.span("outer", label="a"):
        with tr.span("outer.inner"):
            time.sleep(0.002)
    tr.instant("tick", k=1)
    tr.tracer.counter("depth", value=3)
    doc = _dump(tmp_path)

    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phases
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str) and "pid" in e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert {"span_id", "parent_id", "trace_id"} <= set(e["args"])
        if e["ph"] == "i":
            assert e["s"] == "t"
    inner = _spans(doc, "outer.inner")[0]
    outer = _spans(doc, "outer")[0]
    assert inner["dur"] >= 2000  # slept 2 ms, ts/dur are microseconds
    # process + thread metadata records present (Perfetto lane names)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "process_name" for m in meta)
    assert any(m["name"] == "thread_name" for m in meta)
    assert outer["args"]["label"] == "a"


def test_nonfinite_attrs_export_as_valid_json(tmp_path):
    # a guardrails.skip carries loss=nan by construction; the dump must
    # stay spec-valid JSON (bare NaN tokens break browser loaders)
    tr.enable()
    tr.instant("guardrails.skip", loss=float("nan"), peak=float("inf"))
    path = str(tmp_path / "nan.json")
    obs_export.dump_chrome_trace(path, tr.events())
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    ev = [e for e in json.loads(raw)["traceEvents"]
          if e["name"] == "guardrails.skip"][0]
    assert ev["args"]["loss"] == "nan" and ev["args"]["peak"] == "inf"


def test_spans_nest_per_thread(tmp_path):
    tr.enable()

    def worker():
        with tr.span("w.root"):
            with tr.span("w.mid"):
                with tr.span("w.leaf"):
                    time.sleep(0.001)

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    doc = _dump(tmp_path)
    spans = _spans(doc)
    by_id = {e["args"]["span_id"]: e for e in spans}
    for e in spans:
        parent_id = e["args"]["parent_id"]
        if parent_id == 0:
            assert e["name"] == "w.root"
            continue
        parent = by_id[parent_id]
        # child recorded on the same thread, inside the parent interval,
        # in the parent's trace
        assert parent["tid"] == e["tid"]
        assert parent["ts"] <= e["ts"] + 1e-6
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-6
        assert parent["args"]["trace_id"] == e["args"]["trace_id"]


def test_cross_thread_parent_linkage():
    tr.enable()
    got = {}

    def worker(parent_ctx):
        with tr.tracer.attach(parent_ctx):
            with tr.span("child.on.other.thread") as sp:
                got["ctx"] = sp.ctx

    with tr.span("root") as root:
        t = threading.Thread(target=worker, args=(root.ctx,))
        t.start()
        t.join()
    assert got["ctx"].trace_id == root.ctx.trace_id
    events = {e[1]: e for e in tr.events()}
    child = events["child.on.other.thread"]
    assert child[8] == root.ctx.trace_id          # trace_id
    assert child[7] == root.ctx.span_id           # parent_id


def test_disabled_tracer_records_nothing_and_is_cheap():
    assert not tr.enabled()
    with tr.span("invisible", x=1):
        tr.instant("also.invisible")
    assert tr.events() == []
    # near-zero cost when disabled: the fast path is one attribute check
    # returning a shared no-op (generous bound — real cost is ~0.5 us)
    n = 50000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("x"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 20e-6, "disabled span() cost %.2f us" % (per_call * 1e6)


def test_ring_buffer_drops_oldest_never_grows():
    tr.enable(capacity=50)
    for i in range(300):
        tr.instant("e%d" % i)
    events = tr.events()
    assert len(events) == 50
    names = [e[1] for e in events]
    assert names[0] == "e250" and names[-1] == "e299"


def test_phase_stats_histograms():
    tr.enable()
    for _ in range(4):
        with tr.span("phase.fast"):
            pass
    with tr.span("phase.slow"):
        time.sleep(0.005)
    stats = tr.phase_stats()
    assert stats["phase.fast"]["count"] == 4
    assert stats["phase.slow"]["total_ms"] >= 5.0
    buckets = stats["phase.fast"]["buckets_ms"]
    assert sum(buckets.values()) == 4 and buckets["<=1ms"] == 4
    gauge = tr.summary_gauge()
    assert gauge["enabled"] and "phase.slow" in gauge["phases"]


# ---------------------------------------------------------------------------
# profiler session semantics (satellites)
# ---------------------------------------------------------------------------

def test_pause_resume_preserves_session_and_dump_honors_filename(tmp_path):
    target = tmp_path / "my_trace.json"
    profiler.set_config(filename=str(target))
    profiler._state["running"] = True  # host-side session, no jax trace
    tr.enable()
    with tr.span("before.pause"):
        pass
    profiler.pause()
    with tr.span("during.pause"):
        pass
    profiler.resume()
    with tr.span("after.resume"):
        pass
    path = profiler.dump()
    assert path == str(target) and target.exists()
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    # pause did NOT discard the session: pre-pause spans survived, the
    # paused window recorded nothing, resume continued the same buffer
    assert "before.pause" in names
    assert "during.pause" not in names
    assert "after.resume" in names
    assert not profiler._state["running"]  # dump(finished=True) stopped it


def test_set_state_run_starts_fresh_session(tmp_path):
    tr.enable()
    with tr.span("stale"):
        pass
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    try:
        assert tr.enabled()
        assert all(e[1] != "stale" for e in tr.events())
    finally:
        profiler.set_state("stop")
    assert not tr.enabled()


def test_env_pinned_tracing_survives_pause_then_stop(tmp_path, monkeypatch):
    # MXNET_TRACE_ENABLE=1 pins always-on tracing; a profiling session's
    # pause() (which disables the tracer) followed by set_state("stop")
    # must actively re-enable it, not leave it off for the process life
    monkeypatch.setenv("MXNET_TRACE_ENABLE", "1")
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    profiler.pause()
    assert not tr.enabled()
    profiler.set_state("stop")
    assert tr.enabled(), "env-pinned tracing must survive pause()+stop()"


def test_failed_session_start_does_not_wedge_running_state(tmp_path):
    # a failing filename directory must not leave a phantom "running"
    # session: the corrected retry has to actually start
    profiler.set_config(filename="/proc/definitely/not/writable/p.json")
    with pytest.raises(OSError):
        profiler.set_state("run")
    assert not profiler._state["running"]
    assert not tr.enabled()
    profiler.set_config(filename=str(tmp_path / "ok.json"))
    profiler.set_state("run")
    try:
        assert profiler._state["running"] and tr.enabled()
    finally:
        profiler.set_state("stop")


def test_nonpositive_trace_buffer_keeps_default_capacity(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_BUFFER", "0")
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    try:
        assert tr.tracer.capacity == tr.DEFAULT_BUFFER
    finally:
        profiler.set_state("stop")


def test_scoped_objects_appear_in_timeline(tmp_path):
    tr.enable()
    dom = profiler.Domain("user_domain")
    with dom.new_task("user_task"):
        time.sleep(0.001)
    dom.new_marker("user_marker").mark()
    counter = dom.new_counter("user_counter", 1)
    counter.set_value(7)
    counter += 2
    doc = _dump(tmp_path)
    task = _spans(doc, "user_task")[0]
    assert task["args"]["domain"] == "user_domain"
    assert task["dur"] >= 1000
    instants = [e for e in doc["traceEvents"]
                if e["ph"] == "i" and e["name"] == "user_marker"]
    assert instants, "marker missing from timeline"
    counters = [e for e in doc["traceEvents"]
                if e["ph"] == "C" and e["name"] == "user_counter"]
    assert [c["args"]["value"] for c in counters] == [7, 9]
    # aggregate table still fed (the pre-existing contract)
    assert profiler.get_aggregate_stats()["user_task"]["calls"] >= 1


def test_provider_errors_counted_and_warned_once():
    calls = {"n": 0}

    def bad_provider():
        calls["n"] += 1
        raise RuntimeError("broken exporter")

    profiler.register_stats_provider(bad_provider)
    try:
        before = profiler.provider_error_counts().get(
            "test_provider_errors_counted_and_warned_once."
            "<locals>.bad_provider", 0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            stats1 = profiler.get_aggregate_stats()
            stats2 = profiler.get_aggregate_stats()
        ours = [w for w in caught if "broken exporter" in str(w.message)]
        assert len(ours) == 1, "must warn exactly once per provider"
        assert calls["n"] == 2
        errs = profiler.provider_error_counts()
        key = [k for k in errs if "bad_provider" in k][0]
        assert errs[key] == before + 2
        assert stats1["profiler.provider_errors"]["calls"] >= 1
        assert stats2["profiler.provider_errors"]["calls"] >= 2
    finally:
        profiler.unregister_stats_provider(bad_provider)


def test_dumps_reset_resets_providers_with_hook():
    rows = {"custom.row": (3, 0.5)}
    state = {"reset": 0}

    def provider():
        return rows

    def reset():
        state["reset"] += 1
        rows.clear()

    profiler.register_stats_provider(provider, reset_fn=reset)
    try:
        assert "custom.row" in profiler.get_aggregate_stats()
        profiler.dumps(reset=True)
        assert state["reset"] == 1
        assert "custom.row" not in profiler.get_aggregate_stats()
    finally:
        profiler.unregister_stats_provider(provider)


def test_trace_phase_rows_reach_aggregate_and_reset():
    tr.enable()
    with tr.span("rowtest.op"):
        pass
    stats = profiler.get_aggregate_stats()
    assert stats["trace.rowtest.op"]["calls"] == 1
    profiler.dumps(reset=True)  # the tracer provider registered a reset_fn
    assert "trace.rowtest.op" not in profiler.get_aggregate_stats()


# ---------------------------------------------------------------------------
# serving propagation: HTTP -> queue -> execute
# ---------------------------------------------------------------------------

D_IN, D_OUT = 8, 3
_W = np.linspace(-1, 1, D_IN * D_OUT).reshape(D_IN, D_OUT).astype("float32")


def _linear(x):
    return nd.dot(x, nd.array(_W))


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode("utf-8"),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def test_request_id_propagates_http_to_batcher_to_engine(tmp_path):
    from mxnet_tpu.serving import ModelServer
    tr.enable()
    with ModelServer(_linear, port=0, buckets=(1, 2), jit=False,
                     max_latency_ms=1) as srv:
        x = np.random.randn(D_IN).astype("float32")
        code, headers, body = _post(srv.url + "/predict",
                                    {"data": x.tolist()})
        assert code == 200
        rid = headers["X-Request-Id"]
        assert rid
        # client-chosen id is honored (upstream tracing interop)
        code, headers2, _ = _post(srv.url + "/predict",
                                  {"data": x.tolist()},
                                  headers={"X-Request-Id": "req-abc123"})
        assert headers2["X-Request-Id"] == "req-abc123"
        metrics = json.loads(urllib.request.urlopen(
            srv.url + "/metrics", timeout=10).read())
        assert metrics["trace"]["enabled"]
        assert "serving.http" in metrics["trace"]["phases"]
    doc = _dump(tmp_path)
    https = {e["args"]["request_id"]: e for e in _spans(doc, "serving.http")}
    assert rid in https and "req-abc123" in https
    http = https[rid]
    waits = [e for e in _spans(doc, "serving.queue_wait")
             if e["args"].get("request_id") == rid]
    assert waits, "queue-wait span missing for the request"
    # linked: same trace, parented on the HTTP span, recorded from the
    # batcher worker thread (cross-thread propagation)
    assert waits[0]["args"]["trace_id"] == http["args"]["trace_id"]
    assert waits[0]["args"]["parent_id"] == http["args"]["span_id"]
    assert waits[0]["tid"] != http["tid"]
    execs = [e for e in _spans(doc, "serving.batch_execute")
             if rid in (e["args"].get("request_ids") or [])]
    assert execs, "batch-execute span missing the request id"
    assert _spans(doc, "serving.engine.execute")
    assert _spans(doc, "serving.batch_assemble")


# ---------------------------------------------------------------------------
# training propagation: step_stream chunks + stager-thread staging spans
# ---------------------------------------------------------------------------

def _mlp_trainer():
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh(dp=8))


def test_step_stream_chunk_and_staging_spans(tmp_path):
    from mxnet_tpu.parallel import DeviceFeed
    trainer = _mlp_trainer()
    rng = np.random.RandomState(0)
    batches = [(rng.standard_normal((16, 8)).astype("float32"),
                rng.randint(0, 4, 16).astype("float32"))
               for _ in range(6)]
    tr.enable()
    with DeviceFeed(batches, mesh=trainer.mesh, depth=2,
                    name="obs.e2e") as feed:
        losses = trainer.step_stream(feed, chunk=2)
    assert np.asarray(losses).shape == (6,)
    doc = _dump(tmp_path)
    chunks = _spans(doc, "trainer.chunk")
    assert len(chunks) == 3  # 6 steps / chunk=2; the dry 4th is cancelled
    assert sorted(c["args"]["chunk"] for c in chunks) == [0, 1, 2]
    assert all(c["args"]["steps"] == 2 for c in chunks)
    assert all(c["args"]["feed"] == "obs.e2e" for c in chunks)
    stages = _spans(doc, "datafeed.stage")
    assert len(stages) == 6
    # staging runs on the stager thread, chunks on the consumer — two
    # different lanes in the exported timeline (the overlap view)
    stager_tids = {e["tid"] for e in stages}
    chunk_tids = {e["tid"] for e in chunks}
    assert stager_tids and stager_tids.isdisjoint(chunk_tids)
    meta = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any("datafeed-stager" in meta[t] for t in stager_tids)
    # any consumer-side wait span nests inside a chunk span's trace
    for w in _spans(doc, "datafeed.consumer_wait"):
        assert w["args"]["feed"] == "obs.e2e"


def test_trainer_step_span():
    trainer = _mlp_trainer()
    tr.enable()
    x = np.random.randn(16, 8).astype("float32")
    y = np.random.randint(0, 4, 16).astype("float32")
    trainer.step(x, y)
    trainer.step(x, y)
    names = [e[1] for e in tr.events()]
    assert names.count("trainer.step") == 2
    steps = [e for e in tr.events() if e[1] == "trainer.step"]
    assert [e[9]["t"] for e in steps] == [1, 2]


# ---------------------------------------------------------------------------
# the bridge into the profiler's own trace
# ---------------------------------------------------------------------------

def _host_events(trace_dir):
    """``{name: [stats dict]}`` over the host planes of the one profile
    written under ``trace_dir``."""
    import glob
    import jax
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


def _profiled(trace_dir, body):
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def _some_spans():
    with tr.span("trainer.step", t=3, step_num=3):
        with tr.span("bridge.inner", op="m") as inner:
            inner.set(late=5)
    with tr.span("bridge.cancelled") as gone:
        gone.cancel()


def test_enabled_spans_are_in_the_profilers_host_plane(tmp_path):
    tr.enable()
    host = _profiled(tmp_path, _some_spans)
    recorded = {e[1] for e in tr.events() if e[0] == "X"}
    assert recorded == {"trainer.step", "bridge.inner"}
    assert recorded <= set(host)               # every program span is there
    step, = host["trainer.step"]
    assert step["t"] == 3 and step["step_num"] == 3 and step["_r"] == 1
    inner, = host["bridge.inner"]
    assert inner["op"] == "m" and inner["late"] == 5
    assert "_r" not in inner
    # the tracer's own record keeps its layout and attributes
    rec, = [e for e in tr.events() if e[1] == "trainer.step"]
    assert len(rec) == 10 and rec[9] == {"t": 3, "step_num": 3}


def test_disabled_tracer_writes_no_annotation(tmp_path, monkeypatch):
    made = []
    real = tr._TraceAnnotation
    monkeypatch.setattr(tr, "_TraceAnnotation",
                        lambda *a, **kw: made.append(a) or real(*a, **kw))
    assert not tr.enabled()
    assert tr.span("x") is tr._NULL_SPAN and tr.tracer.span("x") is tr._NULL_SPAN
    host = _profiled(tmp_path, _some_spans)
    assert made == [] and tr.events() == []
    assert not {"trainer.step", "bridge.inner", "bridge.cancelled"} & set(host)
    tr.enable()
    _some_spans()
    assert [a[0] for a in made] == ["trainer.step", "bridge.inner",
                                    "bridge.cancelled"]


def test_scoped_profiler_object_is_in_the_profile_once(tmp_path):
    """``_Scoped`` leaves the annotation to the tracer's bridge: a Task of
    a running session shows once in the profiler's trace, not twice."""
    profiler.set_config(filename=str(tmp_path / "profile.json"))
    profiler.set_state("run")
    try:
        if not profiler._state["jax_running"]:
            pytest.skip("no jax profile could be started here")
        with profiler.Domain("d").new_task("user.task"):
            pass
    finally:
        profiler.set_state("stop")
    assert len(_host_events(tmp_path)["user.task"]) == 1


# ---------------------------------------------------------------------------
# the scheduler's host time
# ---------------------------------------------------------------------------

def test_generation_iteration_spans_and_token_times():
    from mxnet_tpu.models import transformer_lm_tiny
    from mxnet_tpu.serving.generation import (DecodeEngine,
                                              GenerationScheduler)
    np.random.seed(0)
    net = transformer_lm_tiny(vocab_size=64)
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((1, 8), "int32")))
    eng = DecodeEngine(net, num_slots=2, max_seq=32, ladder=(8,))
    sched = GenerationScheduler(eng)
    try:
        tr.enable()
        time.sleep(0.2)                        # idle iterations only
        assert [e for e in tr.events()
                if e[1] == "generation.iteration"] == []
        req = sched.submit(np.arange(1, 6), max_new_tokens=4)
        tokens = req.result(timeout=120)
        time.sleep(0.15)                       # and idle again
    finally:
        tr.disable()
        sched.close()
        eng.close()
    assert len(tokens) == 4 and len(req.token_times) == 4
    assert req.token_times[0] == req.first_token_t
    assert req.token_times == sorted(req.token_times)
    assert req.admitted_t <= req.token_times[0] <= req.done_t
    spans = [e for e in tr.events() if e[0] == "X"]
    iterations = {e[6]: e for e in spans if e[1] == "generation.iteration"}
    # one prefill iteration (which also takes the first decode step) and
    # two more steps; the idle waits before and after recorded none
    assert len(iterations) == 3
    assert all(set(e[9]) == {"admits", "live"} for e in iterations.values())
    assert sorted(e[9]["admits"] for e in iterations.values()) == [0, 0, 1]
    for name in ("generation.prefill", "generation.step", "generation.emit"):
        children = [e for e in spans if e[1] == name]
        assert children, name
        assert all(e[7] in iterations for e in children), name
    assert len([e for e in spans if e[1] == "generation.step"]) == 3
    # the request's own trace keeps its link to the slot it was given
    admit, = [e for e in tr.events() if e[1] == "generation.admit"]
    assert admit[9]["slot"] in (0, 1)


def test_retry_attempts_become_instants():
    from mxnet_tpu.resilience.retry import RetryPolicy
    tr.enable()
    pol = RetryPolicy(max_attempts=3, base_delay_ms=1.0, jitter=0.0,
                      retryable=(ValueError,), sleep=lambda s: None,
                      name="obs_retry", register=False)
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise ValueError("transient")
        return "ok"

    assert pol.call(flaky) == "ok"
    retries = [e for e in tr.events() if e[1] == "retry.attempt"]
    assert len(retries) == 2
    assert all(e[9]["policy"] == "obs_retry" for e in retries)
    assert [e[9]["attempt"] for e in retries] == [1, 2]


def test_breaker_transitions_become_instants():
    from mxnet_tpu.resilience.breaker import CircuitBreaker
    tr.enable()
    clock = {"t": 0.0}
    br = CircuitBreaker(failure_threshold=2, recovery_ms=100.0,
                        clock=lambda: clock["t"], name="obs_breaker",
                        register=False)
    br.record_failure()
    br.record_failure()          # -> open
    clock["t"] = 0.2
    assert br.allow()            # -> half-open, probe admitted
    br.record_success()          # -> closed
    states = [e[9]["state"] for e in tr.events()
              if e[1] == "breaker.state"]
    assert states == ["open", "half_open", "closed"]


# ---------------------------------------------------------------------------
# trace_summary tool
# ---------------------------------------------------------------------------

def _load_trace_summary():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_summary.py")
    spec = importlib.util.spec_from_file_location("trace_summary", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summary_on_synthetic_trace(tmp_path):
    ts = _load_trace_summary()
    tr.enable()
    # synthesize a mixed workload entirely from explicit timestamps
    base = tr.now()
    tr.complete("trainer.chunk", base, base + 0.100, steps=4)
    tr.complete("trainer.chunk", base + 0.100, base + 0.220, steps=4)
    tr.complete("datafeed.consumer_wait", base + 0.100, base + 0.110,
                feed="f")
    tr.complete("serving.http", base, base + 0.050, request_id="rid-1")
    tr.complete("serving.queue_wait", base, base + 0.020,
                request_id="rid-1")
    tr.complete("cachedop.compile", base, base + 0.030, op="m")
    tr.instant("guardrails.skip", step=3)
    path = str(tmp_path / "synthetic.json")
    obs_export.dump_chrome_trace(path, tr.events())

    events, kept = ts.load_trace(path)
    summary = ts.summarize(events, top=3, kept=kept)
    cp = summary["critical_path"]
    assert cp["compute_ms"] == pytest.approx(220.0, rel=0.01)
    assert cp["stage_wait_ms"] == pytest.approx(10.0, rel=0.01)
    assert cp["queue_wait_ms"] == pytest.approx(20.0, rel=0.01)
    assert cp["compile_ms"] == pytest.approx(30.0, rel=0.01)
    assert summary["overlap_efficiency"] == pytest.approx(1 - 10.0 / 220.0,
                                                          rel=0.01)
    assert summary["instant_counts"]["guardrails.skip"] == 1
    assert len(summary["top_spans"]) == 3
    assert summary["top_spans"][0]["name"] == "trainer.chunk"
    rid_spans = [s for s in summary["top_spans"]
                 if s["request_id"] == "rid-1"]
    assert rid_spans or all(s["dur_ms"] >= 50.0
                            for s in summary["top_spans"])

    text = ts.format_summary(summary)
    assert "Critical path split" in text
    assert "overlap efficiency" in text
    assert "trainer.chunk" in text
    # the CLI entry point round-trips
    assert ts.main([path, "--top", "2"]) == 0


def test_trace_summary_shows_the_schedulers_self_time(tmp_path):
    ts = _load_trace_summary()
    tr.enable()
    base = tr.now()
    for i, (step_ms, emit_ms) in enumerate([(40, 5), (30, 2)]):
        t0 = base + 0.1 * i
        it = tr.complete("generation.iteration", t0, t0 + 0.060,
                         admits=0, live=2)
        tr.complete("generation.step", t0 + 0.002,
                    t0 + 0.002 + step_ms / 1e3, parent=it, slots=2)
        tr.complete("generation.emit", t0 + 0.050,
                    t0 + 0.050 + emit_ms / 1e3, parent=it, slots=2)
    path = str(tmp_path / "sched.json")
    obs_export.dump_chrome_trace(path, tr.events())
    events, kept = ts.load_trace(path)
    summary = ts.summarize(events, kept=kept)
    cp = summary["critical_path"]
    assert cp["scheduler_iterations"] == 2
    # 120 ms of iterations less 70 ms of steps and 7 ms of emits
    assert cp["scheduler_self_ms"] == pytest.approx(43.0, rel=0.01)
    assert cp["scheduler_emit_ms"] == pytest.approx(7.0, rel=0.01)
    text = ts.format_summary(summary)
    assert "scheduler host (self)" in text and "generation.emit" in text
    # a training trace has no such line
    assert "scheduler host" not in ts.format_summary(ts.summarize([]))


# ---------------------------------------------------------------------------
# full acceptance path: set_state("run") + request + step_stream + dump
# ---------------------------------------------------------------------------

def test_e2e_session_request_and_stream_in_one_dump(tmp_path):
    from mxnet_tpu.parallel import DeviceFeed
    from mxnet_tpu.serving import ModelServer
    profiler.set_config(filename=str(tmp_path / "profile.json"))
    profiler.set_state("run")
    try:
        with ModelServer(_linear, port=0, buckets=(1, 2), jit=False,
                         max_latency_ms=1) as srv:
            x = np.random.randn(D_IN).astype("float32")
            code, headers, _ = _post(srv.url + "/predict",
                                     {"data": x.tolist()})
            assert code == 200
            rid = headers["X-Request-Id"]
        trainer = _mlp_trainer()
        rng = np.random.RandomState(1)
        batches = [(rng.standard_normal((16, 8)).astype("float32"),
                    rng.randint(0, 4, 16).astype("float32"))
                   for _ in range(4)]
        with DeviceFeed(batches, mesh=trainer.mesh, depth=2,
                        name="obs.accept") as feed:
            trainer.step_stream(feed, chunk=2)
    finally:
        path = profiler.dump()  # finished=True also stops the session
    assert path == str(tmp_path / "profile.json")
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"serving.http", "serving.queue_wait", "serving.batch_execute",
            "trainer.chunk", "datafeed.stage"} <= names
    http = [e for e in _spans(doc, "serving.http")
            if e["args"]["request_id"] == rid]
    assert http, "served request missing its X-Request-Id span"
    assert not profiler._state["running"]
