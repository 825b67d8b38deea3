"""Performance attribution plane tests (ISSUE 12).

Acceptance criteria, on the CPU oracle:

- every compiled executable dispatched through the serving e2e path
  shows an arithmetic-intensity value and a bound-by classification in
  BOTH ``/metrics.prom`` (``mxtpu_roofline_*``) and
  ``tools/roofline_report.py`` output;
- ``tools/bench_diff.py --gate`` exits 2 on a synthetic 20% throughput
  regression (0 on noise, 3 on unreadable input);
- a SIGUSR2 flight-recorder dump under live load parses as valid JSON
  containing the last K step/request records;

plus the satellites: classification rules, knob registration +
enable/disable, fake-clock flight recorder, watchdog-stall dump wiring,
checksummed profile capture (server endpoint + gateway proxy), the
``benchmark/*.json`` schema audit, and ``tools/trace_summary.py`` exclusive (self) time.
"""
import glob
import importlib.util
import json
import os
import signal
import time
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.observability import attribution as attr
from mxnet_tpu.observability import export_prom as prom
from mxnet_tpu.observability import tracer as tr
from mxnet_tpu.serving import ModelServer

from test_telemetry import validate_prometheus_text

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool(name):
    path = os.path.join(REPO, "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_attribution():
    """Roofline/flight state is process-global: isolate every test."""
    def _reset():
        attr.roofline.reset()
        attr.configure()
        with attr.flight._lock:
            attr.flight._buf.clear()
            attr.flight._seq = 0
            attr.flight._dumps = 0
        tr.tracer.disable()
        tr.tracer.clear()
        tr.tracer.reset_phase_stats()
    _reset()
    yield
    _reset()


def _mlp_op(name="attr_mlp", d_in=32, d_hid=64, d_out=8):
    rng = np.random.default_rng(0)
    w1 = nd.array(rng.standard_normal((d_in, d_hid)).astype("float32"))
    w2 = nd.array(rng.standard_normal((d_hid, d_out)).astype("float32"))

    def fn(x):
        return nd.dot(nd.relu(nd.dot(x, w1)), w2)

    return CachedOp(fn, name=name), d_in


# ---------------------------------------------------------------------------
# classification rules
# ---------------------------------------------------------------------------

def test_classify_compute_vs_hbm_by_ridge():
    # AI 500 vs ridge 240 -> compute; AI 2 -> hbm (peak/bw unknown)
    bound, ai, achieved, ceiling = attr.classify(
        5e6, 1e4, 1e-3, peak=0, bw=0, ridge=240.0,
        overhead_fraction=0.05)
    assert (bound, ai) == (attr.COMPUTE_BOUND, 500.0)
    assert achieved == pytest.approx(5e9)
    assert ceiling is None
    bound, ai, _, _ = attr.classify(2e4, 1e4, 1e-3, peak=0, bw=0,
                                    ridge=240.0, overhead_fraction=0.05)
    assert (bound, ai) == (attr.HBM_BOUND, 2.0)


def test_classify_overhead_bound_under_known_ceiling():
    # AI 10 at bw 1e9 -> ceiling 1e10; achieved 1e6 << 5% of ceiling
    bound, _, achieved, ceiling = attr.classify(
        1e3, 100.0, 1e-3, peak=1e12, bw=1e9, ridge=1000.0,
        overhead_fraction=0.05)
    assert bound == attr.OVERHEAD_BOUND
    assert ceiling == pytest.approx(1e10)
    assert achieved == pytest.approx(1e6)
    # same program achieving 90% of ceiling is honestly hbm_bound
    bound, _, _, _ = attr.classify(1e3, 100.0, 1e3 / 9e9, peak=1e12,
                                   bw=1e9, ridge=1000.0,
                                   overhead_fraction=0.05)
    assert bound == attr.HBM_BOUND


def test_classify_unknown_without_cost_model():
    assert attr.classify(0.0, 0.0, 1e-3)[0] == attr.UNKNOWN
    assert attr.classify(10.0, 0.0, 1e-3)[0] == attr.UNKNOWN


def test_registry_snapshot_math():
    reg = attr.RooflineRegistry()
    reg.record("a", "sig1", 4, 100.0, 50.0, 0.010)
    reg.record("a", "sig1", 4, 100.0, 50.0, 0.030)
    reg.record("b", "sig2", 8, 10.0, 5.0, 0.010)
    snap = reg.snapshot()
    assert [r["op"] for r in snap] == ["a", "b"]  # sorted by total time
    a = snap[0]
    assert a["calls"] == 2
    assert a["total_s"] == pytest.approx(0.040)
    assert a["ai"] == pytest.approx(2.0)
    assert a["pct_of_total"] == pytest.approx(80.0)
    agg = reg.by_op_bucket()
    assert agg[("a", 4)]["calls"] == 2
    assert agg[("b", 8)]["total_s"] == pytest.approx(0.010)


def test_registry_cold_dispatch_registered_but_untimed():
    """The compile-paying first dispatch registers the executable but
    contributes no wall: per-call time comes from warm dispatches only,
    and an executable with ONLY a cold dispatch classifies by AI (never
    overhead_bound off a compile-inflated wall)."""
    reg = attr.RooflineRegistry()
    reg.record("cold", "sig", 2, 1e6, 1e4, None)      # cold: no wall
    snap = reg.snapshot()[0]
    assert snap["calls"] == 1 and snap["timed_calls"] == 0
    assert snap["total_s"] == 0.0
    assert snap["ai"] == pytest.approx(100.0)
    assert snap["bound"] == attr.HBM_BOUND            # AI 100 < ridge 240
    # a warm dispatch then sets the per-call wall alone
    reg.record("cold", "sig", 2, 1e6, 1e4, 0.004)
    snap = reg.snapshot()[0]
    assert snap["calls"] == 2 and snap["timed_calls"] == 1
    assert snap["total_s"] == pytest.approx(0.004)
    assert snap["achieved_flops_s"] == pytest.approx(1e6 / 0.004)


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_attribution_knobs_registered():
    from mxnet_tpu import config
    for name in ("MXNET_PROF_ATTRIBUTION", "MXNET_PROF_HBM_GBPS",
                 "MXNET_PROF_RIDGE", "MXNET_PROF_OVERHEAD_FRACTION",
                 "MXNET_PROF_CAPTURE_MAX_S", "MXNET_PROF_DIR",
                 "MXNET_FLIGHT_RECORDER", "MXNET_FLIGHT_RECORDS",
                 "MXNET_FLIGHT_DIR"):
        assert name in config.KNOBS, name
        assert config.KNOBS[name].disposition == "wired", name


def test_attribution_disabled_by_knob(monkeypatch):
    monkeypatch.setenv("MXNET_PROF_ATTRIBUTION", "0")
    monkeypatch.setenv("MXNET_FLIGHT_RECORDER", "0")
    attr.configure()
    assert not attr.attribution_enabled()
    attr.record_dispatch("off", "sig", 1, 1.0, 1.0, 0.001)
    attr.flight_note("nope")
    assert attr.snapshot() == []
    assert attr.flight.records() == []
    assert attr.flight_dump("nope") is None
    monkeypatch.delenv("MXNET_PROF_ATTRIBUTION")
    monkeypatch.delenv("MXNET_FLIGHT_RECORDER")
    attr.configure()
    assert attr.attribution_enabled()


def test_ridge_point_knob_override(monkeypatch):
    # CPU oracle: no peak/bw -> default ridge, overridable
    assert attr.ridge_point() == attr.DEFAULT_RIDGE_FLOP_PER_BYTE
    monkeypatch.setenv("MXNET_PROF_RIDGE", "12.5")
    assert attr.ridge_point() == 12.5
    # with peak+bw known the ridge is their quotient
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "2e12")
    monkeypatch.setenv("MXNET_PROF_HBM_GBPS", "1000")
    from mxnet_tpu.observability import telemetry
    n = len(telemetry._accel_devices())
    assert attr.peak_bytes_per_s() == pytest.approx(1e12 * n)
    assert attr.ridge_point() == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# CachedOp integration + exposition
# ---------------------------------------------------------------------------

def test_cachedop_dispatch_feeds_roofline():
    op, d_in = _mlp_op()
    x = nd.array(np.ones((4, d_in), "float32"))
    for _ in range(3):
        op(x)
    snap = attr.snapshot()
    assert len(snap) == 1
    row = snap[0]
    assert row["op"] == "attr_mlp" and row["bucket"] == 4
    assert row["calls"] == 3
    assert row["flops_per_call"] > 0 and row["bytes_per_call"] > 0
    assert row["ai"] == pytest.approx(
        row["flops_per_call"] / row["bytes_per_call"])
    assert row["bound"] in (attr.COMPUTE_BOUND, attr.HBM_BOUND,
                            attr.OVERHEAD_BOUND)
    # bytes ride the cache entry, keyed like flops_per_call
    assert list(op.bytes_per_call().values())[0] == \
        row["bytes_per_call"]
    # profiler aggregate rows carry the same counts
    from mxnet_tpu import profiler
    rows = profiler.get_aggregate_stats()
    assert rows["cachedop.roofline.attr_mlp|b4"]["calls"] == 3


def test_roofline_families_validate_and_carry_ai_and_bound():
    op, d_in = _mlp_op(name="prom_mlp")
    op(nd.array(np.ones((2, d_in), "float32")))
    parsed = validate_prometheus_text(prom.render_process())
    by_name = {}
    for name, labels, value, _ in parsed["samples"]:
        by_name.setdefault(name, []).append((labels, value))
    ai = [(l, v) for l, v in
          by_name.get("mxtpu_roofline_arithmetic_intensity", [])
          if l.get("op") == "prom_mlp"]
    assert ai and ai[0][0]["bucket"] == "2" and ai[0][1] > 0
    bound = [l for l, v in by_name.get("mxtpu_roofline_bound", [])
             if l.get("op") == "prom_mlp" and v == 1]
    assert bound and bound[0]["bound"] in (
        "compute_bound", "hbm_bound", "overhead_bound")
    assert ("mxtpu_roofline_ridge_flop_per_byte" in by_name)


# ---------------------------------------------------------------------------
# serving e2e acceptance: /metrics.prom + roofline_report + SIGUSR2
# ---------------------------------------------------------------------------

def _post(url, doc):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req) as r:
        return r.status, json.loads(r.read())


def test_serving_e2e_every_executable_attributed(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "flight"))
    attr.configure()
    rng = np.random.default_rng(1)
    w = nd.array(rng.standard_normal((16, 4)).astype("float32"))

    def model(x):
        return nd.dot(x, w)

    rr = _tool("roofline_report")
    import threading
    with ModelServer(model, port=0, buckets=(1, 4),
                     max_latency_ms=40.0, max_batch_size=4) as srv:
        # hit BOTH buckets so two executables compile and dispatch:
        # sequential singles pad to bucket 1, a burst of 4 concurrent
        # requests coalesces into one bucket-4 batch
        for _ in range(3):
            _post(srv.url + "/predict", {"data": [0.5] * 16})
        threads = [threading.Thread(
            target=_post, args=(srv.url + "/predict",
                                {"data": [0.5] * 16}))
            for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        with urllib.request.urlopen(srv.url + "/metrics.prom") as r:
            text = r.read().decode()
        dispatched = {str(b) for b
                      in srv.engine.stats()["buckets_seen"]}
        # SIGUSR2 under live load: the handler dumps the ring
        assert attr.install_flight_signal_handler()
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5.0
        dumps = []
        while time.monotonic() < deadline and not dumps:
            dumps = glob.glob(str(tmp_path / "flight" / "*.json"))
            time.sleep(0.01)
    parsed = validate_prometheus_text(text)
    # EVERY executable the engine dispatched is attributed — and the
    # workload really exercised both rungs of the ladder
    assert "1" in dispatched and "4" in dispatched
    engine_buckets = {
        labels["bucket"]
        for name, labels, _, _ in parsed["samples"]
        if name == "mxtpu_roofline_arithmetic_intensity"
        and labels.get("op") == "inference_engine"}
    assert engine_buckets == dispatched
    bounds = {
        labels["bucket"]: labels["bound"]
        for name, labels, v, _ in parsed["samples"]
        if name == "mxtpu_roofline_bound" and v == 1
        and labels.get("op") == "inference_engine"}
    assert set(bounds) == dispatched
    assert all(b in ("compute_bound", "hbm_bound", "overhead_bound")
               for b in bounds.values())

    # the report tool reads the same scrape and ranks both executables
    rows, ridge = rr.parse_prometheus(text)
    engine_rows = [r for r in rows if r["op"] == "inference_engine"]
    assert {r["bucket"] for r in engine_rows} == dispatched
    assert all(r["bound"] in ("compute_bound", "hbm_bound",
                              "overhead_bound") for r in engine_rows)
    assert ridge == pytest.approx(attr.ridge_point())
    report = rr.format_report(
        sorted(rows, key=lambda r: -r["total_s"]), ridge=ridge)
    assert "inference_engine" in report and "bound" in report

    # the SIGUSR2 dump is valid JSON holding the request records
    assert dumps, "SIGUSR2 produced no flight dump"
    with open(dumps[0]) as f:
        doc = json.load(f)
    assert doc["reason"] == "sigusr2"
    kinds = {rec["kind"] for rec in doc["records"]}
    assert "request" in kinds and "dispatch" in kinds
    reqs = [r for r in doc["records"] if r["kind"] == "request"]
    assert all(r["status"] == 200 and r["wall_ms"] > 0 for r in reqs)


def test_roofline_report_keeps_fleet_ranks_separate():
    """A merged fleet scrape stamps rank= on every sample; the report
    must not last-win one rank's numbers over another's."""
    rr = _tool("roofline_report")
    text = (
        "# HELP mxtpu_roofline_seconds c\n"
        "# TYPE mxtpu_roofline_seconds counter\n"
        'mxtpu_roofline_seconds_total{op="eng",bucket="8",rank="0"} 2.0\n'
        'mxtpu_roofline_seconds_total{op="eng",bucket="8",rank="1"} 6.0\n'
        "# EOF\n")
    rows, _ridge = rr.parse_prometheus(text)
    assert len(rows) == 2
    assert sorted((r["rank"], r["total_s"]) for r in rows) == \
        [("0", 2.0), ("1", 6.0)]
    assert [r["pct_of_total"] for r in
            sorted(rows, key=lambda r: r["rank"])] == \
        pytest.approx([25.0, 75.0])
    report = rr.format_report(sorted(rows,
                                     key=lambda r: -r["total_s"]))
    assert "eng@r1" in report and "eng@r0" in report


def test_capture_window_survives_full_trace_ring(tmp_path):
    """The window filter is by timestamp, not ring index: a ring at
    capacity evicting records during the capture must still yield the
    window's spans (the len()-slice bug class)."""
    tr.tracer.set_capacity(8)
    tr.enable()
    base = tr.now()
    for i in range(8):   # fill the ring with pre-window spans
        tr.complete("old.span", base - 10.0, base - 9.0, idx=i)

    def _busy_sleep(_s):
        now = tr.now()
        for i in range(8):   # evict every pre-window record
            tr.complete("window.span", now, now + 0.001, idx=i)

    man = attr.capture_profile(0.001, out_dir=str(tmp_path / "cap"),
                               sleep=_busy_sleep)
    with open(os.path.join(man["dir"], "host_trace.json")) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert names == {"window.span"}
    assert man["host_span_events"] == 8
    tr.tracer.set_capacity(tr.DEFAULT_BUFFER)


def test_roofline_report_from_capture_artifact(tmp_path):
    op, d_in = _mlp_op(name="report_mlp")
    op(nd.array(np.ones((2, d_in), "float32")))
    man = attr.capture_profile(0.0, out_dir=str(tmp_path / "cap"))
    rr = _tool("roofline_report")
    rows, ridge = rr.load_rows(
        os.path.join(man["dir"], "attribution.json"))
    assert any(r["op"] == "report_mlp" for r in rows)
    assert ridge == pytest.approx(attr.ridge_point())
    # unreadable input is a typed exit, not a traceback
    assert rr.main([str(tmp_path / "nope.json")]) == 2


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_fake_clock_ring_and_dump(tmp_path):
    t = [100.0]
    w = [1.7e9]
    rec = attr.FlightRecorder(capacity=3, clock=lambda: t[0],
                              wall_clock=lambda: w[0])
    for i in range(5):
        t[0] += 1.0
        w[0] += 1.0
        rec.note("step", step=i)
    records = rec.records()
    assert len(records) == 3                    # drop-oldest bound
    assert [r["step"] for r in records] == [2, 3, 4]
    assert [r["seq"] for r in records] == [3, 4, 5]
    assert records[-1]["t_mono"] == 105.0
    assert records[-1]["t_wall"] == 1.7e9 + 5.0
    path = rec.dump("unit_test", path=str(tmp_path / "f.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit_test"
    assert doc["capacity"] == 3
    assert [r["step"] for r in doc["records"]] == [2, 3, 4]
    assert rec.stats()["dumps"] == 1
    rec.set_capacity(2)
    assert [r["step"] for r in rec.records()] == [3, 4]


def test_watchdog_stall_dumps_flight_ring(tmp_path, monkeypatch):
    from mxnet_tpu.resilience.guardrails import StepWatchdog
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    attr.configure()
    attr.flight_note("step", step=41)
    t = [0.0]
    wd = StepWatchdog(deadline_ms=100.0, clock=lambda: t[0],
                      name="attrtest")
    wd._thread = object()   # block the real poll thread from starting
    wd.watch(7, lambda: False)
    t[0] = 0.5
    assert wd._scan() == "stall"
    dumps = glob.glob(str(tmp_path / "flight_watchdog_stall_*.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        doc = json.load(f)
    kinds = [r["kind"] for r in doc["records"]]
    assert kinds[0] == "step" and "watchdog_stall" in kinds
    stall = [r for r in doc["records"]
             if r["kind"] == "watchdog_stall"][0]
    assert stall["step"] == 7 and stall["elapsed_s"] == pytest.approx(0.5)
    wd._thread = None


# ---------------------------------------------------------------------------
# on-demand profile capture
# ---------------------------------------------------------------------------

def test_capture_profile_checksummed_artifacts(tmp_path):
    import hashlib
    op, d_in = _mlp_op(name="cap_mlp")
    op(nd.array(np.ones((2, d_in), "float32")))
    man = attr.capture_profile(0.0, out_dir=str(tmp_path / "cap"))
    names = {f["name"] for f in man["files"]}
    assert {"host_trace.json", "flight.json",
            "attribution.json"} <= names
    for f in man["files"]:
        path = os.path.join(man["dir"], f["name"])
        with open(path, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()
        assert digest == f["sha256"], f["name"]
        assert os.path.getsize(path) == f["bytes"]
    with open(os.path.join(man["dir"], "manifest.json")) as fh:
        assert json.load(fh)["files"] == man["files"]
    # attribution.json is roofline_report input (checked elsewhere);
    # host_trace.json is a loadable Chrome trace document
    with open(os.path.join(man["dir"], "host_trace.json")) as fh:
        assert "traceEvents" in json.load(fh)


def test_capture_profile_busy_and_clamped(monkeypatch):
    monkeypatch.setenv("MXNET_PROF_CAPTURE_MAX_S", "0.01")
    slept = []
    man = attr.capture_profile(100.0, sleep=slept.append)
    assert man["seconds_requested"] == pytest.approx(0.01)  # clamped
    assert slept == [pytest.approx(0.01)]
    assert attr._capture_lock.acquire(blocking=False)
    try:
        with pytest.raises(attr.CaptureBusy):
            attr.capture_profile(0.0)
    finally:
        attr._capture_lock.release()


def test_debug_profile_endpoint_admin_guarded(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_SERVING_ADMIN_TOKEN", "hunter2")
    monkeypatch.setenv("MXNET_PROF_DIR", str(tmp_path / "profiles"))
    with ModelServer(lambda x: x * 2.0, port=0, buckets=(1,), jit=False,
                     max_latency_ms=0.5) as srv:
        req = urllib.request.Request(
            srv.url + "/debug/profile?seconds=0", data=b"{}")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 403
        # a valid-JSON non-dict body is a clean 400, not a dropped
        # connection
        bad = urllib.request.Request(
            srv.url + "/debug/profile?seconds=0", data=b"[1]")
        bad.add_header("X-Admin-Token", "hunter2")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
        req.add_header("X-Admin-Token", "hunter2")
        with urllib.request.urlopen(req) as r:
            man = json.loads(r.read())
        assert man["dir"].startswith(str(tmp_path / "profiles"))
        assert {f["name"] for f in man["files"]} >= {"flight.json"}
        # /debug/flight: the HTTP twin of kill -USR2
        freq = urllib.request.Request(srv.url + "/debug/flight",
                                      data=b"")
        freq.add_header("X-Admin-Token", "hunter2")
        monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "fl"))
        with urllib.request.urlopen(freq) as r:
            out = json.loads(r.read())
        assert os.path.exists(out["path"])


def test_gateway_proxies_profile_to_named_replica(tmp_path,
                                                  monkeypatch):
    import urllib.error
    from mxnet_tpu.serving.gateway import Gateway
    monkeypatch.setenv("MXNET_PROF_DIR", str(tmp_path / "profiles"))
    with ModelServer(lambda x: x * 3.0, port=0, buckets=(1,), jit=False,
                     max_latency_ms=0.5) as srv:
        gw = Gateway(replicas=[srv.url], scrape_ms=0,
                     retry_policy=False, bind_profiler=False)
        try:
            gw.scrape_once()
            gw.start()
            rid = next(iter(r.id for r in gw.replicas()))
            req = urllib.request.Request(
                gw.url + "/debug/profile?replica=%d&seconds=0" % rid,
                data=b"{}")
            with urllib.request.urlopen(req) as r:
                man = json.loads(r.read())
            assert "files" in man and man["pid"] == os.getpid()
            # unknown replica is a typed 404
            bad = urllib.request.Request(
                gw.url + "/debug/profile?replica=99&seconds=0",
                data=b"{}")
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad)
            assert ei.value.code == 404
        finally:
            gw.close()


# ---------------------------------------------------------------------------
# bench_diff: the regression ledger gate
# ---------------------------------------------------------------------------

def _bd():
    return _tool("bench_diff")


def test_bench_diff_gates_20pct_throughput_regression(tmp_path):
    bd = _bd()
    base = {"metric": "resnet50_train_img_per_sec_per_chip_b32",
            "value": 2782.55, "unit": "img/s", "vs_baseline": 9.321,
            "compile_s": 69.2}
    regressed = dict(base, value=2226.0, vs_baseline=7.457)  # -20%
    bp = tmp_path / "base.json"
    rp = tmp_path / "reg.json"
    bp.write_text(json.dumps(base))
    rp.write_text(json.dumps(regressed))
    assert bd.main([str(bp), str(rp), "--gate", "--json-only"]) == 2
    # noise inside tolerance passes
    np_ = tmp_path / "noise.json"
    np_.write_text(json.dumps(dict(base, value=2755.0)))
    assert bd.main([str(bp), str(np_), "--gate", "--json-only"]) == 0
    # an IMPROVEMENT never gates
    ip = tmp_path / "imp.json"
    ip.write_text(json.dumps(dict(base, value=3500.0,
                                  vs_baseline=11.7)))
    assert bd.main([str(bp), str(ip), "--gate", "--json-only"]) == 0


def test_bench_diff_unreadable_exits_3(tmp_path):
    bd = _bd()
    good = tmp_path / "g.json"
    good.write_text(json.dumps({"value": 1.0, "unit": "img/s"}))
    assert bd.main([str(good), str(tmp_path / "missing.json"),
                    "--gate"]) == 3
    bad = tmp_path / "bad.json"
    bad.write_text("{truncated")
    assert bd.main([str(good), str(bad), "--gate"]) == 3
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert bd.main([str(good), str(empty), "--gate"]) == 3
    # disjoint artifacts have nothing to compare: also the 3 class
    other = tmp_path / "other.json"
    other.write_text(json.dumps({"different_metric": 5.0}))
    assert bd.main([str(good), str(other), "--gate"]) == 3


def test_bench_diff_directions_and_round_files(tmp_path):
    bd = _bd()
    # latency regression: lower-better by unit declaration
    base = {"sections": {"serving": {"value": 5.0, "unit": "ms"}},
            "p99_ms": 10.0, "hits": 100}
    worse = {"sections": {"serving": {"value": 9.0, "unit": "ms"}},
             "p99_ms": 10.0, "hits": 100}
    v = bd.diff(base, worse)
    assert v["status"] == "regression"
    assert v["regressions"][0]["metric"] == "sections.serving.value"
    # name heuristics: p99 down is improvement, hits down regression
    v2 = bd.diff(base, {"sections": {"serving": {"value": 5.0,
                                                 "unit": "ms"}},
                        "p99_ms": 5.0, "hits": 50})
    assert [r["metric"] for r in v2["regressions"]] == ["hits"]
    assert [r["metric"] for r in v2["improvements"]] == ["p99_ms"]
    # explicit override beats inference
    v3 = bd.diff(base, worse, overrides={"sections.serving.value":
                                         bd.INFO})
    assert v3["status"] == "ok"
    # BENCH_r0x round files compare their parsed payload
    r1 = tmp_path / "r1.json"
    r2 = tmp_path / "r2.json"
    r1.write_text(json.dumps({"n": 4, "cmd": "python round.py", "rc": 0,
                              "tail": "...", "parsed": {
                                  "value": 100.0, "unit": "img/s"}}))
    r2.write_text(json.dumps({"n": 6, "cmd": "python round.py", "rc": 0,
                              "tail": "...", "parsed": {
                                  "value": 70.0, "unit": "img/s"}}))
    assert bd.main([str(r1), str(r2), "--gate", "--json-only"]) == 2


# ---------------------------------------------------------------------------
# schema audit: every benchmark artifact records its backend
# ---------------------------------------------------------------------------

def _artifact_records(doc):
    return doc if isinstance(doc, list) else [doc]


def test_benchmark_artifacts_record_backend_and_cpu_caveat():
    """Every ``benchmark/*.json`` must say which backend produced it
    (``platform``/``backend``/``device_kind``), and any CPU-produced
    artifact must carry a ``cpu_caveat`` — previously convention,
    now contract (the writers share ``benchmark/_artifact.stamp``)."""
    paths = sorted(glob.glob(os.path.join(REPO, "benchmark", "*.json")))
    assert paths, "no benchmark artifacts found"
    offenders = []
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        for i, rec in enumerate(_artifact_records(doc)):
            where = "%s[%d]" % (os.path.basename(path), i)
            plat = (rec.get("platform") or rec.get("backend")
                    or rec.get("device_kind"))
            if not plat:
                offenders.append("%s: no platform/backend" % where)
                continue
            if str(rec.get("platform", plat)).lower() == "cpu" \
                    and not rec.get("cpu_caveat"):
                offenders.append("%s: CPU artifact without cpu_caveat"
                                 % where)
    assert not offenders, offenders


def test_artifact_stamp_helper():
    import sys
    sys.path.insert(0, REPO)
    try:
        from benchmark._artifact import stamp
    finally:
        sys.path.remove(REPO)
    out = stamp({"x": 1}, platform="cpu")
    assert out["cpu_caveat"] and out["platform"] == "cpu"
    tpu = stamp({"x": 1}, platform="tpu", device_kind="TPU v5 lite")
    assert "cpu_caveat" not in tpu and tpu["device_kind"]
    # an artifact that already carries its own caveat keeps it
    keep = stamp({"platform": "cpu", "cpu_caveat": "mine"},
                 platform="cpu")
    assert keep["cpu_caveat"] == "mine"


# ---------------------------------------------------------------------------
# trace_summary exclusive time
# ---------------------------------------------------------------------------

def test_trace_summary_exclusive_time_no_double_count(tmp_path):
    from mxnet_tpu.observability import export as obs_export
    ts = _tool("trace_summary")
    tr.enable()
    # a parent span fully containing a compile child: the old critical
    # path counted the compile into BOTH rows
    with tr.span("serving.http", request_id="rid-x") as root:
        base = tr.now()
        tr.complete("cachedop.compile", base, base + 0.030,
                    parent=root.ctx, op="m")
        time.sleep(0.05)
    path = str(tmp_path / "t.json")
    obs_export.dump_chrome_trace(path, tr.events())
    events, kept = ts.load_trace(path)
    summary = ts.summarize(events, top=5, kept=kept)
    names = summary["by_name"]
    http = names["serving.http"]
    compile_row = names["cachedop.compile"]
    assert compile_row["self_ms"] == pytest.approx(30.0, rel=0.05)
    # parent self excludes the child entirely
    assert http["self_ms"] == pytest.approx(http["total_ms"] - 30.0,
                                            rel=0.05)
    cp = summary["critical_path"]
    assert cp["basis"] == "exclusive"
    assert cp["compile_ms"] == pytest.approx(30.0, rel=0.05)
    assert cp["serving_self_ms"] == pytest.approx(
        cp["serving_ms"] - 30.0, rel=0.05)
    top_http = [s for s in summary["top_spans"]
                if s["name"] == "serving.http"][0]
    assert top_http["self_ms"] < top_http["dur_ms"]
    text = ts.format_summary(summary)
    assert "self ms" in text and "EXCLUSIVE" in text
