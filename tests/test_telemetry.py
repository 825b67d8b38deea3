"""Production telemetry plane tests (ISSUE 9).

Covers the acceptance criteria on the CPU oracle:

- ``/metrics.prom`` passes a STRICT Prometheus text-format validator
  (HELP/TYPE discipline, family contiguity, label-name/escape syntax,
  histogram cumulativity + ``+Inf``/``_sum``/``_count`` invariants,
  OpenMetrics exemplar syntax, the ``mxtpu_`` naming convention);
- reported FLOPs/MFU on a known MLP are within 5% of the analytic
  count (XLA cost model == hand-computed matmul FLOPs);
- the tail sampler keeps 100% of error spans under a synthetic
  5%-error load, random keeps respect the token-bucket budget, and
  kept trace ids surface as histogram exemplars;
- a merged multi-worker scrape carries per-rank labels and still
  validates;
plus the satellites: ring-drop counter + warn-once, the
``telemetry.memory_probe_errors`` counter (no more silent ``(0, 0)``),
the grep-driven MXNET_* knob audit, and ``tools/trace_summary.py``'s
graceful handling of missing/empty/corrupt traces with kept-exemplar
request ids in the top-N table.
"""
import importlib.util
import json
import os
import re
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, profiler
from mxnet_tpu.cached_op import CachedOp
from mxnet_tpu.observability import export_prom as prom
from mxnet_tpu.observability import telemetry
from mxnet_tpu.observability import tracer as tr
from mxnet_tpu.serving import ModelRegistry, ModelServer

D = 4


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Process-global telemetry state must not leak between tests."""
    def _reset():
        tr.tracer.disable()
        tr.tracer.set_sampler(None)
        tr.tracer.clear()
        tr.tracer.reset_phase_stats()
        tr.tracer.set_capacity(tr.DEFAULT_BUFFER)
        telemetry.flops_meter.reset()
        with telemetry._mem_lock:
            telemetry._probe_errors = 0
            telemetry._probe_warned = False
            telemetry._mem_peak.clear()
        profiler._state["running"] = False
        profiler._state["paused"] = False
    _reset()
    yield
    _reset()


def _times(k):
    def fn(x):
        return x * float(k)
    return fn


def _tool(name):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# strict Prometheus text-format validator
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*$")
_VALUE_RE = re.compile(
    r"(?:[+-]?Inf|NaN|[+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?)$")
_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")


def _parse_labels(body):
    """``a="v",b="w"`` -> dict; asserts names, escaping, and syntax."""
    out = {}
    i = 0
    n = len(body)
    while i < n:
        eq = body.index("=", i)
        name = body[i:eq]
        assert _LABEL_RE.match(name), "bad label name %r" % name
        assert body[eq + 1] == '"', "label value must be quoted"
        j = eq + 2
        val = []
        while True:
            assert j < n, "unterminated label value"
            ch = body[j]
            if ch == "\\":
                assert j + 1 < n and body[j + 1] in ('\\', '"', 'n'), \
                    "illegal escape \\%s" % body[j + 1:j + 2]
                val.append({"\\": "\\", '"': '"', "n": "\n"}[body[j + 1]])
                j += 2
            elif ch == '"':
                break
            else:
                assert ch != "\n", "raw newline in label value"
                val.append(ch)
                j += 1
        assert name not in out, "duplicate label %s" % name
        out[name] = "".join(val)
        i = j + 1
        if i < n:
            assert body[i] == ",", "labels must be comma-separated"
            i += 1
    return out


def _split_sample(line):
    """``name[{labels}] value [# {ex} v]`` -> (name, labels, value,
    exemplar|None), asserting syntax along the way."""
    m = re.match(r"([a-zA-Z_:][a-zA-Z0-9_:]*)", line)
    assert m, "bad metric name in %r" % line
    name = m.group(1)
    rest = line[len(name):]
    labels = {}
    if rest.startswith("{"):
        depth_i = 1
        in_q = False
        esc = False
        while True:
            assert depth_i < len(rest), "unterminated label block"
            ch = rest[depth_i]
            if esc:
                esc = False
            elif ch == "\\":
                esc = True
            elif ch == '"':
                in_q = not in_q
            elif ch == "}" and not in_q:
                break
            depth_i += 1
        labels = _parse_labels(rest[1:depth_i])
        rest = rest[depth_i + 1:]
    assert rest.startswith(" "), "missing space before value in %r" % line
    rest = rest[1:]
    exemplar = None
    if " # " in rest:
        value_str, ex = rest.split(" # ", 1)
        assert ex.startswith("{"), "exemplar must start with labels"
        close = ex.index("}")
        ex_labels = _parse_labels(ex[1:close])
        ex_rest = ex[close + 1:].strip()
        parts = ex_rest.split()
        assert parts and _VALUE_RE.match(parts[0]), \
            "bad exemplar value %r" % ex_rest
        assert len(parts) <= 2, "exemplar is value [timestamp]"
        exemplar = (ex_labels, float(parts[0]))
    else:
        value_str = rest
    parts = value_str.split()
    assert parts and _VALUE_RE.match(parts[0]), \
        "bad sample value %r in %r" % (value_str, line)
    assert len(parts) <= 2, "sample is value [timestamp]"
    value = float(parts[0].replace("Inf", "inf").replace("NaN", "nan"))
    return name, labels, value, exemplar


def validate_prometheus_text(text, require_prefix="mxtpu_"):
    """Strict OpenMetrics exposition validation (the one format in
    which exemplars are legal — classic 0.0.4 parsers read them as a
    bad timestamp and reject the whole scrape); returns
    ``{"types": {...}, "samples": [(name, labels, value, exemplar)]}``
    so tests can assert on parsed content too."""
    assert text.endswith("\n"), "exposition must end with a newline"
    assert text.splitlines()[-1] == "# EOF", \
        "OpenMetrics exposition must terminate with # EOF"
    types = {}
    helps = {}
    current = None
    closed = set()
    samples = []
    for line in text.splitlines():
        assert line == line.rstrip(), "trailing whitespace in %r" % line
        if not line:
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            assert len(parts) >= 4, "HELP needs name and text"
            name = parts[2]
            assert _NAME_RE.match(name)
            assert name not in helps, "duplicate HELP for %s" % name
            # only \\ and \n escapes are legal in help text
            i = 0
            while i < len(parts[3]):
                if parts[3][i] == "\\":
                    assert parts[3][i + 1:i + 2] in ("\\", "n"), \
                        "illegal escape in HELP text"
                    i += 2
                else:
                    i += 1
            helps[name] = parts[3]
        elif line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, "TYPE is '# TYPE name type'"
            name, mtype = parts[2], parts[3]
            assert _NAME_RE.match(name)
            assert mtype in _TYPES, "unknown type %s" % mtype
            assert name not in types, "duplicate TYPE for %s" % name
            assert name not in closed, "family %s not contiguous" % name
            types[name] = mtype
            if current is not None and current != name:
                closed.add(current)
            current = name
        elif line.startswith("#"):
            continue
        else:
            name, labels, value, exemplar = _split_sample(line)
            family = name
            for suffix in ("_bucket", "_sum", "_count", "_total"):
                if name.endswith(suffix) and name[:-len(suffix)] in types:
                    family = name[:-len(suffix)]
                    break
            assert family in types, "sample %s has no # TYPE" % name
            if require_prefix:
                assert family.startswith(require_prefix), \
                    "metric %s outside the %s namespace" % (family,
                                                            require_prefix)
            assert family not in closed, \
                "family %s not contiguous" % family
            if current != family:
                if current is not None:
                    closed.add(current)
                current = family
            mtype = types[family]
            if mtype == "counter":
                # OpenMetrics: the family is declared WITHOUT _total,
                # every sample carries it
                assert name == family + "_total", \
                    "counter sample %s must be %s_total" % (name, family)
                assert value >= 0 or value != value
            elif mtype == "gauge":
                assert name == family
                assert exemplar is None, "exemplars are for counters/" \
                    "histograms, not gauge %s" % name
            elif mtype == "histogram":
                assert name != family, \
                    "histogram %s needs _bucket/_sum/_count children" \
                    % family
                if name.endswith("_bucket"):
                    assert "le" in labels, "_bucket needs an le label"
            samples.append((name, labels, value, exemplar))

    # histogram invariants: cumulative buckets ending at +Inf, with
    # _count == the +Inf bucket and a _sum, per label set
    hist = {}
    for name, labels, value, exemplar in samples:
        for family, mtype in types.items():
            if mtype != "histogram":
                continue
            if name.startswith(family + "_"):
                key = tuple(sorted((k, v) for k, v in labels.items()
                                   if k != "le"))
                ent = hist.setdefault((family, key),
                                      {"buckets": [], "sum": None,
                                       "count": None})
                if name == family + "_bucket":
                    ent["buckets"].append((labels["le"], value))
                elif name == family + "_sum":
                    ent["sum"] = value
                elif name == family + "_count":
                    ent["count"] = value
    for (family, key), ent in hist.items():
        assert ent["buckets"], "%s %s: no buckets" % (family, key)
        les = [le for le, _ in ent["buckets"]]
        assert les[-1] == "+Inf", "%s: buckets must end at +Inf" % family
        bounds = [float(le.replace("+Inf", "inf")) for le in les]
        assert bounds == sorted(bounds), "%s: le not ascending" % family
        values = [v for _, v in ent["buckets"]]
        assert values == sorted(values), \
            "%s: buckets not cumulative" % family
        assert ent["sum"] is not None, "%s: missing _sum" % family
        assert ent["count"] == values[-1], \
            "%s: _count != +Inf bucket" % family
    return {"types": types, "helps": helps, "samples": samples}


def _sample_map(parsed):
    return {(name, tuple(sorted(labels.items()))): value
            for name, labels, value, _ in parsed["samples"]}


# ---------------------------------------------------------------------------
# validator self-tests: it must actually be strict
# ---------------------------------------------------------------------------

def test_validator_accepts_minimal_valid():
    text = ("# HELP mxtpu_x a counter\n"
            "# TYPE mxtpu_x counter\n"
            'mxtpu_x_total{a="b"} 3\n'
            "# EOF\n")
    parsed = validate_prometheus_text(text)
    assert parsed["samples"] == [("mxtpu_x_total", {"a": "b"}, 3.0, None)]


@pytest.mark.parametrize("bad", [
    # missing the # EOF terminator
    "# HELP mxtpu_x c\n# TYPE mxtpu_x counter\nmxtpu_x_total 1\n",
    # sample with no TYPE
    "mxtpu_x_total 1\n# EOF\n",
    # counter sample without the _total suffix
    "# HELP mxtpu_x c\n# TYPE mxtpu_x counter\nmxtpu_x 1\n# EOF\n",
    # counter family declared WITH _total (classic style, not OpenMetrics)
    "# HELP mxtpu_x_total c\n# TYPE mxtpu_x_total counter\n"
    "mxtpu_x_total 1\n# EOF\n",
    # illegal escape in a label value
    "# HELP mxtpu_x c\n# TYPE mxtpu_x counter\n"
    'mxtpu_x_total{a="\\q"} 1\n# EOF\n',
    # histogram without +Inf
    "# HELP mxtpu_h h\n# TYPE mxtpu_h histogram\n"
    'mxtpu_h_bucket{le="1"} 1\nmxtpu_h_sum 1\nmxtpu_h_count 1\n# EOF\n',
    # non-cumulative histogram
    "# HELP mxtpu_h h\n# TYPE mxtpu_h histogram\n"
    'mxtpu_h_bucket{le="1"} 5\nmxtpu_h_bucket{le="+Inf"} 3\n'
    "mxtpu_h_sum 1\nmxtpu_h_count 3\n# EOF\n",
    # interleaved (non-contiguous) families
    "# HELP mxtpu_a a\n# TYPE mxtpu_a counter\n"
    "# HELP mxtpu_b b\n# TYPE mxtpu_b counter\n"
    "mxtpu_b_total 1\nmxtpu_a_total 1\nmxtpu_b_total 2\n# EOF\n",
    # duplicate TYPE
    "# TYPE mxtpu_x counter\n# TYPE mxtpu_x counter\n"
    "mxtpu_x_total 1\n# EOF\n",
    # exemplar on a gauge
    "# HELP mxtpu_g g\n# TYPE mxtpu_g gauge\n"
    'mxtpu_g 1 # {trace_id="a"} 1\n# EOF\n',
])
def test_validator_rejects(bad):
    with pytest.raises(AssertionError):
        validate_prometheus_text(bad)


def test_label_escaping_roundtrip():
    w = prom.PromWriter()
    weird = 'quo"te back\\slash new\nline'
    w.gauge("mxtpu_test_escape", "help with back\\slash", 1.5,
            labels={"model": weird})
    parsed = validate_prometheus_text(w.text())
    (name, labels, value, _), = parsed["samples"]
    assert name == "mxtpu_test_escape"
    assert labels["model"] == weird
    assert value == 1.5


# ---------------------------------------------------------------------------
# the exposition: process + HTTP endpoint + fleet lanes
# ---------------------------------------------------------------------------

def test_render_process_validates():
    validate_prometheus_text(prom.render_process())


def test_rank_const_label_from_launcher_env(monkeypatch):
    monkeypatch.setenv("MXTPU_PROCESS_ID", "7")
    parsed = validate_prometheus_text(prom.render_process())
    with_labels = [labels for _, labels, _, _ in parsed["samples"]]
    assert with_labels and all(l.get("rank") == "7" for l in with_labels)


def test_server_metrics_prom_endpoint_e2e():
    telemetry.install_tail_sampler(fraction=0.0, budget_per_s=0.0)
    tr.enable()
    with ModelServer(_times(2), port=0, buckets=(1, 2), jit=False,
                     max_latency_ms=1.0) as srv:
        url = srv.url
        for _ in range(4):
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"data": [1.0] * D}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req).read()
        with urllib.request.urlopen(url + "/metrics.prom") as r:
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text")
            text = r.read().decode()
        with urllib.request.urlopen(
                url + "/metrics?format=prometheus") as r2:
            text2 = r2.read().decode()
        # the JSON surface must be untouched
        with urllib.request.urlopen(url + "/metrics") as r3:
            snap = json.loads(r3.read())
    for t in (text, text2):
        parsed = validate_prometheus_text(t)
        values = _sample_map(parsed)
        assert values[("mxtpu_serving_requests_total", ())] == 4.0
        assert values[("mxtpu_serving_ok_total", ())] == 4.0
        assert ("mxtpu_serving_latency_ms",
                (("quantile", "p99"),)) in values
    assert snap["requests"] == 4
    assert "telemetry" in snap and "flops_total" in snap["telemetry"]
    # the request phase histogram made it out, with TYPE histogram
    assert parsed["types"]["mxtpu_trace_phase_duration_ms"] == "histogram"
    phases = {labels.get("phase") for name, labels, _, _
              in parsed["samples"]
              if name == "mxtpu_trace_phase_duration_ms_bucket"}
    assert "serving.http" in phases


def test_fleet_lanes_labelled_per_model_version():
    with ModelRegistry(name="promreg") as reg:
        reg.load("alpha", "v1", source=_times(1), jit=False)
        reg.load("beta", "v2", source=_times(3), jit=False)
        for rid in ("a", "b", "c"):
            reg.predict(np.ones(D, "float32"), model="alpha",
                        request_id=rid)
        reg.predict(np.ones(D, "float32"), model="beta", request_id="d")
        w = prom.PromWriter()
        prom._render_fleet(w, reg)
        parsed = validate_prometheus_text(w.text())
        values = _sample_map(parsed)
        assert values[("mxtpu_serving_requests_total",
                       (("model", "alpha"), ("version", "v1")))] == 3.0
        assert values[("mxtpu_serving_requests_total",
                       (("model", "beta"), ("version", "v2")))] == 1.0
        assert values[("mxtpu_fleet_version_state",
                       (("model", "alpha"), ("state", "live"),
                        ("version", "v1")))] == 1.0
        assert ("mxtpu_fleet_pointer",
                (("model", "alpha"), ("role", "serving"),
                 ("version", "v1"))) in values


# ---------------------------------------------------------------------------
# FLOPs / MFU accounting
# ---------------------------------------------------------------------------

def test_mfu_within_5pct_of_analytic(monkeypatch):
    B, DIN, DH, DOUT = 8, 64, 128, 16
    rng = np.random.default_rng(0)
    W1 = nd.array(rng.standard_normal((DIN, DH)).astype("float32"))
    W2 = nd.array(rng.standard_normal((DH, DOUT)).astype("float32"))

    def mlp(x):
        return nd.dot(nd.relu(nd.dot(x, W1)), W2)

    t = [0.0]
    meter = telemetry.FlopsMeter(window_s=60.0, clock=lambda: t[0])
    monkeypatch.setattr(telemetry, "flops_meter", meter)
    meter.rate()  # prime the window at t=0, zero flops

    op = CachedOp(mlp, name="mlp")
    x = nd.array(rng.standard_normal((B, DIN)).astype("float32"))
    calls = 10
    for _ in range(calls):
        op(x)

    analytic = calls * (2 * B * DIN * DH + 2 * B * DH * DOUT)
    assert meter.total() == pytest.approx(analytic, rel=0.05)
    per_exec = list(op.flops_per_call().values())
    assert len(per_exec) == 1   # one signature, one cached FLOPs count
    assert per_exec[0] * calls == pytest.approx(meter.total())

    # MFU: 1 wall-second at a known peak
    t[0] = 1.0
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "1e9")
    peak = telemetry.peak_flops()
    n_dev = len(telemetry._accel_devices())
    assert peak == pytest.approx(1e9 * n_dev)
    mfu = telemetry.mfu_percent()
    assert mfu == pytest.approx(meter.total() / peak * 100.0, rel=1e-6)
    assert mfu == pytest.approx(analytic / peak * 100.0, rel=0.05)


def test_flops_disabled_by_knob(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_FLOPS", "0")
    meter = telemetry.FlopsMeter(window_s=60.0)
    monkeypatch.setattr(telemetry, "flops_meter", meter)
    op = CachedOp(lambda x: x * 2.0, name="noflops")
    op(nd.array(np.ones((2, 2), "float32")))
    assert meter.total() == 0.0
    assert list(op.flops_per_call().values()) == [0.0]


def test_flops_rate_not_diluted_by_idle_gap():
    """An idle gap longer than the window must not become the rate's
    denominator: scrape, sleep an hour, burst, scrape — the stale
    anchor is discarded (rate re-primes) instead of reporting the
    burst averaged over the whole gap as near-zero MFU."""
    t = [0.0]
    meter = telemetry.FlopsMeter(window_s=60.0, clock=lambda: t[0])
    meter.rate()                       # prime at t=0
    t[0] = 3600.0
    meter.add(1e9)
    assert meter.rate() == 0.0         # gap > window: re-primed, not 1e9/3600
    t[0] = 3610.0
    meter.add(1e9)
    assert meter.rate() == pytest.approx(1e9 / 10.0)
    # another over-window gap with NO adds: the true windowed rate is 0
    # (the 3610 burst is outside the trailing 60s), not burst/gap
    t[0] = 3700.0
    assert meter.rate() == 0.0
    # steady in-window scrapes measure normally again
    t[0] = 3720.0
    meter.add(2e9)
    assert meter.rate() == pytest.approx(2e9 / 20.0)


def test_mfu_unknown_peak_reports_none(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_PEAK_FLOPS", "0")
    # CPU devices have no entry in the peak table
    assert telemetry.peak_flops() is None
    assert telemetry.mfu_percent() is None


# ---------------------------------------------------------------------------
# tail sampling
# ---------------------------------------------------------------------------

def test_tail_sampler_keeps_every_error_trace():
    """Synthetic 5%-error load: every error trace kept, nothing else
    (fraction=0 disables random keeps)."""
    sampler = telemetry.TailSampler(fraction=0.0, budget_per_s=0.0,
                                    slow_ms=0.0)
    tr.set_sampler(sampler)
    tr.enable()
    error_tids = set()
    for i in range(200):
        with tr.span("serving.http", request_id="r%d" % i) as sp:
            with tr.span("serving.engine.execute"):
                pass
            if i % 20 == 0:   # 5% error rate
                sp.set(error=500)
                error_tids.add(sp.ctx.trace_id)
    kept = sampler.kept_trace_ids()
    assert set(kept) == error_tids
    assert all(reason == "error" for reason in kept.values())
    assert sampler.stats()["kept_error"] == len(error_tids) == 10
    # kept_events pulls the whole trace, children included
    events = sampler.kept_events(tr.events())
    assert {ev[8] for ev in events} == error_tids
    assert {ev[1] for ev in events} == {"serving.http",
                                        "serving.engine.execute"}


def test_tail_sampler_random_keeps_respect_budget():
    t = [0.0]
    sampler = telemetry.TailSampler(fraction=1.0, budget_per_s=5.0,
                                    slow_ms=0.0, clock=lambda: t[0])
    tr.set_sampler(sampler)
    tr.enable()
    for i in range(100):
        with tr.span("serving.http", request_id="r%d" % i):
            pass
    st = sampler.stats()
    assert st["kept_random"] == 5          # initial bucket, no refill
    assert st["budget_denied"] == 95
    t[0] = 2.0                              # 2s => 10 tokens, capped at 5
    for i in range(100):
        with tr.span("serving.http", request_id="s%d" % i):
            pass
    assert sampler.stats()["kept_random"] == 10


def test_tail_sampler_slow_spans_kept():
    sampler = telemetry.TailSampler(fraction=0.0, budget_per_s=0.0,
                                    slow_ms=50.0)
    tr.set_sampler(sampler)
    tr.enable()
    base = tr.now()
    tr.complete("serving.http", base, base + 0.2, request_id="slow-1")
    tr.complete("serving.http", base, base + 0.001, request_id="fast-1")
    kept = sampler.kept_trace_ids()
    assert list(kept.values()) == ["slow"]


def test_exemplars_link_kept_traces():
    sampler = telemetry.TailSampler(fraction=0.0, budget_per_s=0.0)
    tr.set_sampler(sampler)
    tr.enable()
    with tr.span("serving.http", request_id="bad", error=503):
        pass
    with tr.span("serving.http", request_id="fine"):
        pass
    kept_hex = {"%x" % tid for tid in sampler.kept_trace_ids()}
    assert len(kept_hex) == 1
    ex = tr.phase_exemplars()["serving.http"]
    kept_ex = [e for e in ex.values() if e["kept"]]
    assert kept_ex and kept_ex[0]["trace_id"] in kept_hex
    # and it survives into the exposition as an exemplar suffix
    parsed = validate_prometheus_text(prom.render_process())
    ex_ids = {exemplar[0]["trace_id"]
              for name, labels, _, exemplar in parsed["samples"]
              if exemplar is not None
              and labels.get("phase") == "serving.http"}
    assert kept_hex & ex_ids


# ---------------------------------------------------------------------------
# ring-drop accounting (satellite)
# ---------------------------------------------------------------------------

def test_ring_drop_counter_and_warn_once():
    tr.tracer.set_capacity(8)
    tr.enable()
    with pytest.warns(RuntimeWarning, match="ring buffer full"):
        for i in range(20):
            with tr.span("spin"):
                pass
    assert tr.dropped_spans() == 12
    assert tr.event_count() == 8
    # counted, surfaced on the gauge AND the profiler row; warns once
    assert tr.summary_gauge()["dropped_spans"] == 12
    assert profiler.get_aggregate_stats()["trace.dropped_spans"][
        "calls"] == 12
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with tr.span("again"):
            pass
    assert tr.dropped_spans() == 13
    # a fresh session restarts accounting
    tr.clear()
    assert tr.dropped_spans() == 0


# ---------------------------------------------------------------------------
# memory probes (satellite)
# ---------------------------------------------------------------------------

class _BrokenDevice:
    platform = "tpu"
    device_kind = "TPU v99"

    def memory_stats(self):
        raise RuntimeError("probe exploded")


def test_memory_probe_errors_counted_and_warned(monkeypatch):
    monkeypatch.setattr(telemetry, "_accel_devices",
                        lambda: [_BrokenDevice()])
    with pytest.warns(RuntimeWarning, match="memory probe failed"):
        mems = telemetry.device_memory()
    assert mems[0]["available"] is False
    assert telemetry.memory_probe_errors() == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second failure must NOT warn
        telemetry.device_memory()
    assert telemetry.memory_probe_errors() == 2
    rows = profiler.get_aggregate_stats()
    assert rows["telemetry.memory_probe_errors"]["calls"] == 2


def test_gpu_memory_info_counts_probe_errors(monkeypatch):
    monkeypatch.setattr(mx.context.Context, "jax_device",
                        property(lambda self: _BrokenDevice()))
    with pytest.warns(RuntimeWarning, match="gpu_memory_info"):
        free, total = mx.context.gpu_memory_info(0)
    assert (free, total) == (0, 0)
    assert telemetry.memory_probe_errors() == 1


def test_memory_health_degrades_before_oom(monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_HEADROOM_MIN", "0.05")
    low = [{"device": 0, "platform": "tpu", "kind": "TPU v4",
            "available": True, "bytes_in_use": 97, "bytes_limit": 100,
            "peak_bytes_in_use": 97}]
    monkeypatch.setattr(telemetry, "device_memory", lambda: low)
    h = telemetry.memory_health()
    assert h["status"] == "degraded" and h["reason"] == "memory_headroom"
    assert h["headroom"] == pytest.approx(0.03)
    ok = [dict(low[0], bytes_in_use=50)]
    monkeypatch.setattr(telemetry, "device_memory", lambda: ok)
    assert telemetry.memory_health()["status"] == "ok"


class _FullChip:
    """A v5e chip as PR 24 read it while BERT-base b96 trained: 2.06 GB of
    live arrays, 13.57 GB reserved for the step's temporaries."""
    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"bytes_in_use": 2059794432, "peak_bytes_in_use": 2326938624,
                "bytes_reserved": 13573783552,
                "peak_bytes_reserved": 13573783552,
                "bytes_limit": 16900000000}


def test_headroom_counts_memory_reserved_for_programs(monkeypatch):
    monkeypatch.setattr(telemetry, "_accel_devices", lambda: [_FullChip()])
    mem, = telemetry.device_memory()
    assert mem["bytes_reserved"] == mem["peak_bytes_reserved"] == 13573783552
    in_use_only = (mem["bytes_limit"] - mem["bytes_in_use"]) \
        / mem["bytes_limit"]
    assert in_use_only == pytest.approx(0.878, abs=1e-3)   # what it read
    assert telemetry.memory_headroom() < 0.10
    assert telemetry.memory_headroom() == pytest.approx(0.0749, abs=1e-3)
    monkeypatch.setenv("MXNET_TELEMETRY_HEADROOM_MIN", "0.10")
    assert telemetry.memory_health()["reason"] == "memory_headroom"
    text = prom.render_process()
    assert "mxtpu_device_hbm_bytes_reserved{" in text


def test_device_memory_without_reserved_stats_reads_zero(monkeypatch):
    class _Plain(_FullChip):
        def memory_stats(self):
            return {"bytes_in_use": 50, "bytes_limit": 100}
    monkeypatch.setattr(telemetry, "_accel_devices", lambda: [_Plain()])
    mem, = telemetry.device_memory()
    assert mem["bytes_reserved"] == mem["peak_bytes_reserved"] == 0
    assert telemetry.memory_headroom() == pytest.approx(0.5)


def test_server_healthz_degrades_on_low_headroom(monkeypatch):
    with ModelServer(_times(1), port=0, buckets=(1,), jit=False) as srv:
        assert srv.health()["status"] == "ok"
        monkeypatch.setattr(
            telemetry, "memory_health",
            lambda: {"status": "degraded", "reason": "memory_headroom"})
        h = srv.health()
        assert h["status"] == "degraded"
        assert h["memory"]["reason"] == "memory_headroom"


# ---------------------------------------------------------------------------
# fleet-wide scrape aggregation
# ---------------------------------------------------------------------------

def test_merged_multiworker_scrape_with_rank_labels():
    agg_mod = _tool("telemetry_agg")
    s0 = telemetry.serve_metrics(port=0)
    s1 = telemetry.serve_metrics(port=0)
    try:
        agg = agg_mod.Aggregator({0: s0.url, 1: s1.url})
        text = agg.scrape()
        parsed = validate_prometheus_text(text)
        values = _sample_map(parsed)
        # every worker sample is rank-labelled; both ranks present
        ranks = {labels.get("rank")
                 for name, labels, _, _ in parsed["samples"]
                 if name != "mxtpu_scrape_duration_seconds"}
        assert {"0", "1"} <= ranks
        assert values[("mxtpu_scrape_up", (("rank", "0"),))] == 1.0
        assert values[("mxtpu_scrape_up", (("rank", "1"),))] == 1.0
        # one merged family block per family (validator enforced
        # contiguity); a dead worker is a visible 0
        s1.close()
        s1 = None
        text = agg.scrape()
        parsed = validate_prometheus_text(text)
        values = _sample_map(parsed)
        assert values[("mxtpu_scrape_up", (("rank", "1"),))] == 0.0
        # the merged endpoint serves it over HTTP too
        server = agg_mod.AggServer(agg, port=0)
        try:
            with urllib.request.urlopen(
                    server.url + "/metrics.prom") as r:
                validate_prometheus_text(r.read().decode())
            with urllib.request.urlopen(server.url + "/targets") as r:
                assert set(json.loads(r.read())) == {"0", "1"}
        finally:
            server.close()
    finally:
        s0.close()
        if s1 is not None:
            s1.close()


def test_aggregator_respects_worker_self_rank():
    agg_mod = _tool("telemetry_agg")
    text = ("# HELP mxtpu_x c\n# TYPE mxtpu_x counter\n"
            'mxtpu_x_total{rank="7"} 3\n# EOF\n')
    # merge_expositions is the building block — scrape() appends the
    # scrape-health families and the # EOF terminator
    merged = agg_mod.merge_expositions({0: text})
    parsed = validate_prometheus_text(merged + "# EOF\n")
    (name, labels, value, _), = parsed["samples"]
    assert name == "mxtpu_x_total"
    assert labels == {"rank": "7"} and value == 3.0


def test_serve_metrics_env_opt_in(monkeypatch):
    monkeypatch.delenv("MXTPU_METRICS_PORT", raising=False)
    assert telemetry.serve_metrics() is None
    srv = telemetry.serve_metrics(port=0)
    try:
        with urllib.request.urlopen(srv.url + "/metrics.prom") as r:
            validate_prometheus_text(r.read().decode())
        with urllib.request.urlopen(srv.url + "/healthz") as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        srv.close()


def test_worker_healthz_reflects_elastic_and_guardrails(monkeypatch):
    """The standalone worker endpoint must expose the same degradation
    sources a ModelServer does (minus the breaker): a training worker
    with a pending eviction can't report ok on its own /healthz."""
    from mxnet_tpu.resilience import elastic as elastic_mod
    assert telemetry.worker_health()["status"] == "ok"
    monkeypatch.setattr(
        elastic_mod, "health",
        lambda: {"status": "degraded", "reason": "preemption_pending"})
    h = telemetry.worker_health()
    assert h["status"] == "degraded"
    assert h["elastic"]["reason"] == "preemption_pending"
    srv = telemetry.serve_metrics(port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["status"] == "degraded"
    finally:
        srv.close()
    monkeypatch.setattr(
        telemetry, "memory_health",
        lambda: {"status": "degraded", "reason": "memory_headroom"})
    assert telemetry.worker_health()["memory"]["reason"] == \
        "memory_headroom"


# ---------------------------------------------------------------------------
# knob audit (satellite): every MXNET_* read anywhere is registered
# ---------------------------------------------------------------------------

def test_every_mxnet_env_var_is_registered():
    """Grep-driven: any ``MXNET_*`` token in mxnet_tpu/ source must be a
    registered knob in config.KNOBS (or a prefix of one — docstrings
    name families like ``MXNET_RETRY_``). Catches the PR 7
    ``MXNET_GEN_QUEUE_SIZE`` documented-but-unread class of bug
    permanently, from the read side."""
    from mxnet_tpu import config
    root = os.path.dirname(os.path.abspath(config.__file__))
    pattern = re.compile(r"MXNET_[A-Z0-9_]+")
    offenders = {}
    for dirpath, dirnames, files in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root)
            if rel == "config.py":
                continue
            with open(path) as f:
                text = f.read()
            for name in set(pattern.findall(text)):
                if name in config.KNOBS:
                    continue
                if any(k.startswith(name) for k in config.KNOBS):
                    continue   # family prefix (docs/spec grammar)
                offenders.setdefault(name, []).append(rel)
    assert not offenders, \
        "unregistered MXNET_* env vars (add them to config.KNOBS): %r" \
        % offenders


# ---------------------------------------------------------------------------
# trace_summary satellite
# ---------------------------------------------------------------------------

def test_trace_summary_missing_empty_corrupt(tmp_path, capsys):
    ts = _tool("trace_summary")
    assert ts.main([str(tmp_path / "nope.json")]) == 2
    assert "cannot read" in capsys.readouterr().err
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert ts.main([str(empty)]) == 2
    assert "empty" in capsys.readouterr().err
    corrupt = tmp_path / "bad.json"
    corrupt.write_text('{"traceEvents": [truncated')
    assert ts.main([str(corrupt)]) == 2
    assert "not valid JSON" in capsys.readouterr().err
    notrace = tmp_path / "other.json"
    notrace.write_text('{"foo": 1}')
    assert ts.main([str(notrace)]) == 2
    assert "traceEvents" in capsys.readouterr().err


def test_trace_summary_prints_kept_exemplar_request_ids(tmp_path,
                                                        capsys):
    from mxnet_tpu.observability import export as obs_export
    ts = _tool("trace_summary")
    sampler = telemetry.TailSampler(fraction=0.0, budget_per_s=0.0)
    tr.set_sampler(sampler)
    tr.enable()
    with tr.span("serving.http", request_id="rid-err", error=500):
        pass
    with tr.span("serving.http", request_id="rid-ok"):
        pass
    path = str(tmp_path / "trace.json")
    obs_export.dump_chrome_trace(path)   # embeds the sampler's kept set
    assert ts.main([path]) == 0
    out = capsys.readouterr().out
    assert "rid-err" in out and "[kept:error]" in out
    assert "Kept-exemplar request ids" in out
    # json mode carries the same fields
    assert ts.main([path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["kept_request_ids"] == ["rid-err"]
    assert doc["kept_traces"] == 1
