"""Telemetry-plane benchmark: scrape latency, serving overhead with the
full telemetry plane enabled, and tail-sampler keep rates — written to
``benchmark/TELEMETRY.json``.

Three numbers back the ISSUE 9 acceptance criteria:

- **scrape latency** — wall time of one ``/metrics.prom`` render
  (every stats source walked + exposition formatting), direct and over
  HTTP. This is the cost a Prometheus server imposes per scrape
  interval, NOT per request.
- **serving overhead** — what the telemetry plane ADDS to ``/predict``:
  the marginal per-span cost of the tail sampler + exemplar
  bookkeeping + ring-drop accounting (enabled-span cost with the
  sampler attached minus without — plain enabled tracing is PR 5's
  cost, recorded in OBSERVABILITY.json) plus the per-dispatch FLOPs
  add, × spans per request, as a fraction of the measured p50. That
  **modeled** number is **asserted < 1%** (same methodology as
  OBSERVABILITY.json, robust to HTTP jitter); the raw measured
  enabled-vs-disabled p50 delta is recorded alongside (on a CPU host
  run-to-run HTTP noise exceeds the signal).
- **sampler keep rates** — under a synthetic 5%-error load: errors kept
  must be 100% (asserted); random keeps ≈ the configured fraction,
  bounded by the budget.

Usage::

    python benchmark/telemetry_bench.py           # write the artifact
    python benchmark/telemetry_bench.py --quick   # fewer reps (smoke)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import jax  # noqa: E402

from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.observability import telemetry  # noqa: E402
from mxnet_tpu.observability import export_prom  # noqa: E402
from mxnet_tpu.observability import tracer as tr  # noqa: E402
from mxnet_tpu.serving import ModelServer  # noqa: E402

D_IN, D_HID, D_OUT = 64, 128, 16


def _percentile(vals, q):
    vals = sorted(vals)
    if not vals:
        return 0.0
    idx = min(len(vals) - 1, max(0, int(round(q / 100.0 * len(vals))) - 1))
    return vals[idx]


def _mk_server():
    rng = np.random.default_rng(0)
    W1 = nd.array(rng.standard_normal((D_IN, D_HID)).astype("float32"))
    W2 = nd.array(rng.standard_normal((D_HID, D_OUT)).astype("float32"))

    def fn(x):
        return nd.dot(nd.relu(nd.dot(x, W1)), W2)

    srv = ModelServer(fn, port=0, buckets=(1, 2, 4), max_latency_ms=0.5,
                      retry_policy=False)
    srv.engine.warmup(np.zeros((1, D_IN), "float32"))
    return srv


def _predict_p50(url, n, payload):
    import urllib.request
    lats = []
    for _ in range(n):
        t0 = time.perf_counter()
        req = urllib.request.Request(
            url + "/predict", data=payload,
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req).read()
        lats.append(time.perf_counter() - t0)
    return _percentile(lats, 50) * 1e3, lats


def _measure_span_cost_ns(iters=50000):
    """Per-span cost of the enabled record path as currently configured
    (sampler attached or not) — best of 3 passes to shed scheduler
    noise."""
    assert tr.enabled()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(iters):
            with tr.span("bench.cost", i=i):
                pass
        best = min(best, (time.perf_counter() - t0) / iters * 1e9)
    return best


def _measure_flops_add_ns(iters=200000):
    t0 = time.perf_counter()
    for _ in range(iters):
        telemetry.add_flops(8192.0)
    return (time.perf_counter() - t0) / iters * 1e9


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    reps = 60 if args.quick else 400
    scrapes = 10 if args.quick else 50

    payload = json.dumps({"data": [0.5] * D_IN}).encode()
    out = {"platform": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind}

    # ---- disabled baseline -------------------------------------------------
    tr.disable()
    tr.tracer.set_sampler(None)
    telemetry.flops_meter.reset()
    srv = _mk_server()
    srv.start()
    try:
        _predict_p50(srv.url, 20, payload)  # warm the HTTP path
        p50_off, _ = _predict_p50(srv.url, reps, payload)
    finally:
        srv.stop()

    # ---- enabled: tracing + tail sampler + FLOPs accounting ---------------
    sampler = telemetry.install_tail_sampler(fraction=0.01,
                                             budget_per_s=100.0)
    tr.enable()
    srv = _mk_server()
    srv.start()
    try:
        _predict_p50(srv.url, 20, payload)
        p50_on, _ = _predict_p50(srv.url, reps, payload)

        # scrape latency on a warm, populated surface
        t_direct = []
        for _ in range(scrapes):
            t0 = time.perf_counter()
            text = srv.prometheus_text()
            t_direct.append(time.perf_counter() - t0)
        import urllib.request
        t_http = []
        for _ in range(scrapes):
            t0 = time.perf_counter()
            urllib.request.urlopen(srv.url + "/metrics.prom").read()
            t_http.append(time.perf_counter() - t0)
        exposition_bytes = len(text.encode())
        span_iters = 5000 if args.quick else 50000
        span_cost_with_sampler_ns = _measure_span_cost_ns(span_iters)
        tr.tracer.set_sampler(None)
        span_cost_plain_ns = _measure_span_cost_ns(span_iters)
        tr.tracer.set_sampler(sampler)
        flops_add_ns = _measure_flops_add_ns(
            20000 if args.quick else 200000)
    finally:
        srv.stop()

    # spans per /predict request: http + queue_wait + batch_assemble +
    # batch_execute + engine.execute (counted from the phase stats)
    phases = tr.phase_stats()
    serving_spans = sum(1 for name in phases if name.startswith("serving."))
    # the telemetry plane's MARGINAL per-request cost: sampler/exemplar
    # bookkeeping per span (plain enabled tracing is PR 5's recorded
    # cost) + one FLOPs add per engine dispatch
    marginal_ns = (max(0.0, span_cost_with_sampler_ns
                       - span_cost_plain_ns) * serving_spans
                   + flops_add_ns)
    modeled_pct = marginal_ns / (p50_off * 1e6) * 100.0

    # ---- sampler keep rates under synthetic 5%-error load -----------------
    tr.tracer.clear()
    tr.tracer.reset_phase_stats()
    sampler.reset()
    sampler.fraction = 0.01
    n_load = 2000 if args.quick else 20000
    n_err = 0
    for i in range(n_load):
        with tr.span("serving.http", request_id="r%d" % i) as sp:
            if i % 20 == 0:
                sp.set(error=500)
                n_err += 1
    st = sampler.stats()
    err_keep_rate = st["kept_error"] / n_err
    random_keep_rate = st["kept_random"] / (n_load - n_err)

    out.update({
        "scrape_ms_direct_p50": _percentile(t_direct, 50) * 1e3,
        "scrape_ms_http_p50": _percentile(t_http, 50) * 1e3,
        "exposition_bytes": exposition_bytes,
        "predict_p50_ms_disabled": p50_off,
        "predict_p50_ms_enabled": p50_on,
        "predict_p50_overhead_pct_measured":
            (p50_on - p50_off) / p50_off * 100.0,
        "span_cost_ns_plain_tracing": span_cost_plain_ns,
        "span_cost_ns_with_sampler": span_cost_with_sampler_ns,
        "flops_add_ns": flops_add_ns,
        "serving_spans_per_request": serving_spans,
        "predict_p50_overhead_pct_modeled": modeled_pct,
        "sampler_load": {"requests": n_load, "error_rate": n_err / n_load,
                         "error_keep_rate": err_keep_rate,
                         "random_fraction_configured": 0.01,
                         "random_keep_rate": random_keep_rate,
                         "budget_denied": st["budget_denied"]},
        "note": "overhead_pct_modeled = the telemetry plane's marginal "
                "cost (sampler/exemplar per-span delta x serving "
                "spans/request + one FLOPs add) over the disabled p50; "
                "plain enabled-tracing cost is PR 5's, recorded in "
                "OBSERVABILITY.json. HTTP jitter on a CPU host exceeds "
                "the raw measured delta. Asserted: modeled < 1%, "
                "error_keep_rate == 1.0.",
    })

    assert err_keep_rate == 1.0, \
        "tail sampler must keep 100%% of error traces (got %.3f)" \
        % err_keep_rate
    assert modeled_pct < 1.0, \
        "telemetry per-request overhead %.3f%% >= 1%%" % modeled_pct

    from benchmark._artifact import stamp
    out = stamp(out, platform=out.get("platform"))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "TELEMETRY.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print("wrote", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
