"""Prometheus text-format exposition of every stats source.

One exposition, every counter the stack already keeps: ServingMetrics
and GenerationMetrics snapshots, fleet per-model×version lanes, the
profiler aggregate table (which carries every resilience Registry row —
guardrails, elastic, datafeed, breaker, retry — plus ``trace.*``),
CachedOp compile/hit/evict counters, the tracer's per-phase latency
histograms (with trace-id **exemplars** pointing at tail-sampled kept
traces), and the telemetry plane's device-memory / FLOPs / MFU gauges.

Naming scheme (stable, documented in ``docs/observability.md``)::

    mxtpu_<subsystem>_<name>[_total]{model=,version=,quantile=,le=,...}

- counters end in ``_total``; gauges don't.
- per-model×version fleet lanes carry ``model=``/``version=`` labels on
  the same families single-model servers emit unlabelled — one Grafana
  dashboard serves both.
- the profiler aggregate table is exposed generically as
  ``mxtpu_aggregate_calls_total{row="..."}`` /
  ``mxtpu_aggregate_seconds_total{row="..."}`` so every present AND
  future registry row is scrapeable without an exposition change.
- histograms follow the Prometheus contract: cumulative ``_bucket``
  series with ``le`` labels ending at ``+Inf``, plus ``_sum``/``_count``;
  buckets carry OpenMetrics-style exemplars
  (``# {trace_id="..."} value``) linking to kept traces.

Label values are escaped per the exposition-format spec (backslash,
double-quote, newline); HELP text escapes backslash and newline. The
strict validator in ``tests/test_telemetry.py`` enforces all of it.
"""
from __future__ import annotations

import re

from . import attribution as _attribution
from . import telemetry as _telemetry
from . import tracer as _tracer
from .tracer import _BOUNDS_MS, _BUCKET_LABELS

__all__ = ["PromWriter", "CONTENT_TYPE", "render_process", "render_server",
           "render_serving_section", "render_generation_section",
           "render_gateway_section", "render_gateway"]

# Exemplars are only legal in the OpenMetrics exposition (the classic
# 0.0.4 text parser reads anything after the value as a timestamp and
# rejects the WHOLE scrape), so that is the one format we speak:
# Prometheus picks its parser off the response Content-Type, and every
# modern scraper understands OpenMetrics 1.0. The contract that differs
# from classic text: counter families are DECLARED without the
# ``_total`` suffix their samples carry, and the body ends in ``# EOF``.
CONTENT_TYPE = "application/openmetrics-text; version=1.0.0; charset=utf-8"

_NAME_OK = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SANITIZE = re.compile(r"[^a-zA-Z0-9_]")


def _sanitize_name(name):
    out = _SANITIZE.sub("_", str(name))
    if not out or not re.match(r"[a-zA-Z_]", out[0]):
        out = "_" + out
    return out


def _escape_label(value):
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _escape_help(text):
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _fmt(value):
    if isinstance(value, bool):
        return "1" if value else "0"
    if isinstance(value, int):
        return str(value)
    value = float(value)
    if value == int(value) and abs(value) < 1e15:
        return str(int(value))
    return repr(value)


class PromWriter:
    """Buffered exposition writer: families declared once with their
    ``# HELP``/``# TYPE``, samples grouped under their family regardless
    of emission order (the format requires contiguous families).
    ``const_labels`` (e.g. ``rank=``) ride on every sample."""

    def __init__(self, const_labels=None):
        self._families = {}   # name -> [mtype, help, [sample lines]]
        self._order = []
        self._const = dict(const_labels or {})

    def family(self, name, mtype, help_text):
        assert _NAME_OK.match(name), name
        # OpenMetrics: a counter's samples are ``<family>_total`` and the
        # family is declared WITHOUT the suffix — enforce the naming here
        # so a new counter can't silently produce an invalid exposition
        assert mtype != "counter" or name.endswith("_total"), name
        fam = self._families.get(name)
        if fam is None:
            self._families[name] = [mtype, help_text, []]
            self._order.append(name)
        return name

    def sample(self, family, value, labels=None, suffix="", exemplar=None):
        """One sample line. ``suffix`` appends to the family name
        (histogram ``_bucket``/``_sum``/``_count`` children);
        ``exemplar`` is ``(labels_dict, value)``."""
        if value is None:
            return
        fam = self._families[family]
        merged = dict(self._const)
        if labels:
            merged.update(labels)
        if merged:
            body = ",".join('%s="%s"' % (_sanitize_name(k),
                                         _escape_label(v))
                            for k, v in merged.items())
            line = "%s%s{%s} %s" % (family, suffix, body, _fmt(value))
        else:
            line = "%s%s %s" % (family, suffix, _fmt(value))
        if exemplar is not None:
            ex_labels, ex_value = exemplar
            ex_body = ",".join('%s="%s"' % (_sanitize_name(k),
                                            _escape_label(v))
                               for k, v in ex_labels.items())
            line += " # {%s} %s" % (ex_body, _fmt(ex_value))
        fam[2].append(line)

    def counter(self, name, help_text, value, labels=None):
        self.family(name, "counter", help_text)
        self.sample(name, value, labels=labels)

    def gauge(self, name, help_text, value, labels=None):
        self.family(name, "gauge", help_text)
        self.sample(name, value, labels=labels)

    def text(self):
        lines = []
        for name in self._order:
            mtype, help_text, samples = self._families[name]
            if not samples:
                continue
            decl = name[:-len("_total")] if mtype == "counter" else name
            lines.append("# HELP %s %s" % (decl, _escape_help(help_text)))
            lines.append("# TYPE %s %s" % (decl, mtype))
            lines.extend(samples)
        lines.append("# EOF")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# section renderers
# ---------------------------------------------------------------------------

def _quantile_family(w, name, help_text, quantile_dict, labels=None):
    """Percentile dict (``{"p50": v, ...}``) as one gauge family with a
    ``quantile`` label — sliding-window percentiles are point-in-time
    observations, not Prometheus-native summaries."""
    w.family(name, "gauge", help_text)
    for q, v in (quantile_dict or {}).items():
        ql = dict(labels or {})
        ql["quantile"] = q
        w.sample(name, v, labels=ql)


def render_serving_section(w, snap, labels=None):
    """A ``ServingMetrics.snapshot()`` dict (single-model server or one
    fleet lane, distinguished by ``labels``)."""
    from ..serving.metrics import (SERVING_PROM_COUNTERS,
                                  SERVING_PROM_GAUGES)
    for key, help_text in SERVING_PROM_COUNTERS:
        if key in snap:
            w.counter("mxtpu_serving_%s_total" % key, help_text,
                      snap[key], labels=labels)
    for key, help_text in SERVING_PROM_GAUGES:
        if snap.get(key) is not None:
            w.gauge("mxtpu_serving_%s" % key, help_text, snap[key],
                    labels=labels)
    _quantile_family(w, "mxtpu_serving_latency_ms",
                     "request latency percentiles over the sliding window",
                     snap.get("latency_ms"), labels=labels)
    cache = snap.get("executor_cache") or {}
    for key in ("hits", "misses", "evictions"):
        if key in cache:
            w.counter("mxtpu_serving_cache_%s_total" % key,
                      "engine executor-cache %s (misses == XLA compiles)"
                      % key, cache[key], labels=labels)
    for key, help_text in (("size", "compiled executables resident"),
                           ("capacity", "executor-cache LRU bound")):
        if key in cache:
            w.gauge("mxtpu_serving_cache_%s" % key, help_text,
                    cache[key], labels=labels)


def render_generation_section(w, snap, labels=None):
    """A ``GenerationMetrics.snapshot()`` dict."""
    from ..serving.metrics import (GENERATION_PROM_COUNTERS,
                                   GENERATION_PROM_GAUGES)
    for key, help_text in GENERATION_PROM_COUNTERS:
        if key in snap:
            w.counter("mxtpu_generation_%s_total" % key, help_text,
                      snap[key], labels=labels)
    for key, help_text in GENERATION_PROM_GAUGES:
        if snap.get(key) is not None:
            w.gauge("mxtpu_generation_%s" % key, help_text, snap[key],
                    labels=labels)
    _quantile_family(w, "mxtpu_generation_ttft_ms",
                     "time-to-first-token percentiles (queue + prefill)",
                     snap.get("ttft_ms"), labels=labels)
    _quantile_family(w, "mxtpu_generation_tokens_s_per_slot",
                     "per-sequence decode-rate percentiles",
                     snap.get("tokens_s_per_slot"), labels=labels)
    kv = snap.get("kvcache") or {}
    for key, val in kv.items():
        if isinstance(val, (int, float)) and not isinstance(val, bool):
            w.gauge("mxtpu_generation_kvcache_%s" % _sanitize_name(key),
                    "SlotKVCache arena gauge: %s" % key, val,
                    labels=labels)


def _render_aggregate_rows(w):
    from .. import profiler as _profiler
    rows = _profiler.get_aggregate_stats()
    w.family("mxtpu_aggregate_calls_total", "counter",
             "profiler aggregate-table row call counts (every registered "
             "stats provider: resilience, datafeed, trace phases, ...)")
    w.family("mxtpu_aggregate_seconds_total", "counter",
             "profiler aggregate-table row total time")
    for row in sorted(rows):
        st = rows[row]
        w.sample("mxtpu_aggregate_calls_total", st["calls"],
                 labels={"row": row})
        w.sample("mxtpu_aggregate_seconds_total", st["total_ms"] / 1e3,
                 labels={"row": row})


def _render_cachedop(w):
    from .. import cached_op as _cached_op
    stats = _cached_op.cache_stats()
    for key in ("hits", "misses", "evictions"):
        w.counter("mxtpu_cachedop_%s_total" % key,
                  "process-wide CachedOp executor-cache %s (misses == XLA "
                  "compiles)" % key, stats.get(key, 0))


def _render_pcache(w):
    from .. import pcache as _pcache
    st = _pcache.stats()
    w.gauge("mxtpu_pcache_enabled",
            "1 while the persistent XLA compile cache is wired to a "
            "directory (JAX_COMPILATION_CACHE_DIR, else "
            "<checkout>/.jax_cache)", st["enabled"])
    for key, help_text in (
            ("disk_hits", "compiles served from the persistent cache "
                          "(disk read instead of an XLA run)"),
            ("disk_misses", "persistent-cache lookups that fell through "
                            "to a real XLA compile"),
            ("requests", "compile requests that consulted the "
                         "persistent cache"),
            ("ttl_evictions", "persistent-cache entries aged out by the "
                              "TTL sweep at init")):
        w.counter("mxtpu_pcache_%s_total" % key, help_text, st[key])
    w.counter("mxtpu_aot_loads_total",
              "executables installed from serialized AOT artifacts "
              "(zero XLA compiles each)", st["aot_loads"])
    w.counter("mxtpu_aot_fallbacks_total",
              "AOT artifact loads refused (fingerprint mismatch, ladder "
              "drift, corrupt blob) that fell back to normal compiles",
              st["aot_fallbacks"])


def _render_trace(w):
    tr = _tracer.tracer
    w.counter("mxtpu_trace_dropped_spans_total",
              "spans evicted from the full trace ring buffer",
              tr.dropped_spans())
    w.gauge("mxtpu_trace_buffered_events",
            "events currently in the trace ring", tr.event_count())
    w.gauge("mxtpu_trace_enabled", "1 while span recording is on",
            tr.enabled())
    sampler = tr.get_sampler()
    if sampler is not None:
        st = sampler.stats()
        w.family("mxtpu_trace_sampler_kept_total", "counter",
                 "traces kept by the tail sampler, by keep reason")
        for reason in ("error", "slow", "random"):
            w.sample("mxtpu_trace_sampler_kept_total",
                     st.get("kept_" + reason, 0),
                     labels={"reason": reason})
        w.counter("mxtpu_trace_sampler_spans_total",
                  "spans observed by the tail sampler", st.get("spans", 0))
        w.counter("mxtpu_trace_sampler_budget_denied_total",
                  "random keeps denied by the token-bucket budget",
                  st.get("budget_denied", 0))
        w.gauge("mxtpu_trace_sampler_kept_resident",
                "kept traces resident in the sampler's LRU",
                st.get("kept", 0))
    phases = tr.phase_stats()
    if not phases:
        return
    exemplars = tr.phase_exemplars()
    bounds = [str(b) for b in _BOUNDS_MS] + ["+Inf"]
    w.family("mxtpu_trace_phase_duration_ms", "histogram",
             "trace-derived per-phase span latency (same data as the "
             "/metrics trace gauge), with kept-trace exemplars")
    for phase in sorted(phases):
        st = phases[phase]
        per_bucket = [st["buckets_ms"].get(lbl, 0)
                      for lbl in _BUCKET_LABELS]
        phase_ex = exemplars.get(phase, {})
        cum = 0
        for idx, le in enumerate(bounds):
            cum += per_bucket[idx]
            ex = phase_ex.get(_BUCKET_LABELS[idx])
            exemplar = None
            if ex is not None:
                exemplar = ({"trace_id": ex["trace_id"]}, ex["value_ms"])
            w.sample("mxtpu_trace_phase_duration_ms", cum,
                     labels={"phase": phase, "le": le}, suffix="_bucket",
                     exemplar=exemplar)
        w.sample("mxtpu_trace_phase_duration_ms", st["total_ms"],
                 labels={"phase": phase}, suffix="_sum")
        w.sample("mxtpu_trace_phase_duration_ms", st["count"],
                 labels={"phase": phase}, suffix="_count")


def _render_telemetry(w):
    mems = _telemetry.device_memory()
    gauges = (
        ("bytes_in_use", "bytes_in_use", "device allocator bytes in use"),
        ("bytes_limit", "bytes_limit",
         "device allocator capacity (0 = unknown)"),
        ("peak_bytes", "peak_bytes_in_use",
         "peak bytes in use observed by this process"),
        ("bytes_reserved", "bytes_reserved",
         "bytes the runtime holds for compiled programs' temporaries"))
    for fam, _key, text in gauges:
        w.family("mxtpu_device_hbm_" + fam, "gauge", text)
    for m in mems:
        if not m["available"]:
            continue
        labels = {"device": m["device"], "platform": m["platform"],
                  "kind": m["kind"]}
        for fam, key, _text in gauges:
            w.sample("mxtpu_device_hbm_" + fam, m[key], labels=labels)
    headroom = _telemetry.memory_headroom(mems)
    if headroom is not None:
        w.gauge("mxtpu_device_memory_headroom_ratio",
                "worst-case free-HBM fraction across devices (the "
                "/healthz pre-OOM drain signal)", headroom)
    w.counter("mxtpu_memory_probe_errors_total",
              "failed device-memory probes (gauges unavailable, NOT zero)",
              _telemetry.memory_probe_errors())
    w.counter("mxtpu_flops_total",
              "analytic FLOPs executed through CachedOp (XLA cost model, "
              "cached per executable)", _telemetry.flops_total())
    w.gauge("mxtpu_flops_rate",
            "FLOP/s over the trailing MXNET_TELEMETRY_WINDOW_S window",
            _telemetry.flops_rate())
    peak = _telemetry.peak_flops()
    if peak:
        w.gauge("mxtpu_peak_flops",
                "aggregate device peak FLOP/s (table or "
                "MXNET_TELEMETRY_PEAK_FLOPS)", peak)
        w.gauge("mxtpu_mfu_percent",
                "model FLOPs utilization: windowed analytic FLOP/s / peak",
                _telemetry.mfu_percent())


def _render_roofline(w):
    """Per-executable roofline attribution, aggregated per (op, bucket)
    — the bounded-cardinality scrape view (per-signature detail lives
    on ``tools/roofline_report.py`` / the ``/metrics`` JSON gauge).
    ``mxtpu_roofline_bound`` is a one-hot state gauge with a ``bound=``
    label, the fleet-wide "which programs are HBM-bound" query."""
    rows = _attribution.roofline.by_op_bucket()
    if rows:
        w.family("mxtpu_roofline_dispatch_total", "counter",
                 "executable dispatches attributed per (op, bucket)")
        w.family("mxtpu_roofline_seconds_total", "counter",
                 "measured dispatch wall time per (op, bucket) — "
                 "execution time on sync backends; can understate "
                 "execution under async dispatch")
        w.family("mxtpu_roofline_flops_per_call", "gauge",
                 "analytic FLOPs per execution (XLA cost model, "
                 "call-weighted over signatures)")
        w.family("mxtpu_roofline_bytes_per_call", "gauge",
                 "analytic bytes accessed per execution (XLA cost "
                 "model, call-weighted over signatures)")
        w.family("mxtpu_roofline_arithmetic_intensity", "gauge",
                 "FLOPs per byte accessed — position on the roofline's "
                 "x axis")
        w.family("mxtpu_roofline_achieved_flops", "gauge",
                 "analytic FLOPs / measured wall per call (can "
                 "overstate under async dispatch — see "
                 "docs/observability.md)")
        w.family("mxtpu_roofline_ceiling_flops", "gauge",
                 "roofline ceiling min(peak, AI x HBM bandwidth); "
                 "absent when device peak/bandwidth are unknown")
        w.family("mxtpu_roofline_bound", "gauge",
                 "1 for the executable's roofline classification "
                 "(bound= label: compute_bound | hbm_bound | "
                 "overhead_bound | unknown)")
        for (op, bucket) in sorted(rows, key=lambda k: (str(k[0]),
                                                        str(k[1]))):
            ent = rows[(op, bucket)]
            labels = {"op": op, "bucket": bucket}
            w.sample("mxtpu_roofline_dispatch_total", ent["calls"],
                     labels=labels)
            w.sample("mxtpu_roofline_seconds_total", ent["total_s"],
                     labels=labels)
            w.sample("mxtpu_roofline_flops_per_call",
                     ent["flops_per_call"], labels=labels)
            w.sample("mxtpu_roofline_bytes_per_call",
                     ent["bytes_per_call"], labels=labels)
            w.sample("mxtpu_roofline_arithmetic_intensity", ent["ai"],
                     labels=labels)
            w.sample("mxtpu_roofline_achieved_flops",
                     ent["achieved_flops_s"], labels=labels)
            w.sample("mxtpu_roofline_ceiling_flops",
                     ent["ceiling_flops_s"], labels=labels)
            w.sample("mxtpu_roofline_bound", 1,
                     labels={**labels, "bound": ent["bound"]})
    ridge = _attribution.ridge_point()
    w.gauge("mxtpu_roofline_ridge_flop_per_byte",
            "arithmetic-intensity ridge the bound classification used "
            "(peak/bandwidth, MXNET_PROF_RIDGE, or the built-in "
            "default)", ridge)
    bw = _attribution.peak_bytes_per_s()
    if bw:
        w.gauge("mxtpu_peak_hbm_bytes_per_second",
                "aggregate device peak HBM bytes/s (table or "
                "MXNET_PROF_HBM_GBPS)", bw)
    st = _attribution.flight.stats()
    w.gauge("mxtpu_flight_records",
            "flight-recorder ring occupancy (last-K timing records)",
            st["records"])
    w.counter("mxtpu_flight_recorded_total",
              "timing records the flight recorder has observed",
              st["total_recorded"])
    w.counter("mxtpu_flight_dumps_total",
              "flight-recorder JSON dumps written (SIGUSR2, faults, "
              "watchdog stalls, profile captures)", st["dumps"])


def _render_elastic(w):
    from ..resilience import elastic as _elastic
    gauge = _elastic.membership_gauge()
    w.gauge("mxtpu_elastic_preemption_pending",
            "1 while this process holds an unserved eviction notice",
            gauge.get("preemption_pending", False))
    membership = gauge.get("membership")
    if membership:
        w.gauge("mxtpu_elastic_members_expected",
                "world size the coordinator was formed at",
                membership.get("expected"))
        w.gauge("mxtpu_elastic_members_alive",
                "members with a live heartbeat", membership.get("alive"))
        w.gauge("mxtpu_elastic_members_lost",
                "members marked up whose beat passed the deadline",
                len(membership.get("dead") or ()))
    member = gauge.get("member")
    if member:
        w.gauge("mxtpu_elastic_member_step",
                "this member's last published step", member.get("step"))


def _render_fleet(w, registry):
    snap = registry.metrics_snapshot()
    w.family("mxtpu_fleet_version_state", "gauge",
             "1 for each loaded model version, state as a label")
    w.family("mxtpu_fleet_pointer", "gauge",
             "1 for the version each routing pointer targets")
    w.family("mxtpu_fleet_canary_fraction", "gauge",
             "share of the model's traffic routed to its canary version")
    for model, info in snap.items():
        for role in ("serving", "canary"):
            if info.get(role):
                w.sample("mxtpu_fleet_pointer", 1,
                         labels={"model": model, "role": role,
                                 "version": info[role]})
        if info.get("canary"):
            w.sample("mxtpu_fleet_canary_fraction",
                     info.get("canary_fraction"), labels={"model": model})
        for version, vsnap in (info.get("versions") or {}).items():
            labels = {"model": model, "version": version}
            w.sample("mxtpu_fleet_version_state", 1,
                     labels={**labels, "state": vsnap.get("state", "?")})
            render_serving_section(w, vsnap, labels=labels)
            gen = vsnap.get("generation")
            if gen:
                render_generation_section(w, gen, labels=labels)


def render_gateway_section(w, snap):
    """A ``GatewayMetrics.snapshot()`` dict: the ``mxtpu_gateway_*``
    families — routed-request counters, failover/ejection/scale ledger,
    latency percentiles, and the per-replica routing table."""
    from ..serving.gateway import (GATEWAY_PROM_COUNTERS,
                                   GATEWAY_PROM_GAUGES)
    for key, help_text in GATEWAY_PROM_COUNTERS:
        if key in snap:
            w.counter("mxtpu_gateway_%s_total" % key, help_text,
                      snap[key])
    for key, help_text in GATEWAY_PROM_GAUGES:
        if snap.get(key) is not None:
            w.gauge("mxtpu_gateway_%s" % key, help_text, snap[key])
    _quantile_family(w, "mxtpu_gateway_latency_ms",
                     "gateway-observed routed-request latency "
                     "percentiles over the sliding window",
                     snap.get("latency_ms"))
    table = snap.get("replica_table") or {}
    for name, help_text, key in (
            ("mxtpu_gateway_replica_up",
             "1 when the replica is routable (up + healthy + breaker "
             "not open)", None),
            ("mxtpu_gateway_replica_queue_depth",
             "replica batcher queue depth from the last load scrape",
             "queue_depth"),
            ("mxtpu_gateway_replica_inflight",
             "gateway-tracked in-flight requests on the replica",
             "inflight"),
            ("mxtpu_gateway_replica_pins",
             "streams pinned to the replica", "pins"),
            ("mxtpu_gateway_replica_routed_total",
             "requests the gateway has routed to the replica",
             "routed"),
            ("mxtpu_gateway_replica_chips",
             "devices behind the replica (a sharded replica is a "
             "planned mesh of M chips; capacity math divides by this)",
             "chips")):
        mtype = "counter" if name.endswith("_total") else "gauge"
        w.family(name, mtype, help_text)
        for rid, rep in table.items():
            if key is None:
                val = int(rep.get("state") == "up"
                          and rep.get("health") == "ok"
                          and rep.get("breaker") != "open")
            else:
                val = rep.get(key)
            if val is None:
                val = 1 if key == "chips" else 0
            # every per-replica sample carries the mesh size so a
            # dashboard summing replica counts can weight by chips
            w.sample(name, val, labels={"replica": rid,
                                        "mesh": str(rep.get("chips") or 1)})


def _const_labels():
    """Labels stamped on every sample this process exposes: its elastic
    rank when it has one (launcher env or live ElasticMember), so a
    fleet-wide scrape aggregation is attributable per worker even
    before ``tools/telemetry_agg.py`` relabels anything."""
    from ..resilience import elastic as _elastic
    rank = _elastic.current_rank()
    return {"rank": rank} if rank is not None else {}


# ---------------------------------------------------------------------------
# top-level renders
# ---------------------------------------------------------------------------

def render_process(extra=None):
    """The process-wide exposition (no ModelServer required): aggregate
    rows, CachedOp counters, trace histograms + sampler, device
    memory/MFU, elastic membership. ``extra(writer)`` appends more."""
    w = PromWriter(const_labels=_const_labels())
    _render_telemetry(w)
    _render_roofline(w)
    _render_trace(w)
    _render_cachedop(w)
    _render_pcache(w)
    _render_elastic(w)
    _render_aggregate_rows(w)
    if extra is not None:
        extra(w)
    return w.text()


def render_server(server):
    """Everything ``render_process`` exposes plus the server's serving /
    generation / fleet-lane sections — the ``GET /metrics.prom`` body."""

    def _extra(w):
        if server.registry is not None:
            _render_fleet(w, server.registry)
            return
        snap = server.metrics.snapshot()
        render_serving_section(w, snap)
        gen = getattr(server.generator, "metrics", None) \
            if server.generator is not None else None
        if gen is not None:
            render_generation_section(w, gen.snapshot())

    return render_process(extra=_extra)


def render_gateway(gateway):
    """Everything ``render_process`` exposes plus the gateway's routing
    section — the gateway's ``GET /metrics.prom`` body."""
    return render_process(
        extra=lambda w: render_gateway_section(
            w, gateway.metrics.snapshot()))
