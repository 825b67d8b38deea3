#!/usr/bin/env python
"""Summarize a dumped Chrome Trace Event JSON (``profiler.dump()``).

Reads the ``profile.json`` the profiler writes and prints the numbers the
timeline exists to surface:

- the **critical-path split**: host compute (``trainer.*`` spans) vs.
  stage wait (``datafeed.consumer_wait``) vs. queue wait
  (``serving.queue_wait``) vs. XLA compiles (``cachedop.compile``), and
  the staging **overlap efficiency** — the fraction of training time NOT
  spent stalled on input staging (1.0 = perfect overlap, the
  ``step_stream`` design target), and for a generation scheduler the
  host's own time per iteration (``generation.iteration`` less its
  children: the device calls and ``generation.emit``). Category sums use
  **exclusive (self) time** — a span's duration minus its direct
  children's overlap — so a parent is never double-counted over the
  children nested inside it;
- a per-span-name aggregate table (count / total / self / mean / max);
- the **top-N slowest spans**, each with its request id when it carries
  one — the p99 outlier, decomposed.

Pure stdlib, no mxnet_tpu import needed: it reads the JSON interchange
format, so it also works on traces copied off another host.

Usage::

    python tools/trace_summary.py /tmp/mxnet_tpu_profile/profile.json
    python tools/trace_summary.py profile.json --top 20
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

# span-name prefixes -> critical-path category
COMPUTE_PREFIXES = ("trainer.",)
STAGE_WAIT_NAMES = ("datafeed.consumer_wait",)
QUEUE_WAIT_NAMES = ("serving.queue_wait",)
COMPILE_NAMES = ("cachedop.compile",)
# jax's own phases, bridged by pcache.py: name -> column of the set-up block
JAX_PHASES = {"jax.trace": "trace_ms", "jax.lower": "lower_ms",
              "jax.compile": "compile_ms", "pcache.load": "load_ms"}
BUILD_NAMES = ("trainer.build", "block.initialize")
HOST_WAIT = "ndarray.wait"
SERVING_ROOT = "serving.http"
SCHEDULER_ITERATION = "generation.iteration"
SCHEDULER_EMIT = "generation.emit"


class TraceLoadError(Exception):
    """A trace file that can't be summarized — missing, empty, or not
    Chrome Trace JSON — with a message naming which."""


def load_trace(path):
    """``(events, kept)`` from a Chrome Trace JSON file (object format,
    or a bare event array): the ``traceEvents`` list and the
    ``keptTraces`` map (``{trace_id_hex: reason}``) the tail sampler
    embedded, empty when absent. Raises :class:`TraceLoadError` with a
    usable message instead of tracebacking on a missing/empty/corrupt
    file — ``profiler.dump()`` before any span is recorded writes a
    valid-but-empty document, and a crashed run can truncate one."""
    try:
        with open(path) as f:
            raw = f.read()
    except OSError as exc:
        raise TraceLoadError("cannot read trace file %s: %s"
                             % (path, exc)) from exc
    if not raw.strip():
        raise TraceLoadError(
            "trace file %s is empty — was the profiler session ever "
            "started (profiler.set_state('run')) before dump()?" % path)
    try:
        doc = json.loads(raw)
    except ValueError as exc:
        raise TraceLoadError(
            "trace file %s is not valid JSON (%s) — a crashed run can "
            "truncate the dump; re-run profiler.dump()" % (path, exc)) \
            from exc
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if events is None:
            raise TraceLoadError(
                "trace file %s has no traceEvents key — not a Chrome "
                "Trace Event document" % path)
        return events, dict(doc.get("keptTraces") or {})
    if not isinstance(doc, list):
        raise TraceLoadError("trace file %s is neither a Chrome Trace "
                             "object nor an event array" % path)
    return doc, {}


def _is_span(ev):
    return ev.get("ph") == "X" and "dur" in ev


def exclusive_durations(spans):
    """Per-span *self* time: duration minus the time covered by direct
    children (linked via the ``span_id``/``parent_id`` the exporter puts
    in ``args``). Without this, every aggregate that sums durations
    double-counts parents over children — ``serving.http`` "contains"
    its own queue wait, so inclusive sums overstate the serving total by
    exactly the child time. Returns ``{id(ev): self_us}``; spans with no
    linkage (hand-written traces) keep their full duration."""
    by_span_id = {}
    for ev in spans:
        sid = (ev.get("args") or {}).get("span_id")
        if sid is not None:
            by_span_id[sid] = ev
    child_us = defaultdict(float)
    for ev in spans:
        args = ev.get("args") or {}
        parent = args.get("parent_id")
        # a nested event (a jit traced inside another's trace, a cache
        # load inside its compile) lies inside a sibling that counts it
        if not parent or parent not in by_span_id or args.get("nested"):
            continue
        par = by_span_id[parent]
        # clamp the child's contribution to the parent's interval:
        # cross-thread children (queue waits recorded after the fact)
        # can overhang, and a child must never push self time negative
        p0, p1 = par["ts"], par["ts"] + par["dur"]
        c0, c1 = ev["ts"], ev["ts"] + ev["dur"]
        overlap = max(0.0, min(p1, c1) - max(p0, c0))
        child_us[parent] += overlap
    out = {}
    for ev in spans:
        args = ev.get("args") or {}
        sid = args.get("span_id")
        covered = child_us.get(sid, 0.0) if sid is not None else 0.0
        out[id(ev)] = 0.0 if args.get("nested") \
            else max(0.0, ev["dur"] - covered)
    return out


def setup_block(spans):
    """What jax spent making programs, by program, from the bridged
    ``jax.*`` events (``args.fun``): ``{"programs": {fun: {trace_ms,
    lower_ms, compile_ms, load_ms}}, "totals": {...}}``. A trace nested in
    another's adds to its program's row and not to the totals; a load lies
    inside its compile, so the totals' ``compile_ms`` leaves it out."""
    programs = defaultdict(lambda: dict.fromkeys(JAX_PHASES.values(), 0.0))
    totals = dict.fromkeys(JAX_PHASES.values(), 0.0)
    for ev in spans:
        column = JAX_PHASES.get(ev["name"])
        if column is None:
            continue
        args = ev.get("args") or {}
        ms = ev["dur"] / 1e3
        programs[args.get("fun", "?")][column] += ms
        if column == "load_ms":
            totals["load_ms"] += ms
            totals["compile_ms"] -= ms
        elif not args.get("nested"):
            totals[column] += ms
    return {"programs": dict(programs), "totals": totals}


def summarize(events, top=10, kept=None):
    """Aggregate a trace into one JSON-able summary dict. ``kept`` is
    the sampler's ``{trace_id_hex: reason}`` map — top-N spans whose
    trace was kept are flagged, because those are the ones a histogram
    exemplar (or a colleague's trace-id handle) can actually pull up."""
    kept = kept or {}
    spans = [ev for ev in events if _is_span(ev)]
    instants = [ev for ev in events if ev.get("ph") == "i"]
    threads = {ev["tid"]: ev["args"].get("name", str(ev["tid"]))
               for ev in events
               if ev.get("ph") == "M" and ev.get("name") == "thread_name"}

    self_us = exclusive_durations(spans)
    # count, total_us, max_us, self_us
    by_name = defaultdict(lambda: [0, 0.0, 0.0, 0.0])
    for ev in spans:
        ent = by_name[ev["name"]]
        ent[0] += 1
        ent[1] += ev["dur"]
        if ev["dur"] > ent[2]:
            ent[2] = ev["dur"]
        ent[3] += self_us[id(ev)]

    def total_ms(match, exclusive=True):
        """Category total over *exclusive* time by default: a parent's
        children must not be counted into the parent AND themselves
        (e.g. trainer.step nesting inside trainer.step_many, compiles
        inside engine.execute)."""
        idx = 3 if exclusive else 1
        if callable(match):
            return sum(ent[idx] for n, ent in by_name.items()
                       if match(n)) / 1e3
        return sum(by_name[n][idx] for n in match if n in by_name) / 1e3

    compute_ms = total_ms(lambda n: n.startswith(COMPUTE_PREFIXES))
    stage_wait_ms = total_ms(STAGE_WAIT_NAMES)
    queue_wait_ms = total_ms(QUEUE_WAIT_NAMES)
    compile_ms = total_ms(COMPILE_NAMES)
    # the serving root is reported inclusive (a request's wall time) AND
    # exclusive (handler-only time, children counted in their own rows)
    serving_ms = by_name[SERVING_ROOT][1] / 1e3 \
        if SERVING_ROOT in by_name else 0.0
    serving_self_ms = by_name[SERVING_ROOT][3] / 1e3 \
        if SERVING_ROOT in by_name else 0.0
    # the scheduler's own host time: what ran between the device calls
    iterations = by_name[SCHEDULER_ITERATION][0] \
        if SCHEDULER_ITERATION in by_name else 0

    wall_ms = 0.0
    if spans:
        t0 = min(ev["ts"] for ev in spans)
        t1 = max(ev["ts"] + ev["dur"] for ev in spans)
        wall_ms = (t1 - t0) / 1e3

    overlap_efficiency = None
    # stage waits happen INSIDE trainer chunk spans, so the efficiency
    # denominator must be the INCLUSIVE trainer wall (the exclusive
    # compute sum already has the wait subtracted out — dividing by it
    # would double-penalize the wait and clamp efficiency to 0 whenever
    # waits exceed half the chunk)
    # ... counted once: trainer.place / .launch nest inside trainer.step,
    # so only the trainer spans that no other trainer span holds add up
    trainer_ids = {(ev.get("args") or {}).get("span_id") for ev in spans
                   if ev["name"].startswith(COMPUTE_PREFIXES)} - {None}
    compute_incl_ms = sum(
        ev["dur"] for ev in spans
        if ev["name"].startswith(COMPUTE_PREFIXES)
        and (ev.get("args") or {}).get("parent_id") not in trainer_ids) / 1e3
    if compute_incl_ms > 0:
        overlap_efficiency = max(0.0,
                                 1.0 - stage_wait_ms / compute_incl_ms)

    def _kept_reason(ev):
        tid = (ev.get("args") or {}).get("trace_id")
        if tid is None:
            return None
        key = "%x" % tid if isinstance(tid, int) else str(tid)
        return kept.get(key)

    slowest = sorted(spans, key=lambda ev: -ev["dur"])[:top]
    top_spans = [{
        "name": ev["name"],
        "dur_ms": ev["dur"] / 1e3,
        "self_ms": self_us[id(ev)] / 1e3,
        "ts_ms": ev["ts"] / 1e3,
        "thread": threads.get(ev["tid"], str(ev["tid"])),
        "request_id": (ev.get("args") or {}).get("request_id"),
        "trace_id": (ev.get("args") or {}).get("trace_id"),
        "kept": _kept_reason(ev),
    } for ev in slowest]

    # the retrievable handles: request ids of kept traces — what you
    # paste into a bug report next to the exemplar's trace id
    kept_request_ids = sorted({
        (ev.get("args") or {}).get("request_id")
        for ev in spans
        if _kept_reason(ev) and (ev.get("args") or {}).get("request_id")})

    names = {name: {"count": c, "total_ms": t / 1e3, "mean_ms": t / c / 1e3,
                    "max_ms": m / 1e3, "self_ms": s / 1e3}
             for name, (c, t, m, s) in by_name.items()}

    instant_counts = defaultdict(int)
    for ev in instants:
        instant_counts[ev["name"]] += 1

    setup = setup_block(spans)
    setup["build_ms"] = {n: names[n]["total_ms"] for n in BUILD_NAMES
                         if n in names}
    wait = names.get(HOST_WAIT)

    return {
        "spans": len(spans),
        "instants": len(instants),
        "threads": len(threads),
        "wall_ms": wall_ms,
        "critical_path": {
            "compute_ms": compute_ms,
            "stage_wait_ms": stage_wait_ms,
            "queue_wait_ms": queue_wait_ms,
            "compile_ms": compile_ms,
            "serving_ms": serving_ms,
            "serving_self_ms": serving_self_ms,
            "scheduler_iterations": iterations,
            "scheduler_self_ms": total_ms((SCHEDULER_ITERATION,)),
            "scheduler_emit_ms": total_ms((SCHEDULER_EMIT,)),
            "basis": "exclusive",
        },
        "overlap_efficiency": overlap_efficiency,
        "setup": setup,
        "waits": {"count": wait["count"] if wait else 0,
                  "total_ms": wait["total_ms"] if wait else 0.0,
                  "max_ms": wait["max_ms"] if wait else 0.0},
        "by_name": names,
        "instant_counts": dict(instant_counts),
        "top_spans": top_spans,
        "kept_traces": len(kept),
        "kept_request_ids": kept_request_ids,
    }


def format_summary(summary):
    """Render :func:`summarize` output as the human-readable report."""
    lines = []
    cp = summary["critical_path"]
    lines.append("Trace summary: %d spans, %d instants, %d threads, "
                 "wall %.1f ms"
                 % (summary["spans"], summary["instants"],
                    summary["threads"], summary["wall_ms"]))
    lines.append("")
    lines.append("Critical path split:")
    lines.append("  %-28s %12.2f ms" % ("train compute (trainer.*)",
                                        cp["compute_ms"]))
    lines.append("  %-28s %12.2f ms" % ("stage wait (consumer)",
                                        cp["stage_wait_ms"]))
    lines.append("  %-28s %12.2f ms" % ("serving queue wait",
                                        cp["queue_wait_ms"]))
    lines.append("  %-28s %12.2f ms" % ("XLA compiles", cp["compile_ms"]))
    lines.append("  %-28s %12.2f ms  (self %.2f ms)"
                 % ("serving requests (http)", cp["serving_ms"],
                    cp.get("serving_self_ms", cp["serving_ms"])))
    if cp.get("scheduler_iterations"):
        lines.append("  %-28s %12.2f ms  (%d iterations; emit %.2f ms)"
                     % ("scheduler host (self)", cp["scheduler_self_ms"],
                        cp["scheduler_iterations"],
                        cp["scheduler_emit_ms"]))
    lines.append("  (categories are EXCLUSIVE time: children are not "
                 "re-counted into parents)")
    if summary["overlap_efficiency"] is not None:
        lines.append("  staging overlap efficiency: %.1f%%"
                     % (summary["overlap_efficiency"] * 100.0))
    setup = summary.get("setup")
    if setup and (setup["programs"] or setup["build_ms"]):
        tot = setup["totals"]
        lines.append("")
        lines.append("Set-up (jax's own phases; compile leaves the cache "
                     "loads out):")
        lines.append("  %-28s trace %.1f  lower %.1f  compile %.1f  "
                     "load %.1f ms" % ("all programs", tot["trace_ms"],
                                       tot["lower_ms"], tot["compile_ms"],
                                       tot["load_ms"]))
        rows = sorted(setup["programs"].items(),
                      key=lambda kv: -sum(kv[1].values()))
        for fun, row in rows[:10]:
            lines.append("  %-28s trace %.1f  lower %.1f  compile %.1f  "
                         "load %.1f ms"
                         % (fun[:28], row["trace_ms"], row["lower_ms"],
                            row["compile_ms"] - row["load_ms"],
                            row["load_ms"]))
        for name, ms in setup["build_ms"].items():
            lines.append("  %-28s %12.2f ms" % (name, ms))
    waits = summary.get("waits")
    if waits and waits["count"]:
        lines.append("")
        lines.append("Waits: host blocked on the device (ndarray.wait) "
                     "%.2f ms in %d, longest %.2f ms"
                     % (waits["total_ms"], waits["count"], waits["max_ms"]))
    lines.append("")
    lines.append("Per-span aggregates (self = exclusive of children):")
    lines.append("  %-32s %8s %12s %12s %10s %10s"
                 % ("name", "count", "total ms", "self ms", "mean ms",
                    "max ms"))
    for name in sorted(summary["by_name"],
                       key=lambda n: -summary["by_name"][n]["total_ms"]):
        st = summary["by_name"][name]
        lines.append("  %-32s %8d %12.2f %12.2f %10.3f %10.3f"
                     % (name, st["count"], st["total_ms"],
                        st.get("self_ms", st["total_ms"]), st["mean_ms"],
                        st["max_ms"]))
    if summary["instant_counts"]:
        lines.append("")
        lines.append("Instant events:")
        for name in sorted(summary["instant_counts"]):
            lines.append("  %-32s %8d" % (name,
                                          summary["instant_counts"][name]))
    lines.append("")
    lines.append("Top %d slowest spans:" % len(summary["top_spans"]))
    for ev in summary["top_spans"]:
        rid = (" request_id=%s" % ev["request_id"]) if ev["request_id"] \
            else ""
        kept = (" [kept:%s]" % ev["kept"]) if ev.get("kept") else ""
        lines.append("  %10.3f ms  %-28s [%s]%s%s"
                     % (ev["dur_ms"], ev["name"], ev["thread"], rid, kept))
    if summary.get("kept_request_ids"):
        lines.append("")
        lines.append("Kept-exemplar request ids (%d kept trace(s)):"
                     % summary.get("kept_traces", 0))
        for rid in summary["kept_request_ids"]:
            lines.append("  %s" % rid)
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarize a profiler.dump() Chrome Trace JSON")
    ap.add_argument("trace", help="path to profile.json")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest spans to list (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of text")
    args = ap.parse_args(argv)
    try:
        events, kept = load_trace(args.trace)
    except TraceLoadError as exc:
        print("trace_summary: %s" % exc, file=sys.stderr)
        return 2
    summary = summarize(events, top=args.top, kept=kept)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(format_summary(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
