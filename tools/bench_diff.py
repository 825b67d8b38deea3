#!/usr/bin/env python
"""Bench regression ledger: compare two bench artifacts, gate on it.

Every benchmark in this repo writes a JSON artifact (``benchmark/*.json``,
the ``BENCH_r0x.json`` round files) — but until now nothing *compared* them, so a regression was silently
recorded instead of caught (the ROADMAP's "rounds 4→5 have no signal"
failure class). This tool loads two artifacts, walks every **shared**
numeric metric (nested dicts/lists flatten to dotted paths), applies a
per-metric direction + tolerance, and emits a JSON verdict.

Direction inference (override with ``--direction path=higher|lower``):

- *higher is better*: throughput-shaped names — ``img_s``, ``qps``,
  ``tokens``/``img``/``seq`` per second, ``mfu``, ``hits``,
  ``speedup``, ``efficiency``, ``value`` next to a ``unit`` ending in
  ``/s``;
- *lower is better*: latency/cost-shaped names — ``_ms``/``_s``/
  ``_ns`` suffixes, ``p50``/``p95``/``p99``, ``latency``, ``ttft``,
  ``overhead``, ``compile``, ``misses``, ``evictions``, ``penalty``,
  ``wait``, ``stall``, ``dropped``;
- everything else is *informational*: compared, reported on drift, but
  never gates (counts like ``steps`` or ``requests`` are config, not
  performance).

Exit codes (the ``--gate`` contract, for CI and future bench rounds)::

    0  ok (no gated metric regressed beyond tolerance)
    2  regression (at least one gated metric worse than -tolerance)
    3  unreadable input (missing file, bad JSON, no shared metrics)

Usage::

    python tools/bench_diff.py BENCH_r04.json BENCH_r06.json --gate
    python tools/bench_diff.py benchmark/SERVING.json /tmp/SERVING.json \
        --tolerance 0.1
    python tools/bench_diff.py old.json new.json --json-only
"""
from __future__ import annotations

import argparse
import json
import re
import sys

DEFAULT_TOLERANCE = 0.05   # 5% — measurement noise on the CPU oracle
HIGHER, LOWER, INFO = "higher", "lower", "info"

_HIGHER_PAT = re.compile(
    r"(img_s|img_per_sec|per_sec|_s_per_|qps|tokens_s|tok_s|/s$|"
    r"throughput|speedup|mfu|tflops|gflops|flops_rate|hits\b|"
    r"efficiency|vs_baseline|ratio_better|samples_per|tokens_saved|"
    r"improvement)", re.I)
_LOWER_PAT = re.compile(
    r"(_ms\b|_ms_|_ns\b|_ns_|ms_per|ns_per|_s\b$|seconds\b|p50|p95|p99|"
    r"latency|ttft|overhead|compile|misses|evictions|penalty|wait|"
    r"stall|dropped|expired|failures|errors|time_to)", re.I)

# workload-composition ratios from the generation-v2 artifact: compared
# and reported on drift, but never gated — a prefix hit-rate or
# speculative acceptance rate moving tracks the WORKLOAD MIX (and the
# draft model), not a performance regression; the throughput/TTFT
# numbers they drive are the gated ones
_RATE_INFO_PAT = re.compile(
    r"(hit_rate|acceptance_rate|accepted_rate|skip_pct|skipped_pct|"
    r"coverage|tokens_saved_pct|occupancy)", re.I)

# path segments that are configuration/identity, never performance —
# skipped entirely (comparing them as metrics would gate on noise like
# a changed pid or step count)
_SKIP_PAT = re.compile(
    r"(^|\.)(n|pid|port|steps|requests|reps|batch|image|seq|slots|"
    r"devices?|world|buckets?|capacity|seed|version|epoch|fail_step|"
    r"total_ops|timed_ops)($|\.)", re.I)


def _list_segments(items):
    """Path segments for a list's elements: a list of dicts that carry
    an identity key (``metric``/``op``/``name``/``id``) is keyed by it —
    ranked lists (a roofline table, a record list) reorder between
    rounds, and positional comparison would gate row i of one round
    against a DIFFERENT entity's row i in the other.
    Duplicate or missing identities fall back to the index."""
    segs = []
    seen = {}
    for i, val in enumerate(items):
        ident = None
        if isinstance(val, dict):
            for k in ("metric", "op", "name", "id"):
                v = val.get(k)
                if isinstance(v, str) and v:
                    ident = v
                    break
        if ident is None or ident in seen:
            segs.append(str(i))
        else:
            seen[ident] = i
            segs.append(ident)
    return segs


def flatten(doc, prefix=""):
    """Nested dict/list -> {dotted.path: float} over numeric leaves
    (bools excluded — a flipped ``pass`` flag is schema, not a metric;
    list elements become path segments by identity key when they have
    one, else by index — see :func:`_list_segments`)."""
    out = {}
    if isinstance(doc, dict):
        items = doc.items()
    elif isinstance(doc, list):
        items = zip(_list_segments(doc), doc)
    else:
        items = ()
    for key, val in items:
        path = "%s.%s" % (prefix, key) if prefix else str(key)
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            out[path] = float(val)
        elif isinstance(val, (dict, list)):
            out.update(flatten(val, path))
    return out


def unit_directions(doc, prefix=""):
    """Direction overrides read from the artifacts themselves: a dict
    carrying a numeric ``value`` next to a string ``unit`` declares its
    own direction — ``*/s`` throughput units are higher-better,
    ``ms``/``s`` latency units lower-better. This is how the headline
    ``{"metric", "value", "unit"}`` records every bench in this repo
    prints gate correctly without name heuristics."""
    out = {}
    if isinstance(doc, dict):
        unit = doc.get("unit")
        if isinstance(unit, str) and isinstance(
                doc.get("value"), (int, float)) \
                and not isinstance(doc.get("value"), bool):
            path = "%s.value" % prefix if prefix else "value"
            if unit.endswith("/s"):
                out[path] = HIGHER
            elif unit in ("ms", "s", "us", "ns"):
                out[path] = LOWER
        items = doc.items()
    elif isinstance(doc, list):
        # same segmentation as flatten(), or the declared directions
        # would miss the metrics they describe
        items = zip(_list_segments(doc), doc)
    else:
        items = ()
    for key, val in items:
        path = "%s.%s" % (prefix, key) if prefix else str(key)
        if isinstance(val, (dict, list)):
            out.update(unit_directions(val, path))
    return out


def _round_payload(doc):
    """A ``BENCH_r0x.json`` round file carries its real metrics under
    ``parsed`` (None when the round died) — compare that payload, not
    the wrapper's rc/tail bookkeeping."""
    if isinstance(doc, dict) and "parsed" in doc and "cmd" in doc:
        return doc["parsed"] if doc["parsed"] is not None else {}
    return doc


def load_artifact(path):
    """Artifact dict/list from ``path``; raises ``ValueError`` with a
    usable message on unreadable input (the exit-3 class)."""
    try:
        with open(path) as f:
            raw = f.read()
    except OSError as exc:
        raise ValueError("cannot read %s: %s" % (path, exc)) from exc
    if not raw.strip():
        raise ValueError("%s is empty" % path)
    try:
        doc = json.loads(raw)
    except ValueError as exc:
        raise ValueError("%s is not valid JSON: %s" % (path, exc)) \
            from exc
    return _round_payload(doc)


def direction_for(path, overrides=None):
    if overrides:
        if path in overrides:   # exact path beats any suffix pattern
            return overrides[path]
        for pat, d in overrides.items():
            if path.endswith("." + pat):
                return d
    if _SKIP_PAT.search(path):
        return None
    if _RATE_INFO_PAT.search(path):
        return INFO
    if _HIGHER_PAT.search(path):
        return HIGHER
    if _LOWER_PAT.search(path):
        return LOWER
    return INFO


def diff(baseline, candidate, tolerance=DEFAULT_TOLERANCE,
         overrides=None):
    """Compare two flattened artifacts. Returns the verdict dict::

        {status: ok|regression, compared, gated,
         regressions: [...], improvements: [...], drifts: [...],
         only_baseline: [...], only_candidate: [...]}

    A *regression* is a gated metric whose relative change in the
    better direction is below ``-tolerance``; an *improvement* is one
    above ``+tolerance``; in between is noise and stays silent. A
    baseline value of 0 compares by absolute change against
    ``tolerance`` (relative change is undefined).
    """
    base = flatten(baseline)
    cand = flatten(candidate)
    # artifact-declared directions (unit= fields) under any explicit
    # --direction overrides, which win
    declared = unit_directions(baseline)
    declared.update(overrides or {})
    overrides = declared
    shared = sorted(set(base) & set(cand))
    regressions, improvements, drifts = [], [], []
    gated = 0
    for path in shared:
        d = direction_for(path, overrides)
        if d is None:
            continue
        b, c = base[path], cand[path]
        if b == 0.0:
            rel = c - b   # absolute fallback; 0 baselines are rare
        else:
            rel = (c - b) / abs(b)
        signed = rel if d != LOWER else -rel
        rec = {"metric": path, "baseline": b, "candidate": c,
               "change": rel, "direction": d}
        if d == INFO:
            if abs(rel) > tolerance:
                drifts.append(rec)
            continue
        gated += 1
        if signed < -tolerance:
            regressions.append(rec)
        elif signed > tolerance:
            improvements.append(rec)
    regressions.sort(key=lambda r: (r["change"] if r["direction"] == LOWER
                                    else -r["change"]), reverse=True)
    return {
        "status": "regression" if regressions else "ok",
        "tolerance": tolerance,
        "compared": len(shared),
        "gated": gated,
        "regressions": regressions,
        "improvements": improvements,
        "drifts": drifts,
        "only_baseline": sorted(set(base) - set(cand)),
        "only_candidate": sorted(set(cand) - set(base)),
    }


def format_verdict(verdict, baseline_path, candidate_path):
    lines = ["bench_diff: %s -> %s : %s"
             % (baseline_path, candidate_path,
                verdict["status"].upper()),
             "  %d shared metrics, %d gated, tolerance %.0f%%"
             % (verdict["compared"], verdict["gated"],
                verdict["tolerance"] * 100.0)]

    def _section(title, recs):
        if not recs:
            return
        lines.append("  %s:" % title)
        for r in recs:
            lines.append("    %-52s %12.4g -> %-12.4g (%+.1f%%, %s "
                         "is better)"
                         % (r["metric"], r["baseline"], r["candidate"],
                            r["change"] * 100.0, r["direction"]))

    _section("REGRESSIONS", verdict["regressions"])
    _section("improvements", verdict["improvements"])
    _section("info drift (not gated)", verdict["drifts"])
    if verdict["only_baseline"]:
        lines.append("  metrics only in baseline: %d (schema drift?)"
                     % len(verdict["only_baseline"]))
    if verdict["only_candidate"]:
        lines.append("  metrics only in candidate: %d"
                     % len(verdict["only_candidate"]))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Compare two bench artifacts; --gate exits 2 on "
                    "regression, 3 on unreadable input")
    ap.add_argument("baseline")
    ap.add_argument("candidate")
    ap.add_argument("--tolerance", type=float,
                    default=DEFAULT_TOLERANCE,
                    help="relative change treated as noise "
                         "(default %.2f)" % DEFAULT_TOLERANCE)
    ap.add_argument("--direction", action="append", default=[],
                    metavar="path=higher|lower|info",
                    help="override direction inference for a metric "
                         "path (repeatable)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 2 on regression (default exit is 0 "
                         "unless input is unreadable)")
    ap.add_argument("--json-only", action="store_true",
                    help="emit only the JSON verdict")
    args = ap.parse_args(argv)
    overrides = {}
    for spec in args.direction:
        path, _, d = spec.partition("=")
        if d not in (HIGHER, LOWER, INFO):
            print("bench_diff: bad --direction %r (want path=higher|"
                  "lower|info)" % spec, file=sys.stderr)
            return 3
        overrides[path] = d
    try:
        baseline = load_artifact(args.baseline)
        candidate = load_artifact(args.candidate)
    except ValueError as exc:
        print("bench_diff: %s" % exc, file=sys.stderr)
        return 3
    verdict = diff(baseline, candidate, tolerance=args.tolerance,
                   overrides=overrides)
    if verdict["compared"] == 0:
        print("bench_diff: no shared numeric metrics between %s and %s "
              "— nothing to compare" % (args.baseline, args.candidate),
              file=sys.stderr)
        print(json.dumps(verdict, indent=2))
        return 3
    if args.json_only:
        print(json.dumps(verdict, indent=2))
    else:
        print(format_verdict(verdict, args.baseline, args.candidate))
        print(json.dumps(verdict, indent=2))
    if args.gate and verdict["status"] == "regression":
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
