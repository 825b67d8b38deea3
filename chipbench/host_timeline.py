"""The host's side of a training run, read from the program's own spans and
counters: where set-up went, and where a long step-to-step interval went.

Set-up (process start to the window's start) reaches a reader two ways,
because the harness clears the tracer's ring when the window opens:
``obs["counters"]["pcache"]`` (jax's own trace / lower / compile / load
seconds, snapshotted at the window's start) and the tracer's
``phase_stats()`` (per-name totals and self times, which outlive the
clearing). The window reaches it as ``obs["spans"]``: ``(name, start, end,
attrs)`` on the monotonic clock, all of one thread in a training cell.

A *stall* is an interval between the starts of two consecutive
``trainer.step`` spans longer than ``STALLED`` medians. Intervals that hold
the profiler's start or stop are left out: the benchmark itself waits there.
Every function returns None on a program that lacks the spans or counters
(the parent's) and in a cell that does not train.
"""
import json

from chipbench.stats import percentile
from chipbench.trace_reduce import open_span_at, union_seconds

STEP, LAUNCH, WAIT = "trainer.step", "trainer.launch", "ndarray.wait"
STALLED = 1.5


# ---- set-up -----------------------------------------------------------------

def setup_counter(obs, key):
    """Seconds under ``pcache.stats()[key]`` at the window's start."""
    if obs["kind"] != "train":
        return None
    return obs["counters"]["pcache"].get(key)


def phase(obs, name):
    """``phase_stats()[name]`` of the program's tracer, or None."""
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.observability import tracer
    return tracer.phase_stats().get(name)


def setup_named_pct(obs):
    """Share of set-up under at least one program span: every name's self
    time so far, less the window's own spans, over ``setup_s``."""
    if obs["kind"] != "train":
        return None
    from mxnet_tpu.observability import tracer
    stats = tracer.phase_stats().values()
    if not any("self_ms" in st for st in stats):
        return None
    named = sum(st["self_ms"] for st in stats) / 1e3
    window = union_seconds([(s, e) for _, s, e, _ in obs["spans"]])
    return 100.0 * (named - window) / obs["setup_s"]


# ---- the window -------------------------------------------------------------

def seconds_by_name(spans, lo, hi):
    """``{name: seconds}`` of ``[lo, hi]`` by the innermost span open, and
    under ``"none"`` where none is."""
    inside = [(n, s, e) for n, s, e, _ in spans if e > lo and s < hi]
    cuts = sorted({lo, hi, *(min(max(t, lo), hi)
                             for _, s, e in inside for t in (s, e))})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        who = open_span_at(inside, (a + b) / 2)
        out[who] = out.get(who, 0.0) + (b - a)
    return out


def analyse(spans, marks=()):
    """The step-to-step intervals of a span list. ``marks``: times the
    profiler was started or stopped at. Returns None where the program
    recorded no ``trainer.launch`` (the parent's) or fewer than three
    steps; else ``median_s``, ``max_over_p50``, ``stall_s`` (the seconds
    over the median of the intervals longer than ``STALLED`` medians),
    ``stall_host_pct`` (of that excess, the share not spent in longer
    ``ndarray.wait``s than the median interval's; where no interval
    stalled, of the longest interval's excess: every traced run has one,
    so the metric is in every result line, and beside a ``stall_s`` of 0
    it says which side the step's jitter came from; None only where no
    interval is longer than the median) and ``longest``: the three
    longest intervals with the step's ``t`` and the seconds under each
    span name."""
    steps = sorted((s, a.get("t")) for n, s, _, a in spans if n == STEP)
    if len(steps) < 3 or not any(n == LAUNCH for n, *_ in spans):
        return None
    intervals = [(a, b, t) for (a, t), (b, _) in zip(steps, steps[1:])
                 if not any(a <= m < b for m in marks)]
    if len(intervals) < 2:
        return None
    names = [seconds_by_name(spans, a, b) for a, b, _ in intervals]
    lengths = [b - a for a, b, _ in intervals]
    median = percentile(lengths, 50)
    usual_wait = percentile([n.get(WAIT, 0.0) for n in names], 50)
    order = sorted(range(len(lengths)), key=lambda i: -lengths[i])
    stalled = [i for i in order if lengths[i] > STALLED * median]
    stall_s = sum((lengths[i] - median for i in stalled), 0.0)
    excess = waited = 0.0
    for i in stalled or order[:1]:
        excess += lengths[i] - median
        waited += min(lengths[i] - median,
                      max(0.0, names[i].get(WAIT, 0.0) - usual_wait))
    return {"median_s": median, "intervals": len(lengths),
            "max_over_p50": lengths[order[0]] / median,
            "stall_s": stall_s,
            "stall_host_pct": 100.0 * (1.0 - waited / excess)
            if excess > 0.0 else None,
            "longest": [{"t": intervals[i][2], "seconds": lengths[i],
                         "by_name": names[i]} for i in order[:3]]}


def timeline(obs):
    """:func:`analyse` of the window, once a run: kept in ``obs`` for the
    other stall readers, and printed as a progress line (the three longest
    intervals: what the builder of a stall's repair reads)."""
    if "host_timeline" not in obs:
        found = None
        if obs["kind"] == "train":
            trace = obs.get("trace")
            marks = [t + trace["to_monotonic"] for t in trace["window"]] \
                if trace else ()
            found = analyse(obs["spans"], marks)
        obs["host_timeline"] = found
        if found is not None:
            print("host timeline " + json.dumps(found), flush=True)
    return obs["host_timeline"]


def stall(obs, key):
    found = timeline(obs)
    return None if found is None else found[key]
