"""Run one cell of the benchmark once, on the chip.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. Refuses to start unless JAX came up on ``tpu`` with
the chips the cell asks for; never sets ``JAX_PLATFORMS``. Progress goes to
earlier lines; the LAST line of stdout is the one JSON result. Everything
that belongs to one configuration, traffic mix or per-layer metric is a file
of its own, found by the name ``BENCHMARK.json`` gives (see README.md).
"""
import time

T_START = time.monotonic()      # before anything heavy is imported

import argparse
import importlib
import importlib.util
import json
import os
import re
import sys

from chipbench import check
from chipbench.peaks import peaks_for
from chipbench.trace_reduce import TraceWindow, open_span_at

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Context:
    """What a traffic module gets: the cell, the seed, the clock of the
    run and the trace switch."""

    def __init__(self, cfg, cell, seed, seconds, trace, devices):
        self.cfg, self.cell = cfg, cell
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.tracer = TraceWindow(os.path.join(TRACE_DIR, cell["name"]))
        self.setup_s = None
        self.window = None
        self.counters_at_open = {}

    def log(self, msg, **fields):
        print(msg + (" " + json.dumps(fields) if fields else ""), flush=True)

    def since_start(self):
        return time.monotonic() - T_START

    def open_window(self):
        """Set-up ends here: process start to now, compilation included."""
        from mxnet_tpu import pcache
        from mxnet_tpu.observability import tracer
        self.counters_at_open = {"pcache": pcache.stats()}
        if self.trace:
            tracer.clear()
        self.setup_s = self.since_start()
        self.window = [time.monotonic(), None]


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("%s %r is not in the benchmark's index (has %s)"
                     % (what, name, [e["name"] for e in entries]))


def load_cell(workload, index=None, workload_dir=None):
    """``(index entry, configuration, cell)`` of a workload: the entry of
    ``BENCHMARK.json`` (or of the tests' tiny index), the configuration's
    file and ``<workload_dir>/<workload>.json``."""
    index = index or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = find(index["workloads"], workload, "workload")
    cfg_entry = find(index["configs"], entry["config"], "configuration")
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    cell = load_json(os.path.join(
        workload_dir or os.path.join(HERE, "workloads"), workload + ".json"))
    cell["name"] = workload
    if cell["config"] != entry["config"] or cell["traffic"] != entry["traffic"]:
        raise SystemExit("workload file and index disagree for %r" % workload)
    return entry, cfg, cell


def load_reader(name, directory):
    """The per-layer metric ``name`` is the file ``<name>.py`` there."""
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def program_spans(window):
    """The program's own spans that overlap the window, as ``(name, start,
    end, attrs)`` on the monotonic clock."""
    from mxnet_tpu.observability import tracer
    lo, hi = window
    out = []
    for rec in tracer.events():
        if rec[0] != "X":
            continue
        _, name, t0, dur, *_rest, attrs = rec
        if t0 + dur > lo and t0 < hi:
            out.append((name, t0, t0 + dur, attrs or {}))
    return out


def breakdown_of(trace, spans):
    """Top device operations and longest idle gaps (by the program span
    open on the host in each), at most 10 of each. Operations are summed
    over the compiler's numbering (``fusion.12`` and ``fusion.40`` are both
    ``fusion``): twelve layers give every op twelve names, and the ten
    longest single names would all be one kernel."""
    stems = {}
    for name, seconds in trace["by_name"].items():
        stem = re.sub(r"\.\d+$", "", name)
        stems[stem] = stems.get(stem, 0.0) + seconds
    ops = sorted(stems.items(), key=lambda kv: -kv[1])[:10]
    shift = trace["to_monotonic"]
    named = {}
    for start, end in trace["gaps"]:
        mid = (start + end) / 2 + shift
        who = open_span_at([s[:3] for s in spans], mid)
        named[who] = named.get(who, 0.0) + (end - start)
    gaps = sorted(named.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


def run_cell(index, workload, seed, seconds, trace, devices, workload_dir=None,
             metric_dir=os.path.join(HERE, "layer_metrics"), rehearsal=False):
    """Drive one cell and return the result line as a dict. ``index`` is the
    parsed ``BENCHMARK.json`` (the tests pass a tiny one of their own, with
    its own cells and metric lists, and ``rehearsal``: no device trace is
    read, so ``obs["trace"]`` is None)."""
    import jax
    entry, cfg, cell = load_cell(workload, index, workload_dir)
    ctx = Context(cfg, cell, seed, seconds, trace, devices[:entry["chips"]])
    if trace:
        from mxnet_tpu.observability import tracer
        tracer.enable(capacity=1 << 20)
    traffic = importlib.import_module("chipbench.traffic." + cell["kind"])
    drove = traffic.drive(ctx)

    used = ctx.devices
    stats = [d.memory_stats() or {} for d in used]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(
                  s.get("peak_bytes_in_use", 0) for s in stats)}
    if not rehearsal:       # a rehearsal prints no time, rate or share
        ctx.log("window closed", setup_s=ctx.setup_s, device=device,
                memory_stats=stats[0], **drove["end_to_end"])

    wanted = index["per_layer"] if trace else index["end_to_end"]
    here = [m for m in wanted if workload in m.get("workloads", [workload])]
    metrics, breakdown = {}, None
    if trace:
        spans = program_spans(ctx.window)
        compiled = [(n, a) for n, _, _, a in spans if n == "cachedop.compile"]
        from mxnet_tpu import pcache
        requests = pcache.stats()["requests"] \
            - ctx.counters_at_open["pcache"]["requests"]
        if compiled or requests:
            ctx.log("compiled inside the window", spans=compiled[:5],
                    persistent_cache_requests=requests)
        reduction = None
        if not rehearsal:
            reduction = ctx.tracer.reduction(entry["chips"])
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
            breakdown = breakdown_of(reduction, spans)
        obs = dict(drove["observations"], trace=reduction, spans=spans,
                   cfg=cfg, cell=cell, setup_s=ctx.setup_s,
                   window=ctx.window, counters=ctx.counters_at_open,
                   peaks=None if rehearsal
                   else peaks_for(used[0].device_kind))
        for m in here:
            value = load_reader(m["name"], metric_dir)(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(drove["end_to_end"], setup_s=ctx.setup_s)
        for m in here:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    numbers = drove["verify"]()
    correct, compared = check.judge(numbers, cell["limits"],
                                    cell.get("not_compared", ()))
    result = {"correct": correct, "attempted": drove["attempted"],
              "failed": drove["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    index = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = find(index["workloads"], args.workload, "workload")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit("chipbench runs on the chip: jax came up on %r (%s)"
                 % (devices[0].platform, devices))
    if len(devices) < entry["chips"]:
        sys.exit("%s needs %d chip(s): jax reports %d"
                 % (args.workload, entry["chips"], len(devices)))
    result = run_cell(index, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    for name, (value, limit) in result["compared"].items():
        print("compared %s %.6g limit %.6g" % (name, value, limit),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    # a result whose outputs are wrong is still a result: exit 0, and the
    # line says ``correct: false``
    return 0


if __name__ == "__main__":
    sys.exit(main())
