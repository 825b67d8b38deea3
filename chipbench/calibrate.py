"""Read, on the chip and at the cell's own size, the two readings each
limit is set from: what sound runs of the program give against the
reference (the lower reading, over many seeds), and what the control and the
planted faults give (the upper reading, over a few). One process, one JSON
line per seed on stdout and in ``chiprun_out/calibrate_<workload>.jsonl``.

    python -m chipbench.calibrate --workload <name> --seeds 11,12,13 \
        --controls 3 [--seconds 15]

The control is the reference in the configuration's ``control_precision``
put in the program's place. Faults (training): ``half_batch`` - half of the
batch left out, the mean taken over the rest - planted in the reference. A
state left unchanged reads 1 by the measure of ``check.worst_leaf_gap`` and
needs no run. The benchmark's own runs never call this.
"""
import argparse
import importlib
import json
import os
import sys
import time

from chipbench import check, run, serving
from chipbench.traffic import train_steps


def training_seed(cfg, cell, seed, devices, with_control):
    builder = importlib.import_module(cfg["builder"])
    t0 = time.monotonic()
    system = builder.build(cfg, cell, seed, devices)
    program = train_steps.checked_steps(system, cell)
    system.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    t1 = time.monotonic()
    steps = cell["check_steps"]
    reference = builder.reference(cfg, cell, seed, steps)
    out = {"seed": seed, "losses": program["losses"],
           "memory_peak_bytes_before_reference": peak,
           "program_s": t1 - t0, "reference_s": time.monotonic() - t1}
    out["program"], out["program_leaves"] = check.training_numbers(
        program, reference)
    if with_control:
        control = builder.reference(cfg, cell, seed, steps,
                                    precision=cfg["control_precision"])
        out["control"], _ = check.training_numbers(control, reference)
        rows = cell["batch"] * cell.get("dp", 1) // 2
        half = builder.reference(cfg, cell, seed, steps, rows_used=rows)
        out["half_batch"], _ = check.training_numbers(half, reference)
    return out


def serving_seeds(cfg, cell, seeds, devices, controls, seconds):
    """One build for all seeds: the engine takes its weights as arguments,
    so each seed only sets new ones. A short window at the cell's own load,
    then the program's reading and (first ``controls`` seeds) the
    control's, over the same sample of finished requests."""
    traffic = importlib.import_module("chipbench.traffic." + cell["kind"])
    first = run.Context(cfg, cell, seeds[0], seconds, False, devices)
    builder, system = serving.build_and_warm(first)
    for n, seed in enumerate(seeds):
        ctx = run.Context(cfg, cell, seed, seconds, False, devices)
        system.set_weights(seed)
        records, t0 = traffic.offer(ctx, system)
        drove = serving.finish(ctx, builder, system, records, t0)
        out = {"seed": seed, "attempted": drove["attempted"],
               "failed": drove["failed"], "end_to_end": drove["end_to_end"]}
        detail = {}
        out["program"] = dict(serving.serving_numbers(
            cfg, cell, seed, builder, drove["finished"], detail=detail),
            **detail)
        if n < controls:
            for name, kw in cfg["controls"].items():
                detail = {}
                out["control_" + name] = dict(serving.serving_numbers(
                    cfg, cell, seed, builder, drove["finished"], control=kw,
                    detail=detail), **detail)
        yield out
    system.close()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    entry, cfg, cell = run.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        sys.exit("calibrate reads the chip: jax came up on %s" % devices)
    os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(run.ROOT, "chiprun_out",
                        "calibrate_%s.jsonl" % args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    used = devices[:entry["chips"]]
    if cell["kind"] == "train_steps":
        outs = (training_seed(cfg, cell, seed, used, n < args.controls)
                for n, seed in enumerate(seeds))
    else:
        outs = serving_seeds(cfg, cell, seeds, used, args.controls,
                             args.seconds)
    for out in outs:
        line = json.dumps(out)
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
