"""``calibrate.py`` for the cells of ``configs/mla_moe``: the program's
reading over many seeds, and over the first few the control (the reference
in the configuration's ``control_precision``) and the two faults this
configuration plants in the reference, each of which must come out not
correct: ``expert_dropped`` (one held expert's assignments left out) and
``no_rope_on_shared_key`` (the rotary embedding left off the shared key).
One process, one JSON line per seed on stdout and in
``chiprun_out/calibrate_<workload>.jsonl``.

    python -m chipbench.calibrate_mla_moe --workload <name> --seeds 11,12 \
        --controls 1
"""
import argparse
import importlib
import json
import os
import sys
import time

from chipbench import check, run
from chipbench.configs import mla_moe_ref
from chipbench.traffic import train_steps


def one_seed(cfg, cell, seed, devices, with_controls):
    builder = importlib.import_module(cfg["builder"])
    t0 = time.monotonic()
    system = builder.build(cfg, cell, seed, devices)
    program = train_steps.checked_steps(system, cell)
    rows = system.routed_rows()
    system.close()
    t1 = time.monotonic()
    steps = cell["check_steps"]
    reference = builder.reference(cfg, cell, seed, steps)
    out = {"seed": seed, "losses": program["losses"],
           "reference_losses": reference["losses"], "routed_rows": rows,
           "program_s": t1 - t0, "reference_s": time.monotonic() - t1}
    out["program"], out["program_leaves"] = check.training_numbers(
        program, reference)
    if with_controls:
        planted = {"control": {"precision": cfg["control_precision"]}}
        planted.update({f: {"fault": f} for f in mla_moe_ref.FAULTS})
        for name, kw in planted.items():
            broken = builder.reference(cfg, cell, seed, steps, **kw)
            out[name], out[name + "_leaves"] = check.training_numbers(
                broken, reference)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=1)
    args = ap.parse_args()
    entry, cfg, cell = run.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < entry["chips"]:
        sys.exit("calibrate reads the chip: jax came up on %s" % devices)
    os.makedirs(os.path.join(run.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(run.ROOT, "chiprun_out",
                        "calibrate_%s.jsonl" % args.workload)
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = json.dumps(one_seed(cfg, cell, seed, devices[:entry["chips"]],
                                   n < args.controls))
        print(line, flush=True)
        with open(path, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
