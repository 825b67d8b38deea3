"""``calibrate_mla_moe.py`` for any ``train_steps`` configuration: the
faults are those the module under the configuration's ``reference`` key
plants (``FAULTS``), so a new configuration needs no copy of this file.
The program's reading over many seeds, and over the first few the control
(the reference in the configuration's ``control_precision``) and each
planted fault, every one of which must come out not correct: with its
numbers each reading carries the verdict of ``check.judge`` under the
limits in the cell's file (``<name>_correct``, and ``<name>_over``: the
numbers over their limit). The sibling's ``main`` does the rest: one
process, one JSON line per seed on stdout and in
``chiprun_out/calibrate_<workload>.jsonl``.

    python -m chipbench.calibrate_eva --workload <name> --seeds 11,12 \
        --controls 1
"""
import gc
import importlib
import time

from chipbench import calibrate_mla_moe, check
from chipbench.traffic import train_steps


def one_seed(cfg, cell, seed, devices, with_controls):
    builder = importlib.import_module(cfg["builder"])
    faults = importlib.import_module(cfg["reference"]).FAULTS
    t0 = time.monotonic()
    system = builder.build(cfg, cell, seed, devices)
    program = train_steps.checked_steps(system, cell)
    system.close()
    del system
    gc.collect()    # the model and its trainer are cycles: 3.3 GB a seed
    t1 = time.monotonic()
    steps = cell["check_steps"]
    reference = builder.reference(cfg, cell, seed, steps)
    out = {"seed": seed, "losses": program["losses"],
           "reference_losses": reference["losses"],
           "program_s": t1 - t0, "reference_s": time.monotonic() - t1}

    def read(name, run):
        out[name], out[name + "_leaves"] = check.training_numbers(
            run, reference)
        out[name + "_correct"], compared = check.judge(
            out[name], cell["limits"], cell.get("not_compared", ()))
        out[name + "_over"] = sorted(
            k for k, (value, limit) in compared.items() if not value <= limit)

    read("program", program)
    if with_controls:
        planted = {"control": {"precision": cfg["control_precision"]}}
        planted.update({f: {"fault": f} for f in faults})
        for name, kw in planted.items():
            read(name, builder.reference(cfg, cell, seed, steps, **kw))
    return out


def main():
    calibrate_mla_moe.one_seed = one_seed   # its ``main`` looks it up there
    calibrate_mla_moe.main()


if __name__ == "__main__":
    main()
