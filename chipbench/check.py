"""The comparison that decides ``correct``.

Each number compared has a limit of its own, kept in the cell's workload
file under ``limits`` with the readings it was set from in ``PERF.md``. A
number reads ``[value, limit]``; the run is correct when every value is at
most its limit (and finite).
"""
import math
import statistics

# leaves whose first gradient is nought to rounding in the reference move
# under Adam by round-off alone: left out of the change by this rule
DEAD_LEAF_SHARE = 1e-3


def worst_leaf_gap(program, reference, leaves=None):
    """Largest gap between the program's norm and the reference's over the
    leaves, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns ``(gap, leaf)``."""
    leaves = sorted(reference if leaves is None else leaves)
    floor = statistics.median(reference[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(program[k] - reference[k]) / max(reference[k], floor)
        if not gap <= worst:            # also catches nan
            worst, where = gap, k
    return worst, where


def leaf_differences(program, reference):
    """``{leaf: norm of (program's leaf - reference's leaf)}`` for two dicts
    of device arrays, in float32, in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32)))) for k in b}

    return {k: float(v) for k, v in
            jax.device_get(norms(program, reference)).items()}


def training_numbers(program, reference):
    """``{name: value}`` for a training cell: each step's loss gap, the
    worst leaf's gap in the norm of the first gradient, the worst leaf's gap
    in the norm of the parameters' change (dead leaves left out), and the
    median leaf's norm of the DIFFERENCE of the first gradients over the
    reference's norm (what a lower precision moves; a gap of norms is blind
    to noise that leaves the norm alone)."""
    out, notes = {}, {}
    if "first_gradient" in program:
        diff = leaf_differences(program["first_gradient"],
                                reference["first_gradient"])
        ref = reference["grad_norms"]
        floor = statistics.median(ref.values())
        shares = {k: diff[k] / max(ref[k], floor) for k in diff}
        out["grad_diff_median"] = statistics.median(shares.values())
        out["grad_diff_worst"] = max(shares.values())
        notes["grad_diff_leaf"] = max(shares, key=shares.get)
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]), 1):
        out["loss%d_gap" % i] = abs(a - b) / abs(b)
    out["grad_norm_gap"], notes["grad_norm_leaf"] = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    g = reference["grad_norms"]
    alive = [k for k in g if g[k] >= DEAD_LEAF_SHARE * statistics.median(g.values())]
    out["change_norm_gap"], notes["change_norm_leaf"] = worst_leaf_gap(
        program["change_norms"], reference["change_norms"], alive)
    return out, notes


def judge(numbers, limits, not_compared=()):
    """``(correct, {name: [value, limit]})``. A number without a limit in
    the cell's file is an error of the cell, not a pass, unless the file
    names it under ``not_compared`` (PERF.md says why, with its readings)."""
    compared, correct = {}, True
    for name, value in numbers.items():
        if name in not_compared:
            continue
        if name not in limits:
            raise KeyError("the cell's file gives no limit for %r" % name)
        limit = limits[name]
        compared[name] = [value, limit]
        if not (math.isfinite(value) and value <= limit):
            correct = False
    return correct, compared
