"""Traffic kind ``open_loop``: independent users. Requests are due on a
schedule that is a function of the seed and the cell's parameters alone and
are sent whether or not earlier ones have finished; each is timed from when
it was due. Parameters: ``rate_per_s``, ``prompt`` and ``output``
(``{"median", "sigma", "min", "max"}`` of a clipped lognormal),
``warm_prompt_lengths``, ``check_requests``, ``trace_after_s``,
``trace_seconds``.

Every seed gets the same set of gaps and lengths (the quantiles of their
distributions, one a request), each shuffled as a whole by the seed: the
window's offered work is the same for every seed, and the process is
stationary (any order of the set is as likely as any other, so there is no
trend and short gaps do bunch). It is a permutation of exponential gaps, not
independent draws: the count of arrivals in the window is fixed where a
Poisson process's would vary. A cell's ``why`` says so.
"""
import math
import statistics
import time

import numpy as np

from chipbench import serving


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def lognormal_grid(spec, n):
    """``n`` lengths: the quantiles of a lognormal of this median and sigma,
    clipped to ``min``..``max``."""
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf(q) for q in _quantiles(n)])
    lengths = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(int)


def schedule(cell, seed, seconds):
    """``[(due_s, prompt_len, new_tokens)]`` for the window, sorted by due
    time: ``round(rate * seconds)`` requests, the first due at 0."""
    n = max(1, round(cell["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(-np.log(1.0 - _quantiles(n)) / cell["rate_per_s"])
    prompts = rng.permutation(lognormal_grid(cell["prompt"], n))
    outputs = rng.permutation(lognormal_grid(cell["output"], n))
    due = np.cumsum(gaps) - gaps[0]
    return [(float(d), int(p), int(o)) for d, p, o in zip(due, prompts, outputs)]


def offer(ctx, system):
    """Send the window's schedule to ``system`` on time; returns the
    records and the time the window opened."""
    cfg, cell = ctx.cfg, ctx.cell
    plan = schedule(cell, ctx.seed, ctx.seconds)
    rng = np.random.default_rng(ctx.seed)
    prompts = [serving.random_prompt(rng, cfg["vocab_size"], p)
               for _, p, _ in plan]
    ctx.open_window()
    t0 = time.monotonic()
    wait_trace = serving.start_trace_timer(ctx, t0)
    records, late = [], []
    for (due, _, new_tokens), prompt in zip(plan, prompts):
        delay = t0 + due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        late.append(time.monotonic() - (t0 + due))
        rec = serving.Record(prompt, new_tokens, t0 + due)
        serving.send(system, rec)
        records.append(rec)
    delay = t0 + ctx.seconds - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    wait_trace()
    ctx.log("generator", sent=len(records),
            late_ms_max=round(1e3 * max(late), 3))
    return records, t0


def drive(ctx):
    builder, system = serving.build_and_warm(ctx)
    records, t0 = offer(ctx, system)
    return serving.finish(ctx, builder, system, records, t0)
