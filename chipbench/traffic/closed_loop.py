"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
request when the last one has ended, for the length of the window; a request
open at the close is waited for. Parameters: ``clients``, ``prompt`` and
``output`` (``{"min", "max"}``, uniform), ``grid`` (how many requests the
seed's list holds), ``warm_prompt_lengths``, ``check_requests``,
``trace_after_s``, ``trace_seconds``.

Every seed gets the same list of lengths (an even grid), shuffled as a whole
by the seed; the clients take from it in turn.
"""
import itertools
import threading
import time

import numpy as np

from chipbench import serving


def request_list(cell, seed):
    """``[(prompt_len, new_tokens)]``: even grids over the two ranges,
    shuffled apart by the seed."""
    n = cell["grid"]
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(np.rint(np.linspace(
        cell["prompt"]["min"], cell["prompt"]["max"], n)).astype(int))
    outputs = rng.permutation(np.rint(np.linspace(
        cell["output"]["min"], cell["output"]["max"], n)).astype(int))
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


def offer(ctx, system):
    """Run the callers against ``system`` for the window; returns the
    records and the time the window opened."""
    cfg, cell = ctx.cfg, ctx.cell
    todo = request_list(cell, ctx.seed)
    rng = np.random.default_rng(ctx.seed)
    prompts = [serving.random_prompt(rng, cfg["vocab_size"], p)
               for p, _ in todo]
    turn = itertools.count()
    records, lock = [], threading.Lock()
    ctx.open_window()
    t0 = time.monotonic()
    t1 = t0 + ctx.seconds
    wait_trace = serving.start_trace_timer(ctx, t0)

    def caller():
        while time.monotonic() < t1:
            i = next(turn)
            if i >= len(todo):
                return
            rec = serving.Record(prompts[i], todo[i][1], time.monotonic())
            with lock:
                records.append(rec)
            serving.send(system, rec, inline=True)

    callers = [threading.Thread(target=caller, daemon=True)
               for _ in range(cell["clients"])]
    for c in callers:
        c.start()
    for c in callers:
        c.join(ctx.seconds + 2 * serving.DRAIN_S)
    wait_trace()
    return records, t0


def drive(ctx):
    builder, system = serving.build_and_warm(ctx)
    records, t0 = offer(ctx, system)
    return serving.finish(ctx, builder, system, records, t0)
