"""Traffic kind ``train_steps``: a user's training loop on one compiled
step. Parameters (the cell's workload file): ``batch`` (per chip), ``seq``,
``picked``, ``pool`` (host batches made from the seed), ``dp``,
``check_steps`` (followed by the reference), ``warm_steps``, ``loss_lag``
(the loop reads the loss of the step that many behind, as a loop that logs
does, so the host never runs further ahead), ``trace_after_s`` and
``trace_seconds``.
"""
import gc
import importlib
import time
from collections import deque

from chipbench import check


def checked_steps(system, cell):
    """Drive ``system`` through its first ``check_steps`` steps, by the
    window's own call and feed, and read what the reference is compared
    with: each loss, the first gradient (norms and leaves, from the
    optimizer's state after one step), the parameters' change."""
    program = {"losses": []}
    system.snapshot_start()
    for i in range(cell["check_steps"]):
        program["losses"].append(float(system.step(i).asnumpy()))
        if i == 0:
            program["grad_norms"] = system.first_gradient_norms()
            program["first_gradient"] = system.first_gradient()
    program["change_norms"] = system.change_norms()
    return program


def drive(ctx):
    cfg, cell = ctx.cfg, ctx.cell
    builder = importlib.import_module(cfg["builder"])
    system = builder.build(cfg, cell, ctx.seed, ctx.devices)
    ctx.log("built", seconds=round(ctx.since_start(), 2))

    program = checked_steps(system, cell)
    step_text = None
    if ctx.trace:
        compiled = system.compiled_step()
        step_text = compiled.as_text()
        # the step's temporaries live in memory the runtime reserves for
        # programs, which ``peak_bytes_in_use`` does not count: say them
        memory = compiled.memory_analysis()
        ctx.log("step program", argument_bytes=memory.argument_size_in_bytes,
                temp_bytes=memory.temp_size_in_bytes,
                alias_bytes=memory.alias_size_in_bytes)
    i = cell["check_steps"]
    for _ in range(cell["warm_steps"]):
        last = system.step(i)
        i += 1
    float(last.asnumpy())
    ctx.log("warm", losses=program["losses"])

    pending, lag = deque(), cell["loss_lag"]
    steps, trace_at, trace_until = 0, None, None
    ctx.open_window()
    t0 = time.monotonic()
    while True:
        now = time.monotonic() - t0
        if now >= ctx.seconds:
            break
        if ctx.trace and trace_at is None and now >= cell["trace_after_s"]:
            trace_at = ctx.tracer.start()
            trace_until = now + cell["trace_seconds"]
        if trace_until is not None and now >= trace_until:
            float(pending[-1].asnumpy())
            ctx.tracer.stop()
            trace_until = None
        pending.append(system.step(i))
        i += 1
        steps += 1
        if len(pending) > lag:
            float(pending.popleft().asnumpy())
    final = [float(p.asnumpy()) for p in pending][-1]
    window_s = time.monotonic() - t0
    if trace_until is not None:
        ctx.tracer.stop()
    ctx.window = [t0, t0 + window_s]

    tokens = steps * system.tokens_per_step
    obs = {"kind": "train", "steps": steps, "tokens": tokens,
           "window_s": window_s, "final_loss": final,
           "batch": system.batch, "seq": system.seq, "picked": system.picked,
           "step_text": step_text, "chips": cell.get("dp", 1)}

    def verify():
        system.close()
        gc.collect()
        reference = builder.reference(cfg, cell, ctx.seed, cell["check_steps"])
        numbers, notes = check.training_numbers(program, reference)
        ctx.log("compared against the reference", **notes)
        return numbers

    return {"attempted": steps, "failed": 0,
            "end_to_end": {"train_tokens_per_s": tokens / window_s},
            "observations": obs, "verify": verify}
