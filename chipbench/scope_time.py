"""Device time under ANY scope the program wrote, by name (``scopes.py``
sorts instructions into its four layers only). An instruction lies under a
scope where one of the ``op_name``s the compiler gave it (joined with ``;``
where it merged instructions) has the scope as a path component."""
import functools
import statistics

from chipbench import scopes
from chipbench.trace_reduce import union_seconds


@functools.lru_cache(maxsize=1)
def paths_of(text):
    """``{instruction name: set of scope names it lies under}``."""
    return {name: {part for one in op.split(";")
                   for part in scopes.scope_path(one)}
            for name, op in scopes.op_names(text).items()}


def seconds_under(obs, names):
    """Device seconds of the traced window under any of ``names``: the
    UNION of the intervals of those instructions' events, averaged over
    the chips (an instruction inside a ``while`` or ``conditional`` runs
    inside its parent's event, so a sum over names would count it twice).
    ``None`` where there is no trace or no step text, or the program wrote
    none of the names anywhere (a commit from before the scopes)."""
    trace, text = obs.get("trace"), obs.get("step_text")
    if trace is None or not text:
        return None
    paths, names = paths_of(text), set(names)
    if not any(names & found for found in paths.values()):
        return None
    devices = trace.get("devices") or {"first": trace["events"]}
    return sum(union_seconds([(s, e) for name, s, e in events
                              if names & paths.get(name, set())])
               for events in devices.values()) / len(devices)


def steps_traced(obs, names):
    """Steps of the compiled program the trace holds, counted from the
    events of the instructions under ``names`` that run once a step (an
    instruction inside a loop of its own runs many times a step and is left
    out: those whose count is further than 1 from the (low) median)."""
    paths, names = paths_of(obs["step_text"]), set(names)
    counts = {}
    for name, _, _ in obs["trace"]["events"]:
        if names & paths.get(name, set()):
            counts[name] = counts.get(name, 0) + 1
    if not counts:
        return 0.0
    typical = statistics.median_low(counts.values())
    once = [c for c in counts.values() if abs(c - typical) <= 1]
    return sum(once) / len(once)


def routed_rows(obs):
    """``[[rows routed to each held expert] per expert layer]`` of the last
    step, from the program's own counter, fetched now; ``None`` where the
    program has no such counter or no expert layer is alive."""
    if obs["kind"] != "train":
        return None
    try:
        from mxnet_tpu.models import mla_moe
    except ImportError:
        return None
    snapshot = getattr(mla_moe, "routed_rows_snapshot", None)
    rows = snapshot() if snapshot else None
    return rows or None


def routed_rows_by_step(obs):
    """``routed_rows`` of every step of the WINDOW, in order, from the log
    the cell's builder keeps of the program's counter (routing moves as the
    model trains, so the last step does not stand for the others); ``None``
    where the builder keeps none."""
    if obs["kind"] != "train":
        return None
    import importlib
    builder = importlib.import_module(obs["cfg"]["builder"])
    log = getattr(builder, "routed_rows_by_step", lambda: None)()
    return log[-obs["steps"]:] if log else None


def routed_rows_traced(obs, names):
    """Mean rows routed to each expert layer, ``[rows per layer]``, over the
    steps the trace holds: the device runs the window's steps back to back,
    so the trace, opened ``trace_after_s`` into the window, starts in step
    ``trace_after_s / (window / steps)`` and holds ``steps_traced`` of them
    (to the nearest step: routing moves by about a hundredth a step)."""
    by_step = routed_rows_by_step(obs)
    if not by_step:
        return None
    first = int(obs["cell"]["trace_after_s"] * obs["steps"] / obs["window_s"])
    first = min(first, len(by_step) - 1)
    held = by_step[first:first + max(1, round(steps_traced(obs, names)))]
    return [sum(sum(step[layer]) for step in held) / len(held)
            for layer in range(len(held[0]))]
