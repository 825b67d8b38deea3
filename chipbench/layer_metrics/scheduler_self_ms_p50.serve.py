"""Median self time of the scheduler's ``generation.iteration`` spans in
the window: an iteration's duration less what its children cover (the
device calls ``generation.prefill`` / ``generation.step`` and
``generation.emit``), which is what the host did while the device waited."""
from chipbench.stats import percentile
from chipbench.trace_reduce import union_seconds

CHILDREN = ("generation.prefill", "generation.step", "generation.emit",
            "generation.spec_draft", "generation.spec_verify")


def read(obs):
    if obs["kind"] != "serve":
        return None
    inner = sorted((s, e) for n, s, e, _ in obs["spans"] if n in CHILDREN)
    selfs = []
    for name, start, end, _ in obs["spans"]:
        if name != "generation.iteration":
            continue
        covered = union_seconds([(max(s, start), min(e, end))
                                 for s, e in inner if e > start and s < end])
        selfs.append(1e3 * (end - start - covered))
    return percentile(selfs, 50) if selfs else None
