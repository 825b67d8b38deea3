"""Share of the device's busy time in collective instructions (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and their
``-start`` / ``-done`` halves) while no other instruction runs on the same
chip: the part of the exchange the step does not hide. Averaged over the
chips of the cell; ``None`` on one chip."""
import re

from chipbench.trace_reduce import union_seconds

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


def _overlap(a, b):
    """Length of the intersection of two unions of intervals."""
    return union_seconds(a) + union_seconds(b) - union_seconds(a + b)


def read(obs):
    trace = obs["trace"]
    if obs["kind"] != "train" or trace is None or obs["chips"] < 2:
        return None
    exposed = busy = 0.0
    for events in trace["devices"].values():
        ours = [(s, e) for n, s, e in events if _COLLECTIVE.match(n)]
        rest = [(s, e) for n, s, e in events if not _COLLECTIVE.match(n)]
        exposed += union_seconds(ours) - _overlap(ours, rest)
        busy += union_seconds(ours + rest)
    return 100.0 * exposed / busy if busy else None
