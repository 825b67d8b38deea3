"""From an ``.xplane.pb`` to device-op intervals by name, with JAX alone.

A traced window is bracketed by two host ``TraceAnnotation`` marks
(``MARK_START``, ``MARK_STOP``); the benchmark records ``time.monotonic()``
beside the first, which puts the program's host spans (same clock) and the
device events on one time line. Device events are those of the line
``XLA Ops`` of each ``/device:TPU:<n>`` plane.
"""
import glob
import os
import shutil

MARK_START = "chipbench_trace_start"
MARK_STOP = "chipbench_trace_stop"
OPS_LINE = "XLA Ops"


def union_seconds(intervals):
    """Total length of the union of ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def idle_gaps(intervals, window):
    """``[(start, end)]`` of the gaps the union of ``intervals`` leaves in
    ``window``, longest first."""
    lo, hi = window
    gaps, reach = [], lo
    for start, end in sorted(intervals):
        if start > reach:
            gaps.append((reach, min(start, hi)))
        reach = max(reach, end)
    if reach < hi:
        gaps.append((reach, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def clip(events, window):
    """Events ``(name, start, end)`` cut to ``window``; those outside go."""
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def short_name(text):
    """An event of the ``XLA Ops`` line is named by its whole HLO line,
    ``%fusion.12 = (shapes) fusion(operands)``: keep ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_custom_call(text):
    """A Mosaic (Pallas) kernel is an HLO ``custom-call`` to this target;
    the compiler's own small custom calls have other targets."""
    return 'custom_call_target="tpu_custom_call"' in text


def load(path):
    """``{"marks": {name: start_s}, "devices": {plane: [(name, start_s,
    end_s)]}, "custom_calls": [name]}`` in the trace's own clock, seconds;
    names as :func:`short_name` gives them."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    marks, devices, custom = {}, {}, set()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                events = []
                for e in line.events:
                    name = short_name(e.name)
                    if is_custom_call(e.name):
                        custom.add(name)
                    events.append((name, e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9))
                devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in (MARK_START, MARK_STOP):
                        marks[e.name] = e.start_ns * 1e-9
    return {"marks": marks, "devices": devices, "custom_calls": sorted(custom)}


def reduce(raw, chips):
    """The traced window's reduction: ``window_s``, ``busy_s`` (union of
    device-op intervals, averaged over the ``chips`` busiest devices),
    ``by_name`` (seconds per op name, same average), ``events`` and
    ``gaps`` of the first device, ``custom_calls`` (the names that are
    Mosaic kernels), all inside the marks."""
    window = (raw["marks"][MARK_START], raw["marks"][MARK_STOP])
    per_device = {name: clip(evs, window)
                  for name, evs in raw["devices"].items()}
    used = sorted(per_device, key=lambda n: -union_seconds(
        [(s, e) for _, s, e in per_device[n]]))[:chips]
    if not used:
        raise RuntimeError("the trace holds no device plane with an %r line"
                           % OPS_LINE)
    by_name = {}
    for name in used:
        for op, s, e in per_device[name]:
            by_name[op] = by_name.get(op, 0.0) + (e - s) / len(used)
    busy = sum(union_seconds([(s, e) for _, s, e in per_device[n]])
               for n in used) / len(used)
    first = per_device[sorted(used)[0]]
    return {"window": window, "window_s": window[1] - window[0],
            "busy_s": busy, "by_name": by_name, "events": first,
            "custom_calls": set(raw.get("custom_calls", ())),
            "devices": {n: per_device[n] for n in used},
            "gaps": idle_gaps([(s, e) for _, s, e in first], window)}


def open_span_at(spans, t):
    """Name of the innermost program span (``(name, start, end)`` on the
    host's monotonic clock) open at time ``t``, or ``"none"``."""
    inside = [(end - start, name) for name, start, end in spans
              if start <= t < end]
    return min(inside)[1] if inside else "none"


class TraceWindow:
    """Starts and stops the JAX profiler around part of the window, in a
    directory inside the checkout that is emptied first."""

    def __init__(self, directory):
        self.directory = directory
        self.mark_monotonic = None
        self.running = False

    def start(self):
        import time
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.running = True
        self.mark_monotonic = time.monotonic()
        with jax.profiler.TraceAnnotation(MARK_START):
            pass
        return self.mark_monotonic

    def stop(self):
        import jax
        if not self.running:
            return
        with jax.profiler.TraceAnnotation(MARK_STOP):
            pass
        jax.profiler.stop_trace()
        self.running = False

    def reduction(self, chips):
        """Reduce the recorded trace and shift it onto the host's monotonic
        clock; the trace files are deleted afterwards."""
        found = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("no trace was written under %s" % self.directory)
        raw = load(found[0])
        red = reduce(raw, chips)
        red["to_monotonic"] = self.mark_monotonic - raw["marks"][MARK_START]
        shutil.rmtree(self.directory, ignore_errors=True)
        return red
