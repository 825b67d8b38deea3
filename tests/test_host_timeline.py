"""The host's side of a training run under spans (PR 35): jax's own trace /
lower / compile / cache-load durations bridged onto the tracer's clock
(``pcache.py``), the trainer's build / place / launch, ``block.initialize``
and ``ndarray.wait`` spans, self time in ``phase_stats()``, the benchmark's
stall and set-up readers (``chipbench/host_timeline.py``) as pure functions
of a span list and end to end through ``run_cell(..., rehearsal=True)``
with a planted slow step, and ``tools/trace_summary.py``'s set-up block.
"""
import importlib.util
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, parallel, pcache
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.observability import export as obs_export
from mxnet_tpu.observability import tracer as tr
from mxnet_tpu.resilience import chaos

from chipbench import host_timeline, run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("trace_s", "lower_s", "compile_s", "load_s")


@pytest.fixture(autouse=True)
def _clean():
    """Every test starts and ends with a disabled, empty tracer, no phase
    aggregate and no armed chaos rule."""
    def reset():
        tr.tracer.disable()
        tr.tracer.clear()
        tr.tracer.reset_phase_stats()
        tr.tracer.set_capacity(tr.DEFAULT_BUFFER)
        chaos.clear()
    reset()
    yield
    reset()


def _spans(name=None):
    """Recorded "X" events as dicts, oldest first."""
    out = []
    for ph, n, ts, dur, _tid, _tn, sid, parent, _trace, args in tr.events():
        if ph == "X" and (name is None or n == name):
            out.append({"name": n, "t0": ts, "t1": ts + dur, "id": sid,
                        "parent": parent, "args": args or {}})
    return out


def _fresh_jit(scale):
    """A jitted function nothing has compiled yet (``scale`` is baked into
    a new closure, so each test's program is its own)."""
    def host_timeline_probe(x):
        return jnp.tanh(x * scale).sum()
    return jax.jit(host_timeline_probe)


def _deltas(before):
    after = pcache.stats()
    return {k: after[k] - before[k] for k in PHASES}


# ---- A. the bridge ----------------------------------------------------------

def test_bridge_puts_jax_phases_inside_the_open_span():
    tr.enable()
    fn = _fresh_jit(1.25)
    before = pcache.stats()
    with tr.span("outer") as outer:
        fn(jnp.ones(7)).block_until_ready()
    moved = _deltas(before)
    outer_ev = _spans("outer")[0]
    mine = [e for e in _spans() if e["args"].get("fun")
            == "host_timeline_probe"]
    assert {e["name"] for e in mine} == {"jax.trace", "jax.lower",
                                         "jax.compile"}
    for e in mine:
        assert e["parent"] == outer.ctx.span_id
        assert outer_ev["t0"] <= e["t0"] <= e["t1"] <= outer_ev["t1"] + 1e-6
    for key, name in (("trace_s", "jax.trace"), ("lower_s", "jax.lower"),
                      ("compile_s", "jax.compile")):
        top = sum(e["t1"] - e["t0"] for e in _spans(name)
                  if not e["args"].get("nested"))
        assert moved[key] > 0.0
        assert top == pytest.approx(moved[key], rel=1e-6, abs=1e-9)
    row = pcache.programs()["host_timeline_probe"]
    assert row["count"] == 1 and row["compile_s"] > 0.0
    assert row["trace_s"] <= moved["trace_s"] + 1e-9


def test_nested_trace_adds_to_programs_and_not_twice_to_trace_s():
    tr.enable()

    @jax.jit
    def host_timeline_inner(x):
        return jnp.sin(x) * 3.5

    def host_timeline_outer(x):
        return host_timeline_inner(x) + host_timeline_inner(x + 1)

    before = pcache.stats()
    jax.jit(host_timeline_outer)(jnp.ones(5)).block_until_ready()
    moved = _deltas(before)
    progs = pcache.programs()
    inner, outer = progs["host_timeline_inner"], progs["host_timeline_outer"]
    assert inner["trace_s"] > 0.0 and inner["count"] == 2
    assert inner["lower_s"] == inner["compile_s"] == 0.0    # never alone
    # the flat counter is the union: every top-level trace, the outer's
    # among them, and not the inner's again
    tops = sum(e["t1"] - e["t0"] for e in _spans("jax.trace")
               if not e["args"].get("nested"))
    assert moved["trace_s"] == pytest.approx(tops, rel=1e-6)
    every = sum(row["trace_s"] for row in progs.values())
    assert every >= moved["trace_s"] + inner["trace_s"] - 1e-9
    assert outer["trace_s"] >= inner["trace_s"]
    nested = [e for e in _spans("jax.trace") if e["args"].get("nested")]
    assert all(e["args"]["fun"] for e in nested)
    # nested events carry no self time: the aggregate stays the union
    assert tr.phase_stats()["jax.trace"]["self_ms"] == pytest.approx(
        tops * 1e3, rel=1e-6)


def test_bridge_counts_with_the_tracer_off_and_leaves_the_ring_empty():
    assert not tr.enabled()
    before = pcache.stats()
    _fresh_jit(2.5)(jnp.ones(3)).block_until_ready()
    moved = _deltas(before)
    assert moved["trace_s"] > 0 and moved["lower_s"] > 0
    assert moved["compile_s"] > 0 and moved["load_s"] == 0.0
    assert tr.event_count() == 0 and tr.phase_stats() == {}


def test_a_steady_call_moves_no_counter():
    fn = _fresh_jit(4.5)
    x = jnp.ones(3)
    fn(x).block_until_ready()
    before = pcache.stats()
    for _ in range(3):
        fn(x).block_until_ready()
    assert _deltas(before) == dict.fromkeys(PHASES, 0.0)


def test_cache_load_is_counted_inside_the_compile(tmp_path):
    """A persistent-cache hit: ``load_s`` moves, lies inside ``compile_s``,
    and lands on the program's row and on the time line as ``pcache.load``."""
    from jax.experimental.compilation_cache import compilation_cache
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    pcache.init(cache_dir=str(tmp_path / "cache"), min_compile_secs=0,
                min_entry_bytes=0, force=True)
    try:
        def host_timeline_cached(x):
            return jnp.cos(x * 1.75).sum()
        jax.jit(host_timeline_cached)(jnp.ones(9)).block_until_ready()
        jax.clear_caches()      # the in-memory executable goes, disk stays
        tr.enable()
        before = pcache.stats()
        jax.jit(host_timeline_cached)(jnp.ones(9)).block_until_ready()
        after = pcache.stats()
        assert after["disk_hits"] > before["disk_hits"]
        loaded = after["load_s"] - before["load_s"]
        assert 0.0 < loaded <= after["compile_s"] - before["compile_s"]
        assert pcache.programs()["host_timeline_cached"]["load_s"] > 0.0
        loads = [e for e in _spans("pcache.load")
                 if e["args"]["fun"] == "host_timeline_cached"]
        assert len(loads) == 1 and loads[0]["args"]["nested"]
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_enable_compilation_cache", was[1])
        compilation_cache.reset_cache()
        pcache._state["enabled"] = bool(was[0]) and bool(was[1])
        pcache._state["dir"] = was[0]


def test_stats_stay_flat_numbers_and_reset_clears_the_new_keys():
    _fresh_jit(6.5)(jnp.ones(3)).block_until_ready()
    stats = pcache.stats()
    for key in PHASES:
        assert isinstance(stats[key], float)
    for key, value in stats.items():
        if key not in ("enabled", "dir"):
            assert isinstance(value, (int, float)), key
    assert pcache.programs()
    pcache.reset_stats()
    stats = pcache.stats()
    assert [stats[k] for k in PHASES] == [0.0] * 4
    assert isinstance(stats["disk_hits"], int) and stats["disk_hits"] == 0
    assert pcache.programs() == {}
    # the profiler's rows and the Prometheus page still read it
    assert "cachedop.pcache.hits" in pcache._rows()
    from mxnet_tpu.observability import export_prom
    assert "mxtpu_pcache_requests_total" in export_prom.render_process()


# ---- B. the trainer's and the array's boundaries ----------------------------

def _tiny_trainer():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=16),
                nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 0.05}, mesh=parallel.make_mesh())


def _batch():
    rng = np.random.RandomState(0)
    return (nd.array(rng.standard_normal((32, 16)).astype("float32")),
            nd.array(rng.randint(0, 4, 32).astype("float32")))


def test_trainer_build_place_launch_are_children_of_what_is_open():
    tr.enable()
    with tr.span("setup") as setup:
        trainer = _tiny_trainer()
    build = _spans("trainer.build")
    assert len(build) == 1 and build[0]["parent"] == setup.ctx.span_id
    params = 4
    assert build[0]["args"]["params"] == params
    # float32 values + Adam's two moments of each
    sizes = 16 * 32 + 32 + 32 * 4 + 4
    assert build[0]["args"]["bytes"] == 3 * 4 * sizes
    assert build[0]["args"]["devices"] == trainer.mesh.devices.size
    init = _spans("block.initialize")
    assert len(init) == 1 and init[0]["args"]["params"] == params
    assert init[0]["parent"] == setup.ctx.span_id

    x, y = _batch()
    for _ in range(3):
        trainer.step(x, y)
    steps = _spans("trainer.step")
    places, launches = _spans("trainer.place"), _spans("trainer.launch")
    assert len(steps) == len(places) == len(launches) == 3
    for step, place, launch in zip(steps, places, launches):
        assert place["parent"] == launch["parent"] == step["id"]
        assert step["t0"] <= place["t0"] <= place["t1"] <= launch["t0"]
        assert launch["t1"] <= step["t1"]
        assert place["args"]["bytes"] == 32 * 16 * 4 + 32 * 4
    # ``first`` on the compiling call only, and the step's own phases
    # landed inside that launch
    assert [s["args"]["first"] for s in launches] == [True, False, False]
    assert [s["args"]["t"] for s in steps] == [1, 2, 3]
    step_phases = [e for e in _spans() if e["args"].get("fun") == "step"]
    assert {e["name"] for e in step_phases} >= {"jax.trace", "jax.lower",
                                                "jax.compile"}
    assert all(e["parent"] == launches[0]["id"] for e in step_phases)


def test_step_many_and_step_stream_record_place_and_launch():
    tr.enable()
    trainer = _tiny_trainer()
    rng = np.random.RandomState(1)
    xs = rng.standard_normal((2, 32, 16)).astype("float32")
    ys = rng.randint(0, 4, (2, 32)).astype("float32")
    trainer.step_many(nd.array(xs), nd.array(ys))
    many = _spans("trainer.step_many")[0]
    assert _spans("trainer.place")[0]["parent"] == many["id"]
    assert _spans("trainer.launch")[0]["parent"] == many["id"]
    assert _spans("trainer.launch")[0]["args"]["first"] is True
    tr.clear()
    trainer.step_stream([(xs[0], ys[0]), (xs[1], ys[1])], chunk=2)
    chunk = _spans("trainer.chunk")[0]
    assert _spans("trainer.place")[0]["parent"] == chunk["id"]
    launch = _spans("trainer.launch")[0]
    assert launch["parent"] == chunk["id"]
    assert launch["args"]["first"] is False     # the span program is cached


def test_trainer_sites_record_nothing_with_the_tracer_off():
    trainer = _tiny_trainer()
    x, y = _batch()
    float(trainer.step(x, y).asnumpy())
    nd.waitall()
    assert tr.event_count() == 0 and tr.phase_stats() == {}


def test_block_initialize_is_one_span_for_the_outermost_call():
    class Pair(gluon.Block):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.a = nn.Dense(3, in_units=2)
                self.b = nn.Dense(3, in_units=2)

        def initialize(self, *args, **kw):  # children one by one
            self.a.initialize(*args, **kw)
            self.b.initialize(*args, **kw)
            super().initialize(*args, force_reinit=True, **kw)

    tr.enable()
    Pair().initialize()
    assert len(_spans("block.initialize")) == 3     # no outer call open
    tr.clear()

    class Outer(gluon.Block):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.pair = nn.Dense(3, in_units=2)

        def initialize(self, *args, **kw):
            from mxnet_tpu.gluon import block as block_mod
            block_mod._initializing.on = True   # as an enclosing call does
            try:
                super().initialize(*args, **kw)
            finally:
                block_mod._initializing.on = False

    Outer().initialize()
    assert _spans("block.initialize") == []


def _slow_value():
    """A device value the host has to wait for."""
    a = jnp.ones((1500, 1500), jnp.float32)
    for _ in range(8):
        a = jnp.tanh(a @ a * 1e-3)
    return a


@pytest.mark.parametrize("how", ["asnumpy", "wait_to_read", "asscalar"])
def test_ndarray_wait_records_a_value_that_is_not_ready(how):
    tr.enable()
    _slow_value().block_until_ready()       # compile outside the reading
    for _ in range(5):
        tr.clear()
        value = _slow_value()
        arr = NDArray(value.sum() if how == "asscalar" else value)
        getattr(arr, how)()
        waits = _spans("ndarray.wait")
        if waits:
            break
    assert len(waits) == 1
    assert waits[0]["args"]["bytes"] == arr._data.nbytes
    assert waits[0]["t1"] - waits[0]["t0"] > 0.0


def test_ndarray_wait_records_nothing_for_a_ready_value_or_a_tracer():
    tr.enable()
    x = nd.array(np.arange(1, dtype="float32"))
    x.wait_to_read()
    tr.clear()
    for _ in range(3):
        x.asnumpy()
        x.wait_to_read()
        float(x)
    assert _spans("ndarray.wait") == []

    seen = []

    @jax.jit
    def traced(v):
        arr = NDArray(v)
        arr.wait_to_read()                  # a tracer handle: no wait
        seen.append(arr)
        return v + 1

    traced(jnp.ones(3)).block_until_ready()
    assert seen and _spans("ndarray.wait") == []


def test_waitall_is_a_wait_point():
    tr.enable()
    nd.waitall()
    for e in _spans("ndarray.wait"):
        assert e["args"]["bytes"] == 4
    tr.disable()
    tr.clear()
    nd.waitall()
    assert tr.event_count() == 0


# ---- C. self time -----------------------------------------------------------

def test_self_ms_of_a_parent_with_two_children():
    tr.enable()
    with tr.span("parent"):
        with tr.span("child"):
            for _ in range(2000):
                pass
        with tr.span("child"):
            with tr.span("grandchild"):
                for _ in range(2000):
                    pass
    stats = tr.phase_stats()
    parent, child, grand = (stats[k] for k in ("parent", "child",
                                               "grandchild"))
    assert child["count"] == 2
    assert parent["self_ms"] == pytest.approx(
        parent["total_ms"] - child["total_ms"], abs=1e-6)
    assert child["self_ms"] == pytest.approx(
        child["total_ms"] - grand["total_ms"], abs=1e-6)
    assert grand["self_ms"] == pytest.approx(grand["total_ms"], abs=1e-9)
    # disjoint by construction: the self times add up to the root's span
    assert sum(st["self_ms"] for st in stats.values()) == pytest.approx(
        parent["total_ms"], abs=1e-6)
    for st in stats.values():       # the keys export_prom reads are there
        assert {"count", "total_ms", "mean_ms", "max_ms",
                "buckets_ms"} <= set(st)


def test_self_ms_of_a_completed_child_and_a_nested_one():
    tr.enable()
    with tr.span("parent") as parent:
        time.sleep(0.012)
        t = tr.now()
        tr.complete("done", t - 0.010, t, parent=tr.current())
        tr.complete("done.nested", t - 0.004, t, parent=tr.current(),
                    nested=True)
        # a completed event of another thread's parent charges nobody here
        tr.complete("elsewhere", t - 0.5, t,
                    parent=tr.SpanContext(999, 999))
    stats = tr.phase_stats()
    assert stats["done"]["self_ms"] == pytest.approx(10.0)
    assert stats["done.nested"]["self_ms"] == 0.0
    assert stats["done.nested"]["total_ms"] == pytest.approx(4.0)
    assert stats["parent"]["self_ms"] == pytest.approx(
        stats["parent"]["total_ms"] - 10.0, abs=1e-6)
    nested = _spans("done.nested")[0]
    assert nested["args"]["nested"] is True
    assert nested["parent"] == parent.ctx.span_id
    tr.tracer.clear()
    assert tr.phase_stats()["done"]["self_ms"] == pytest.approx(10.0)


def test_a_cancelled_span_charges_nobody():
    tr.enable()
    with tr.span("parent"):
        with tr.span("dry") as sp:
            sp.cancel()
    stats = tr.phase_stats()
    assert "dry" not in stats
    assert stats["parent"]["self_ms"] == pytest.approx(
        stats["parent"]["total_ms"])


# ---- D. the stall readers, as functions of a span list ----------------------

def _window(steps=12, step_s=0.1, wait_s=0.08, stall_at=None, stall_s=0.0,
            stall_in="none", launch=True):
    """A training window's spans: every ``step_s`` a ``trainer.step`` of
    10 ms (2 ms placing, 3 ms launching) and then the wait for a value;
    before step ``stall_at`` starts, ``stall_s`` more under ``stall_in``
    (``"none"``, ``"ndarray.wait"`` or ``"trainer.step"``)."""
    spans, t = [], 100.0
    for i in range(1, steps + 1):
        extra = stall_s if stall_at is not None and i == stall_at - 1 else 0.0
        step_len = 0.010 + (extra if stall_in == "trainer.step" else 0.0)
        spans.append(("trainer.step", t, t + step_len, {"t": i}))
        spans.append(("trainer.place", t + 0.001, t + 0.003, {"bytes": 8}))
        if launch:
            spans.append(("trainer.launch", t + 0.004, t + 0.007,
                          {"first": False}))
        wait_len = wait_s + (extra if stall_in == "ndarray.wait" else 0.0)
        spans.append(("ndarray.wait", t + step_len + 0.002,
                      t + step_len + 0.002 + wait_len, {"bytes": 4}))
        t += step_s + extra
    return spans


def _read(name, obs):
    return bench_run.load_reader(
        name, os.path.join(ROOT, "chipbench", "layer_metrics"))(obs)


def _obs(spans, **more):
    return dict({"kind": "train", "spans": spans, "trace": None}, **more)


STALL_READERS = ("step_interval_max_over_p50.train", "stall_s.train",
                 "stall_host_pct.train")


def test_a_window_without_a_stall_reads_one_zero_none(capsys):
    # 0.125 s a step: the starts are exact in binary, so no interval is
    # longer than the median and there is nothing to attribute
    obs = _obs(_window(step_s=0.125))
    assert _read(STALL_READERS[0], obs) == 1.0
    assert _read(STALL_READERS[1], obs) == 0.0
    assert _read(STALL_READERS[2], obs) is None
    # one progress line for the three readers
    assert capsys.readouterr().out.count("host timeline ") == 1


@pytest.mark.parametrize("stall_in,host_pct", [
    ("ndarray.wait", 0.0), ("none", 100.0), ("trainer.step", 100.0)])
def test_without_a_stall_the_longest_interval_is_attributed(stall_in,
                                                            host_pct):
    """A real window always has a longest interval: the share is reported
    in every traced run (the driver wants each listed metric in the line),
    of that interval's excess where nothing stalled."""
    obs = _obs(_window(stall_at=7, stall_s=0.02, stall_in=stall_in))
    assert _read("step_interval_max_over_p50.train", obs) == pytest.approx(
        1.2)
    assert _read("stall_s.train", obs) == 0.0
    assert _read("stall_host_pct.train", obs) == pytest.approx(
        host_pct, abs=1e-6)


@pytest.mark.parametrize("stall_in,host_pct", [
    ("ndarray.wait", 0.0), ("none", 100.0), ("trainer.step", 100.0)])
def test_a_planted_stall_is_sized_and_attributed(stall_in, host_pct, capsys):
    obs = _obs(_window(stall_at=7, stall_s=0.25, stall_in=stall_in))
    assert _read("step_interval_max_over_p50.train", obs) == pytest.approx(
        3.5)
    assert _read("stall_s.train", obs) == pytest.approx(0.25)
    assert _read("stall_host_pct.train", obs) == pytest.approx(
        host_pct, abs=1e-6)
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("host timeline ")]
    longest = json.loads(line[0][len("host timeline "):])["longest"][0]
    assert longest["t"] == 6                 # the interval that step began
    assert longest["seconds"] == pytest.approx(0.35)
    assert longest["by_name"][stall_in] >= 0.25
    assert sum(longest["by_name"].values()) == pytest.approx(0.35)


def test_an_interval_that_holds_the_profilers_start_is_left_out():
    spans = _window(step_s=0.125, stall_at=7, stall_s=0.25)
    stalled_from = sorted(s for n, s, _, _ in spans
                          if n == "trainer.step")[5]
    # the device trace's clock runs 40 s behind the monotonic one
    trace = {"window": (stalled_from + 0.2 - 40.0, stalled_from + 0.5 - 40.0),
             "to_monotonic": 40.0}
    obs = _obs(spans, trace=trace)
    assert _read("stall_s.train", obs) == 0.0
    assert _read("step_interval_max_over_p50.train", obs) == pytest.approx(
        1.0)
    assert _read("stall_host_pct.train", obs) is None
    # ... the stop, two intervals on, takes that one out too
    found = host_timeline.analyse(spans, [stalled_from + 0.2,
                                          stalled_from + 0.5])
    assert found["intervals"] == 11 - 2


def test_half_a_stall_in_the_wait_reads_fifty():
    spans = _window(stall_at=7, stall_s=0.2, stall_in="ndarray.wait")
    # move half of the long wait out from under the span
    spans = [(n, s, e - 0.1 if n == "ndarray.wait" and e - s > 0.2 else e, a)
             for n, s, e, a in spans]
    found = host_timeline.analyse(spans)
    assert found["stall_s"] == pytest.approx(0.2)
    assert found["stall_host_pct"] == pytest.approx(50.0)


@pytest.mark.parametrize("name", STALL_READERS)
def test_stall_readers_return_none_on_the_parents_program_and_serving(name):
    parent = _obs(_window(stall_at=7, stall_s=0.25, launch=False))
    assert _read(name, parent) is None
    serving = dict(_obs(_window(stall_at=7, stall_s=0.25)), kind="serve")
    assert _read(name, serving) is None
    assert _read(name, _obs(_window(steps=2))) is None   # too few steps


def test_seconds_by_name_takes_the_innermost_span():
    spans = [("outer", 0.0, 10.0, {}), ("inner", 2.0, 3.0, {}),
             ("inner", 9.0, 12.0, {})]
    got = host_timeline.seconds_by_name(spans, 1.0, 11.0)
    assert got == pytest.approx({"outer": 7.0, "inner": 3.0})
    assert host_timeline.seconds_by_name(spans, 20.0, 21.0) == {"none": 1.0}


# ---- D. the set-up readers --------------------------------------------------

SETUP_COUNTERS = {"setup_trace_s": 3.0, "setup_lower_s": 2.0,
                  "setup_compile_s": 4.0, "setup_cache_load_s": 1.0}


@pytest.mark.parametrize("name", sorted(SETUP_COUNTERS))
def test_setup_counter_readers(name):
    counters = {"pcache": {"trace_s": 3.0, "lower_s": 2.0, "compile_s": 5.0,
                           "load_s": 1.0, "disk_misses": 0, "requests": 9}}
    obs = _obs([], counters=counters)
    assert _read(name, obs) == SETUP_COUNTERS[name]
    # the parent's program has no such counter; a serving cell is not read
    assert _read(name, _obs([], counters={"pcache": {"requests": 9}})) is None
    assert _read(name, dict(obs, kind="serve")) is None


def test_setup_build_and_named_share_read_the_aggregate_past_a_clear():
    obs = _obs([], setup_s=2.0)
    # the parent's tracer: no ``trainer.build`` span, no self time
    assert _read("setup_build_s.train", obs) is None
    assert _read("setup_named_pct.train", obs) is None
    tr.enable()
    t = tr.now()
    tr.complete("trainer.build", t - 1.0, t - 0.5)
    tr.complete("jax.compile", t - 0.5, t - 0.25)
    tr.clear()                  # the window opens: the ring goes
    tr.complete("trainer.step", t, t + 0.125)       # the window's own
    obs = _obs([("trainer.step", t, t + 0.125, {})], setup_s=2.0)
    assert _read("setup_build_s.train", obs) == pytest.approx(0.5)
    assert _read("setup_named_pct.train", obs) == pytest.approx(37.5)
    assert _read("setup_build_s.train", dict(obs, kind="serve")) is None
    assert _read("setup_named_pct.train", dict(obs, kind="serve")) is None


def test_the_index_lists_the_nine_readers_in_the_five_cells():
    index = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in index["workloads"]]
    first = [m["name"] for m in index["per_layer"]].index("setup_trace_s")
    mine = index["per_layer"][first:first + 9]     # later PRs append after
    assert [m["name"] for m in mine] == [
        "setup_trace_s", "setup_lower_s", "setup_compile_s",
        "setup_cache_load_s", "setup_build_s.train", "setup_named_pct.train",
        "step_interval_max_over_p50.train", "stall_s.train",
        "stall_host_pct.train"]
    for m in mine:
        assert m["workloads"] == cells
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", m["name"] + ".py"))
        assert m["moves"] == ("setup_s" if m["name"].startswith("setup_")
                              else "train_tokens_per_s")


# ---- end to end: a planted slow step through the harness --------------------

def test_rehearsal_finds_sizes_and_attributes_a_planted_slow_step(capsys):
    here = os.path.join(ROOT, "chipbench", "tests")
    index = bench_run.load_json(os.path.join(here, "BENCHMARK.host_tiny.json"))
    # long against what a loaded CPU box adds of its own: its hiccups
    # (tens of ms under a step of 10) count as stalls too
    delay_s, at = 2.0, 14
    chaos.arm("trainer.step", "slow", delay_ms=delay_s * 1e3, at=at)
    result = bench_run.run_cell(
        index, "bert_tiny_train_host", 7, 3.0, True, jax.devices(),
        workload_dir=os.path.join(here, "workloads"), rehearsal=True)
    assert result["correct"] and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(m["name"] for m in index["per_layer"]) == set(metrics)
    assert metrics["stall_s.train"] == pytest.approx(delay_s, rel=0.2)
    assert metrics["stall_host_pct.train"] > 75.0
    assert metrics["step_interval_max_over_p50.train"] > 3.0
    assert metrics["compiles_in_window.train"] == 0.0
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("host timeline ")]
    assert len(lines) == 1
    longest = json.loads(lines[0][len("host timeline "):])["longest"][0]
    assert longest["t"] == at
    assert longest["by_name"]["trainer.step"] == pytest.approx(
        delay_s, rel=0.2)
    # set-up: every part is there, the parts are below the whole, and the
    # compile cache is off under the suite, so nothing was loaded
    parts = [metrics[k] for k in ("setup_trace_s", "setup_lower_s",
                                  "setup_compile_s", "setup_cache_load_s",
                                  "setup_build_s.train")]
    assert all(p >= 0.0 for p in parts) and metrics["setup_trace_s"] > 0.0
    assert metrics["setup_cache_load_s"] == 0.0
    assert 0.0 < metrics["setup_named_pct.train"] <= 100.0
    # the traced run asked for the step's program a second time (its
    # text): jax's own cache answers, and the trace's event is counted
    assert pcache.programs()["step"]["count"] >= 2


# ---- tools/trace_summary.py -------------------------------------------------

def _trace_summary():
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(ROOT, "tools", "trace_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_summary_prints_setup_and_waits_and_counts_trainer_once():
    # a recorded span list (seconds): build with a trace inside it, a step
    # with its place and its launch (which traced, lowered, loaded), a wait
    events, ids = [], iter(range(1, 100))

    def ev(name, t0, t1, parent=0, **args):
        sid = next(ids)
        events.append(("X", name, t0, t1 - t0, 1, "MainThread", sid, parent,
                       1, args or None))
        return sid

    build = ev("trainer.build", 0.0, 2.0, params=4)
    ev("jax.trace", 0.5, 1.0, build, fun="copy")
    step = ev("trainer.step", 2.0, 12.0, t=1)
    ev("trainer.place", 2.0, 3.0, step, bytes=8)
    launch = ev("trainer.launch", 3.0, 11.0, step, first=True)
    ev("jax.trace", 3.0, 4.0, launch, fun="inner", nested=True)
    ev("jax.trace", 3.0, 6.0, launch, fun="step")
    ev("jax.lower", 6.0, 7.0, launch, fun="step")
    ev("pcache.load", 7.5, 9.5, launch, fun="step", nested=True)
    ev("jax.compile", 7.0, 10.0, launch, fun="step")
    ev("ndarray.wait", 12.0, 12.5, bytes=4)
    ts = _trace_summary()
    doc = obs_export.to_chrome_trace(events)
    summary = ts.summarize(doc["traceEvents"])
    tot = summary["setup"]["totals"]
    assert tot == pytest.approx({"trace_ms": 3500.0, "lower_ms": 1000.0,
                                 "compile_ms": 1000.0, "load_ms": 2000.0})
    progs = summary["setup"]["programs"]
    assert progs["step"] == pytest.approx(
        {"trace_ms": 3000.0, "lower_ms": 1000.0, "compile_ms": 3000.0,
         "load_ms": 2000.0})
    assert progs["inner"]["trace_ms"] == pytest.approx(1000.0)
    assert summary["setup"]["build_ms"] == pytest.approx(
        {"trainer.build": 2000.0})
    assert summary["waits"] == pytest.approx(
        {"count": 1, "total_ms": 500.0, "max_ms": 500.0})
    # trainer.* by self time: build 1.5 + step 1 + place 1 + launch 1
    # (8 less trace 3, lower 1, compile 3; the nested two counted once)
    assert summary["by_name"]["trainer.launch"]["self_ms"] == pytest.approx(
        1000.0)
    assert summary["by_name"]["jax.trace"]["self_ms"] == pytest.approx(3500.0)
    assert summary["by_name"]["pcache.load"]["self_ms"] == 0.0
    assert summary["critical_path"]["compute_ms"] == pytest.approx(4500.0)
    # the inclusive wall counts the step once, not its place and launch too
    assert summary["overlap_efficiency"] == pytest.approx(1.0)
    text = ts.format_summary(summary)
    assert "Set-up (jax's own phases" in text
    assert "Waits: host blocked on the device (ndarray.wait) 500.00 ms in 1" \
        in text
    assert "trainer.build" in text and "step " in text
