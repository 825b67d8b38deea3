"""The harness's own tests. Run by hand, not collected by tier-1:

    JAX_PLATFORMS=cpu JAX_ENABLE_COMPILATION_CACHE=false \
      XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m pytest chipbench/tests -q

The rehearsals drive each cell's code at the tiny configurations beside this
file; the fault and control cases break the timed path underneath (or put
the lower-precision reference in its place) and must see ``correct`` false.
"""
import json
import os
import re

import numpy as np
import pytest

from chipbench import flops, stats, trace_reduce
from chipbench.tests.rehearse import rehearse
from chipbench.traffic import closed_loop, open_loop

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BERT = {"hidden_size": 768, "intermediate_size": 3072, "vocab_size": 30522,
        "num_hidden_layers": 12}
GPT2 = {"hidden_size": 768, "intermediate_size": 3072, "vocab_size": 50257,
        "num_hidden_layers": 12}


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


# ---- the trace reducer, on a small trace recorded on the chip --------------

def test_reducer_on_recorded_trace():
    raw = load("chipbench/tests/recorded_trace.json")
    raw["devices"] = {k: [tuple(e) for e in v]
                      for k, v in raw["devices"].items()}
    red = trace_reduce.reduce(raw, chips=1)
    expect = raw["expect"]
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    idle = sum(e - s for s, e in red["gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # custom-call selection: the Mosaic kernels, not the compiler's own
    kernels = {n: s for n, s in red["by_name"].items()
               if n in red["custom_calls"]}
    assert len(kernels) == expect["kernel_names"] == 21
    assert sum(kernels.values()) == pytest.approx(expect["kernel_s"], rel=1e-9)
    # the breakdown sums an op over the compiler's numbering of it
    from chipbench.run import breakdown_of
    ops = dict(breakdown_of(dict(red, to_monotonic=0.0), [])["device_ops"])
    assert len(ops) == 10 and "transpose_jvp___" in ops and "fusion" in ops
    assert ops["transpose_jvp___"] + ops["jvp__"] == pytest.approx(
        expect["kernel_s"], rel=1e-9)


def test_union_and_gaps_of_overlapping_intervals():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.8)]
    assert trace_reduce.union_seconds(spans) == pytest.approx(4.0)
    gaps = trace_reduce.idle_gaps(spans, (0.0, 10.0))
    assert gaps == [(6.0, 10.0), (3.0, 5.0)]
    clipped = trace_reduce.clip([("a", -1.0, 0.5), ("b", 9.5, 12.0),
                                 ("c", 20.0, 21.0)], (0.0, 10.0))
    assert clipped == [("a", 0.0, 0.5), ("b", 9.5, 10.0)]
    assert trace_reduce.open_span_at(
        [("outer", 0.0, 10.0), ("inner", 2.0, 3.0)], 2.5) == "inner"
    assert trace_reduce.open_span_at([("outer", 0.0, 1.0)], 2.5) == "none"


# ---- arithmetic ------------------------------------------------------------

def test_percentile_and_pooled_gaps():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.pooled_gaps([[0.0, 1.0, 3.0], [10.0], [5.0, 5.5]]) == \
        [1.0, 2.0, 0.5]
    assert stats.iqr_spread([10, 10, 10, 10, 11, 9]) == pytest.approx(0.05)


def test_open_loop_schedule_is_a_function_of_the_seed_alone():
    cell = load("chipbench/tests/workloads/lm_tiny_serve_chat.json")
    cell = dict(cell, rate_per_s=3.0)       # 135 requests in 45 s
    a = open_loop.schedule(cell, 2**31 + 5, 45.0)
    assert a == open_loop.schedule(cell, 2**31 + 5, 45.0)
    b = open_loop.schedule(cell, 7, 45.0)
    assert a != b
    # every seed gets the same set of gaps and lengths, in another order
    for col in (1, 2):
        assert sorted(r[col] for r in a) == sorted(r[col] for r in b)
    assert a[0][0] == 0.0 and len(a) == round(cell["rate_per_s"] * 45.0)
    # ... the gaps too: all but the one before the first request, at 0
    grid = set(np.round(-np.log(1.0 - (np.arange(135) + 0.5) / 135) / 3.0, 9))
    assert set(np.round(np.diff([r[0] for r in a]), 9)) <= grid
    assert all(cell["prompt"]["min"] <= p <= cell["prompt"]["max"] and
               cell["output"]["min"] <= o <= cell["output"]["max"]
               for _, p, o in a)
    assert sorted(r[0] for r in a) == [r[0] for r in a]
    assert np.median([p for _, p, _ in a]) == pytest.approx(
        cell["prompt"]["median"], rel=0.05)
    # stationary: over many seeds the first third of the window is offered
    # what the last third is (no ramp from short, fast to long, slow), and
    # short gaps do fall together (a third of the runs of three shortest)
    first, last, bunched = [], [], 0
    for seed in range(200):
        plan = open_loop.schedule(cell, seed, 45.0)
        n = len(plan) // 3
        first.append(sum(o for _, _, o in plan[:n]) / (plan[n][0] - plan[0][0]))
        last.append(sum(o for _, _, o in plan[-n:]) / (plan[-1][0] - plan[-n - 1][0]))
        gaps = np.diff([d for d, _, _ in plan])
        short = gaps < np.quantile(gaps, 1 / 3)
        bunched += bool(np.any(short[:-2] & short[1:-1] & short[2:]))
    assert np.mean(first) == pytest.approx(np.mean(last), rel=0.05)
    assert bunched > 190
    docs = load("chipbench/tests/workloads/lm_tiny_serve_docs.json")
    assert sorted(closed_loop.request_list(docs, 1)) != \
        closed_loop.request_list(docs, 1)
    assert sorted(p for p, _ in closed_loop.request_list(docs, 1)) == \
        sorted(p for p, _ in closed_loop.request_list(docs, 2))


def test_flops_against_hand_worked_numbers():
    # one block: 4*768^2 + 2*768*3072 = 7,077,888 matmul weights
    assert flops.block_params(BERT) == 7077888
    # BERT-base, batch 32 x 512, 20 picked: forward =
    #   blocks 2*16384*12*7077888            = 2.78317e12
    #   attention 12 * 4*32*512*512*768      = 3.09238e11
    #   MLM head 2*640*(768^2 + 768*30522)   = 3.07594e10
    #   pooler + NSP 2*32*(768^2 + 1536)     = 3.78e7
    step = flops.bert_train_flops_per_step(BERT, 32, 512, 20)
    assert step == pytest.approx(3 * (2.78317e12 + 3.09238e11 + 3.07594e10
                                      + 3.78e7), rel=1e-4)
    assert step / (32 * 512) == pytest.approx(0.5714e9, rel=1e-3)
    # GPT-2 small: decoding one token over 511 cached positions =
    #   2*12*7077888 + 4*12*768*512 + 2*768*50257 = 2.65938e8
    assert flops.lm_decode_flops(GPT2, 511) == pytest.approx(2.65938e8, rel=1e-5)
    #   prefill of 512: 2*512*12*7077888 + 4*12*768*(512*513/2) + 2*768*50257
    assert flops.lm_prefill_flops(GPT2, 512) == pytest.approx(
        8.69731e10 + 4.84148e9 + 7.7195e7, rel=1e-4)
    f, b = flops.flash_forward_cost(32, 512, BERT, 2, causal=False)
    assert f == 4 * 32 * 512 * 512 * 768 and b == 4 * 32 * 512 * 768 * 2
    fb, bb = flops.flash_backward_cost(32, 512, BERT, 2, causal=False)
    assert fb == 2 * f and bb == 2 * b
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(f, b, peaks) == pytest.approx(f / 197e12)


# ---- BENCHMARK.json resolves to files --------------------------------------

def test_benchmark_index_names_and_files():
    index = load("BENCHMARK.json")
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    cells = {w["name"] for w in index["workloads"]}
    e2e = {m["name"] for m in index["end_to_end"]}
    assert "setup_s" in e2e
    for c in index["configs"]:
        assert name.match(c["name"]) and os.path.isfile(os.path.join(ROOT, c["file"]))
        cfg = load(c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in ("builder", "reference"):
            assert os.path.isfile(os.path.join(
                ROOT, cfg[key].replace(".", "/") + ".py"))
    for w in index["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        cell = load("chipbench/workloads/%s.json" % w["name"])
        assert cell["config"] == w["config"] and cell["traffic"] == w["traffic"]
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench/traffic/%s.py" % cell["kind"]))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in index["end_to_end"] + index["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in index["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench/layer_metrics/%s.py" % m["name"]))


# ---- rehearsals of every cell, and the cases that must come out false ------

@pytest.mark.parametrize("workload", [
    "bert_tiny_train", "bert_tiny_train_dp4", "lm_tiny_serve_chat",
    "lm_tiny_serve_docs"])
def test_rehearsal_is_correct_and_cannot_pass_for_a_chip_run(workload):
    import jax
    if workload.endswith("dp4") and len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices (XLA_FLAGS in the docstring)")
    out = rehearse(workload, seed=2**31 + 11, trace=True)
    assert out["correct"] and out["platform"] == "cpu" and out["rehearsal"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(isinstance(m, str) for m in out["metrics_read"])
    assert "metrics" not in out and "device" not in out


def _break_step(monkeypatch, fault):
    """Plant a fault under the timed training path."""
    from chipbench.configs import bert
    real_step = bert.TrainSystem.step
    real_batches = bert.TrainSystem._as_program_batch

    if fault == "state_unchanged":
        def step(self, i):
            import jax.numpy as jnp
            tr = self.trainer       # the step donates: keep copies
            values = [jnp.array(a, copy=True) for a in tr._values]
            states = [tuple(jnp.array(a, copy=True) for a in s)
                      for s in tr._states]
            loss = real_step(self, i)
            tr._values, tr._states = values, states
            return loss
        monkeypatch.setattr(bert.TrainSystem, "step", step)
    else:
        share = {"half_batch": 2, "no_exchange": 4}[fault]

        def batches(b):
            # rows beyond the first share are copies of it: the mean over
            # the batch is then the mean over that share alone
            out = []
            for a in real_batches(b):
                keep = len(a) // share
                out.append(np.concatenate([a[:keep]] * share))
            return tuple(out)
        monkeypatch.setattr(bert.TrainSystem, "_as_program_batch",
                            staticmethod(batches))


@pytest.mark.parametrize("fault,workload", [
    ("state_unchanged", "bert_tiny_train"),
    ("half_batch", "bert_tiny_train"),
    ("no_exchange", "bert_tiny_train_dp4")])
def test_training_fault_comes_out_not_correct(monkeypatch, fault, workload):
    import jax
    if workload.endswith("dp4") and len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    _break_step(monkeypatch, fault)
    out = rehearse(workload, seed=5)
    assert not out["correct"], out["compared"]


def test_altered_token_comes_out_not_correct(monkeypatch):
    from mxnet_tpu.serving.generation import DecodeEngine
    real = DecodeEngine.decode_step
    calls = {"n": 0}

    def decode_step(self, tokens, temperatures):
        toks = real(self, tokens, temperatures)
        calls["n"] += 1
        return (toks + 1) % 1000 if calls["n"] % 5 == 0 else toks
    monkeypatch.setattr(DecodeEngine, "decode_step", decode_step)
    out = rehearse("lm_tiny_serve_docs", seed=6)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("workload,kw", [
    ("bert_tiny_train", {"precision": "fp8"}),
    ("lm_tiny_serve_chat", {"control": {"operands": "fp8"}})])
def test_control_in_lower_precision_comes_out_not_correct(monkeypatch,
                                                          workload, kw):
    """The reference in the nearest precision below the configuration's,
    put in the program's place, must fail at least one number."""
    from chipbench import check, run, serving
    from chipbench.configs import bert, transformer_lm
    index = load("chipbench/tests/BENCHMARK.tiny.json")
    cfg = load(run.find(index["configs"], workload.rsplit("_", 2)[0]
                        if workload.startswith("lm") else "bert_tiny",
                        "config")["file"])
    cell = load("chipbench/tests/workloads/%s.json" % workload)
    if workload.startswith("bert"):
        ref = bert.reference(cfg, cell, 9, cell["check_steps"])
        ctl = bert.reference(cfg, cell, 9, cell["check_steps"], **kw)
        numbers, _ = check.training_numbers(ctl, ref)
    else:
        rng = np.random.default_rng(9)
        records = []
        for n in (20, 60, 110, 40):
            rec = serving.Record(rng.integers(0, 1000, n).tolist(), 24, 0.0)
            rec.tokens = [0] * 24
            records.append(rec)
        # the control need not decode: at each position of the same prompts
        # and tokens it reads the gap of the token IT puts first
        params = transformer_lm.reference_weights(cfg, 9)
        for rec in records:     # greedy tokens of the reference itself
            for i in range(24):
                rows = transformer_lm.served_logits(params, cfg, rec.prompt,
                                                    rec.tokens[:i + 1])
                rec.tokens[i] = int(np.asarray(rows)[i].argmax())
        numbers = serving.serving_numbers(cfg, cell, 9, transformer_lm,
                                          records, **kw)
    correct, compared = check.judge(numbers, cell["limits"])
    assert not correct, compared
