"""The harness's tests of what ``configs/mla_moe`` added (run by hand with
the others, see ``test_harness.py``): the tiny cell's rehearsal through the
harness's own code and index entries, the planted faults and the control in
the tiny check, the new arithmetic and the new readers on made-up traces."""
import json
import os

import pytest

from chipbench import check, flops, flops_mla_moe, run, scope_time
from chipbench.configs import mla_moe

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
INDEX = os.path.join(HERE, "BENCHMARK.mla_moe_tiny.json")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def rehearse(workload, seed, trace):
    """``tests/rehearse.py`` on this file's own tiny index."""
    import jax
    result = run.run_cell(run.load_json(INDEX), workload, seed, 1.0, trace,
                          jax.devices(),
                          workload_dir=os.path.join(HERE, "workloads"),
                          rehearsal=True)
    return {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"],
            "platform": result["device"]["platform"],
            "metrics_read": sorted(result["metrics"]),
            "compared": result["compared"]}


def test_rehearsal_is_correct_and_names_the_metrics_it_could_read():
    out = rehearse("mla_moe_tiny_train", 2**31 + 17, trace=True)
    assert out["correct"], out["compared"]
    assert out["platform"] == "cpu" and out["attempted"] > 0
    assert out["failed"] == 0
    # no device trace in a rehearsal: the counter's reader and the host's
    assert {"routed_rows_pct.train", "step_mfu_pct.mla_moe_train"} \
        - set(out["metrics_read"]) == {"step_mfu_pct.mla_moe_train"}
    assert "compiles_in_window.train" in out["metrics_read"]


@pytest.mark.parametrize("kw", [
    {"fault": "expert_dropped"}, {"fault": "no_rope_on_shared_key"},
    {"precision": "fp8"}, {"rows_used": 1}],
    ids=["expert_dropped", "no_rope_on_shared_key", "fp8_control",
         "half_batch"])
def test_planted_fault_or_control_comes_out_not_correct(kw):
    cfg = load("chipbench/tests/configs/mla_moe_tiny.json")
    cell = load("chipbench/tests/workloads/mla_moe_tiny_train.json")
    sound = mla_moe.reference(cfg, cell, 9, cell["check_steps"])
    broken = mla_moe.reference(cfg, cell, 9, cell["check_steps"], **kw)
    numbers, _ = check.training_numbers(broken, sound)
    correct, compared = check.judge(numbers, cell["limits"])
    assert not correct, compared


def test_index_entries_resolve_to_files():
    index = load("BENCHMARK.json")
    cells = {w["name"]: w for w in index["workloads"]}
    for name in ("kimi_vl_a3b_train_s8192", "bert_base_train_s512_dp4"):
        entry, cfg, cell = run.load_cell(name)
        assert cell["kind"] == "train_steps" and entry["chips"] == cell["dp"] \
            or entry["chips"] == 1
        assert set(cell["limits"]) | set(cell.get("not_compared", ())) >= {
            "grad_norm_gap", "change_norm_gap", "grad_diff_median",
            "grad_diff_worst", "loss1_gap"}
    cfg = load("chipbench/configs/kimi_vl_a3b.json")
    assert sorted(cfg["reduced"]) == sorted(
        run.find(index["configs"], "kimi_vl_a3b", "config")["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 163840}
    twin = load("chipbench/workloads/bert_base_train_s512.json")
    dp4 = load("chipbench/workloads/bert_base_train_s512_dp4.json")
    assert dict(twin, dp=4, traffic="train_s512_dp4") == dp4
    for m in index["per_layer"]:
        if "bert_base_train_s512" in m.get("workloads", ()) \
                and m["name"].endswith(".train"):
            assert "bert_base_train_s512_dp4" in m["workloads"], m["name"]
    assert sum(w["chips"] == 4 for w in cells.values()) == 1


def test_flops_against_hand_worked_numbers():
    cfg = load("chipbench/configs/kimi_vl_a3b.json")
    # attention weights: 2048*3072 + 2048*576 + 512*4096 + 2048*2048
    assert flops_mla_moe.attention_weights(cfg) == 13762560
    assert flops_mla_moe.expert_weights(cfg) == 3 * 2048 * 1408
    assert flops_mla_moe.expected_rows_per_token(cfg) == 0.75
    # one row of 8192, one layer, causal: 16 heads x 8192^2 x (192 + 128)
    f, b = flops_mla_moe.latent_forward_cost(1, 8192, cfg)
    assert f == 16 * 8192 * 8192 * 320 and f / 8192 == pytest.approx(41.9e6, rel=1e-2)
    assert b == 8192 * (3072 + 4096 + 64 + 2048) * 2
    fb, bb = flops_mla_moe.latent_backward_cost(1, 8192, cfg)
    assert fb == 2 * f and bb == 8192 * (2 * (3072 + 4096 + 64) + 4096) * 2
    # 12288 rows: 9 matmuls of 2 x 12288 x 2048 x 1408
    g, _ = flops_mla_moe.grouped_cost(12288, cfg)
    assert g == 9 * 2 * 12288 * 2048 * 1408
    # a token, forward, an expert layer: projections 27.5 + shared 34.6 MFLOP
    step = flops_mla_moe.train_flops_per_step(cfg, 2, 8192, 5 * 12288)
    assert step / (2 * 8192) == pytest.approx(2.6e9, rel=0.1)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(f, b, peaks) == pytest.approx(f / 197e12)


def _obs(events=(), devices=None, chips=1, text="", busy_s=1.0):
    return {"kind": "train", "chips": chips, "step_text": text,
            "trace": {"by_name": {}, "events": list(events),
                      "busy_s": busy_s, "devices": devices}}


TEXT = """
ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/moe_/moe_experts/gather"}
  %fusion.2 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(net0))/layer1_/moe_/checkpoint/moe_experts/mul"}
  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/moe_/moe_router/dot_general"}
  %fusion.4 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/attn_/attention/exp"}
  %fusion.5 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/optimizer/mul"}
}
"""


def test_scope_time_is_the_union_of_the_events_under_a_scope():
    # fusion.2 runs INSIDE fusion.1's event (a conditional's body): once
    events = [("fusion.1", 0.0, 3.0), ("fusion.2", 1.0, 2.0),
              ("fusion.3", 3.0, 7.0), ("fusion.4", 7.0, 15.0),
              ("fusion.5", 15.0, 31.0), ("copy.9", 31.0, 63.0),
              ("fusion.1", 63.0, 66.0), ("fusion.2", 64.0, 65.0)]
    obs = _obs(events, text=TEXT, busy_s=66.0)
    assert scope_time.seconds_under(obs, ("moe_experts",)) == 6.0
    assert scope_time.seconds_under(
        obs, ("moe_router", "moe_experts", "moe_shared")) == 10.0
    assert scope_time.seconds_under(obs, ("attention",)) == 8.0
    assert scope_time.seconds_under(obs, ("no_such_scope",)) is None
    assert scope_time.steps_traced(obs, ("moe_experts",)) == 2.0
    # an instruction in a loop of its own (fusion.2, 6 times a step here)
    # does not count the steps
    looped = _obs(events + [("fusion.2", 70.0 + i, 70.5 + i)
                            for i in range(10)], text=TEXT)
    assert scope_time.steps_traced(looped, ("moe_experts",)) == 2.0
    reader = run.load_reader("moe_scope_pct.train",
                             os.path.join(ROOT, "chipbench/layer_metrics"))
    assert reader(obs) == pytest.approx(100.0 * 10.0 / 66.0)
    # over two chips: the average of each chip's union
    two = _obs(events, devices={"a": events, "b": events[:3]}, chips=2,
               text=TEXT)
    assert scope_time.seconds_under(two, ("moe_experts",)) == 4.5


def test_collective_exposed_counts_what_nothing_overlaps():
    reader = run.load_reader("collective_exposed_pct.train",
                             os.path.join(ROOT, "chipbench/layer_metrics"))
    device = [("fusion.1", 0.0, 4.0), ("all-reduce-start.1", 4.0, 4.5),
              ("fusion.2", 4.5, 8.0), ("all-reduce-done.1", 8.0, 10.0),
              ("all-gather.3", 9.0, 10.0)]
    overlapped = [("fusion.1", 0.0, 10.0), ("all-reduce.2", 2.0, 5.0)]
    obs = _obs(devices={"a": device, "b": overlapped}, chips=2)
    # device a: 0.5 + 2.0 exposed of 10 busy; device b: none of 10
    assert reader(obs) == pytest.approx(100.0 * 2.5 / 20.0)
    assert reader(dict(obs, chips=1)) is None


def test_readers_take_the_rows_of_the_steps_they_time(monkeypatch):
    """Routing moves as the model trains: the whole step's share counts the
    rows of every step of the window, the experts' share of their roofline
    those of the steps traced."""
    class Log:      # step n sent 100 (n + 1) rows to the one expert layer
        def routed_rows_by_step(self):
            return [[[100.0 * (n + 1), 0.0]] for n in range(12)]

    monkeypatch.setattr(mla_moe, "_LIVE", [Log()])
    cfg = load("chipbench/configs/kimi_vl_a3b.json")
    events = [("fusion.1", 0.0, 3.0), ("fusion.2", 1.0, 2.0),
              ("fusion.1", 63.0, 66.0), ("fusion.2", 64.0, 65.0)]
    obs = dict(_obs(events, text=TEXT), cfg=cfg, steps=10, window_s=5.0,
               batch=2, seq=8192, cell={"trace_after_s": 1.0},
               peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    window = scope_time.routed_rows_by_step(obs)
    assert [sum(map(sum, rows)) for rows in window] == [
        100.0 * n for n in range(3, 13)]        # the last 10 of the 12
    # 0.5 s a step: the trace opens in the window's step 2 and holds 2
    assert scope_time.routed_rows_traced(obs, ("moe_experts",)) == [550.0]
    metrics = os.path.join(ROOT, "chipbench/layer_metrics")
    least = flops.roofline_seconds(*flops_mla_moe.grouped_cost(550.0, cfg),
                                   obs["peaks"])
    assert run.load_reader("expert_roofline_pct.train", metrics)(obs) == \
        pytest.approx(100.0 * least * 2 / 6.0)
    done = sum(flops_mla_moe.train_flops_per_step(cfg, 2, 8192, 100.0 * n)
               for n in range(3, 13))
    assert run.load_reader("step_mfu_pct.mla_moe_train", metrics)(obs) == \
        pytest.approx(100.0 * done / (5.0 * 197e12))
    monkeypatch.setattr(mla_moe, "_LIVE", [])
    assert scope_time.routed_rows_by_step(obs) is None
    assert run.load_reader("step_mfu_pct.mla_moe_train", metrics)(obs) is None
