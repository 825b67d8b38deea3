"""The harness's tests of what ``configs/eva_lm`` added (run by hand with
the others, see ``test_harness.py``): the tiny cell's rehearsal through the
harness's own code and index entries, the planted faults and the control in
the tiny check, the new arithmetic and the new readers on made-up traces."""
import json
import os

import pytest

from chipbench import check, flops, flops_eva, run
from chipbench.configs import eva_lm

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
INDEX = os.path.join(HERE, "BENCHMARK.eva_tiny.json")
METRICS = os.path.join(ROOT, "chipbench/layer_metrics")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


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
    out = rehearse("eva_tiny_train", 2**31 + 17, trace=True)
    assert out["correct"], out["compared"]
    assert out["platform"] == "cpu" and out["attempted"] > 0
    assert out["failed"] == 0
    # no device trace, no peak and no kernel in a rehearsal on the CPU: of
    # the new readers the dispatcher's counter alone finds something to read
    new = {"step_mfu_pct.eva_train", "eva_roofline_pct.train",
           "attention_eva_pct.train", "eva_pool_scope_pct.train",
           "eva_dead_tiles_pct.train"}
    assert new & set(out["metrics_read"]) == {"attention_eva_pct.train"}
    assert "compiles_in_window.train" in out["metrics_read"]


@pytest.mark.parametrize("kw", [
    {"fault": "summaries_left_out"}, {"fault": "own_window_summaries_seen"},
    {"fault": "first_head_only"}, {"precision": "fp8"}],
    ids=["summaries_left_out", "own_window_summaries_seen", "first_head_only",
         "fp8_control"])
def test_planted_fault_or_control_comes_out_not_correct(kw):
    cfg = load("chipbench/tests/configs/eva_tiny.json")
    cell = load("chipbench/tests/workloads/eva_tiny_train.json")
    sound = eva_lm.reference(cfg, cell, 9, cell["check_steps"])
    broken = eva_lm.reference(cfg, cell, 9, cell["check_steps"], **kw)
    numbers, _ = check.training_numbers(broken, sound)
    correct, compared = check.judge(numbers, cell["limits"])
    assert not correct, compared


def test_calibrate_reads_the_faults_by_the_reference_key_and_judges_each():
    """``calibrate_eva.one_seed`` on the tiny cell: the program comes out
    correct under the cell's limits, the control and every fault the
    reference module plants not, each with the numbers over their limit."""
    import jax
    from chipbench import calibrate_eva
    from chipbench.configs import eva_lm_ref
    cfg = load("chipbench/tests/configs/eva_tiny.json")
    cell = load("chipbench/tests/workloads/eva_tiny_train.json")
    out = calibrate_eva.one_seed(cfg, cell, 9, jax.devices()[:1], True)
    assert out["program_correct"] and out["program_over"] == []
    for name in ("control",) + tuple(eva_lm_ref.FAULTS):
        assert not out[name + "_correct"] and out[name + "_over"], name
        assert set(out[name + "_over"]) <= set(cell["limits"])
    json.dumps(out)


def test_index_entries_resolve_to_files_and_widths_are_the_published():
    index = load("BENCHMARK.json")
    entry, cfg, cell = run.load_cell("evabyte_train_s32768")
    assert entry["chips"] == 1 and cell["kind"] == "train_steps"
    assert (cell["batch"], cell["seq"]) == (1, 32768)
    assert set(cell["limits"]) | set(cell.get("not_compared", ())) >= {
        "grad_norm_gap", "change_norm_gap", "grad_diff_median",
        "grad_diff_worst", "loss1_gap"}
    listed = run.find(index["configs"], "evabyte", "config")
    assert cfg["reduced"] == listed["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["source"] == listed["source"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["intermediate_size"], cfg["window_size"], cfg["chunk_size"],
            cfg["vocab_size"], cfg["num_pred_heads"], cfg["rope_theta"],
            cfg["num_hidden_layers"], cfg["init_std"]) == (
        4096, 32, 11008, 2048, 16, 320, 8, 100000, 4, 0.01275)
    for m in index["per_layer"]:
        if "kimi_vl_a3b_train_s8192" in m.get("workloads", ()) and \
                "bert_base_train_s512" in m["workloads"]:
            assert "evabyte_train_s32768" in m["workloads"], m["name"]
    assert sum(w["chips"] == 4 for w in index["workloads"]) == 1


def test_flops_against_hand_worked_numbers():
    cfg = load("chipbench/configs/evabyte.json")
    # 16 windows: 16 x 2048 x 2049 / 2 positions, 2048 x 128 x 120 summaries
    assert flops_eva.live_pairs(32768, cfg) == (33570816, 31457280)
    assert sum(flops_eva.live_pairs(32768, cfg)) / 32768 == 1984.5
    assert flops_eva.live_pairs(2048, cfg) == (2048 * 2049 // 2, 0)
    assert flops_eva.layer_weights(cfg) == 4 * 4096 ** 2 + 3 * 4096 * 11008
    f, b = flops_eva.eva_forward_cost(1, 32768, cfg)
    # 32 heads x (pairs x 4 x 128 + the pooling's 32768 x 8 x 128)
    assert f == 32 * (65028096 * 512 + 32768 * 1024)
    assert f == pytest.approx(1.07e12, rel=1e-2)
    assert b == 32768 * (4 * 4096 + 1024) * 2
    fb, bb = flops_eva.eva_backward_cost(1, 32768, cfg)
    assert fb == 2 * f and bb == 2 * b
    assert flops.roofline_seconds(f, b, PEAKS) == pytest.approx(f / 197e12)
    step = flops_eva.train_flops_per_step(cfg, 1, 32768)
    assert step == pytest.approx(174e12, rel=1e-2)
    head = 2.0 * sum(32767 - j for j in range(8)) * 4096 * 320
    assert step == 3 * (2.0 * 32768 * 4 * 202375168 + 4 * f + head)


def _obs(events=(), text="", busy_s=1.0, **more):
    cfg = load("chipbench/configs/evabyte.json")
    return dict({"kind": "train", "chips": 1, "step_text": text, "cfg": cfg,
                 "batch": 1, "seq": 32768, "steps": 20, "window_s": 45.0,
                 "peaks": PEAKS,
                 "trace": {"by_name": {}, "events": list(events),
                           "busy_s": busy_s, "devices": None}}, **more)


TEXT = """
ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/attn_/attention/eva_pool/reduce_sum"}
  %custom-call.2 = f32[8]{0} custom-call(), metadata={op_name="jit(step)/jvp(net0)/layer1_/attn_/attention/flash_eva_fwd/pallas_call"}
  %custom-call.3 = f32[8]{0} custom-call(), metadata={op_name="jit(step)/transpose(jvp(net0))/layer1_/attn_/attention/flash_eva_bwd/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/ffn_/dot_general"}
}
"""


def test_new_readers_on_a_made_up_trace(monkeypatch):
    events = [("fusion.1", 0.0, 0.002), ("custom-call.2", 0.002, 0.022),
              ("custom-call.3", 0.022, 0.062), ("fusion.4", 0.062, 1.0),
              ("fusion.1", 1.0, 1.002), ("custom-call.2", 1.002, 1.022),
              ("custom-call.3", 1.022, 1.062), ("fusion.4", 1.062, 2.0)]
    obs = _obs(events, TEXT, busy_s=2.0)
    cfg = obs["cfg"]
    least = sum(flops.roofline_seconds(*cost(1, 32768, cfg), PEAKS)
                for cost in (flops_eva.eva_forward_cost,
                             flops_eva.eva_backward_cost))
    # two steps traced, 0.124 s under ``attention``, four layers' least time
    assert run.load_reader("eva_roofline_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * least * 4 * 2 / 0.124)
    assert run.load_reader("eva_pool_scope_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 0.004 / 2.0)
    assert run.load_reader("step_mfu_pct.eva_train", METRICS)(obs) == \
        pytest.approx(100.0 * 20 * flops_eva.train_flops_per_step(
            cfg, 1, 32768) / (45.0 * 197e12))
    from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_DISPATCHED", {
        "packed": 0, "flash": 0, "latent": 0, "eva": 3, "xla": 1})
    assert run.load_reader("attention_eva_pct.train", METRICS)(obs) == 75.0
    monkeypatch.setattr(pk, "_EVA_TILES", {"stepped": 320, "live": 228})
    assert run.load_reader("eva_dead_tiles_pct.train", METRICS)(obs) == 28.75


def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program from before this configuration (no ``eva`` path, no tile
    counter, no ``eva_pool`` scope), another configuration, a rehearsal."""
    from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_DISPATCHED", {"packed": 2, "xla": 0})
    monkeypatch.delattr(pk, "eva_tile_stats")
    bare = _obs(text="ENTRY %main () -> f32[] {\n}\n")
    kimi = dict(bare, cfg=load("chipbench/configs/kimi_vl_a3b.json"))
    for name in ("attention_eva_pct.train", "eva_dead_tiles_pct.train",
                 "eva_pool_scope_pct.train", "eva_roofline_pct.train"):
        assert run.load_reader(name, METRICS)(bare) is None, name
    for name in ("step_mfu_pct.eva_train", "eva_roofline_pct.train"):
        assert run.load_reader(name, METRICS)(kimi) is None, name
    assert run.load_reader("step_mfu_pct.eva_train", METRICS)(
        dict(bare, peaks=None)) is None
