"""The harness's tests of what ``configs/hybrid_ssm`` added (run by hand
with the others, see ``test_harness.py``): the tiny cell's rehearsal through
the harness's own code and index entries, the planted faults and the control
in the tiny check, the new arithmetic and the new readers on made-up
traces."""
import json
import os

import pytest

from chipbench import check, flops, flops_ssm, run
from chipbench.configs import hybrid_ssm

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
INDEX = os.path.join(HERE, "BENCHMARK.ssm_tiny.json")
METRICS = os.path.join(ROOT, "chipbench/layer_metrics")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "granite_4_0_h_micro_train_s32768"
NEW = {"step_mfu_pct.ssm_train", "ssd_roofline_pct.train",
       "gqa_roofline_pct.train", "ssm_scope_pct.train",
       "ssm_scan_scope_pct.train", "ssm_conv_scope_pct.train",
       "ssm_scan_kernel_pct.train", "attention_grouped_pct.train"}


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
    out = rehearse("ssm_tiny_train", 2**31 + 17, trace=True)
    assert out["correct"], out["compared"]
    assert out["platform"] == "cpu" and out["attempted"] > 0
    assert out["failed"] == 0
    # no device trace, no peak and no kernel in a rehearsal on the CPU: of
    # the new readers the two counters alone find something to read
    assert NEW & set(out["metrics_read"]) == {"ssm_scan_kernel_pct.train",
                                              "attention_grouped_pct.train"}
    assert "compiles_in_window.train" in out["metrics_read"]
    index = run.load_json(INDEX)
    assert NEW <= {m["name"] for m in index["per_layer"]}


@pytest.mark.parametrize("kw", [
    {"fault": "state_not_carried"}, {"fault": "conv_tap_dropped"},
    {"fault": "skip_dropped"}, {"fault": "residual_one"},
    {"fault": "kv_heads_misgrouped"}, {"precision": "fp8"}],
    ids=["state_not_carried", "conv_tap_dropped", "skip_dropped",
         "residual_one", "kv_heads_misgrouped", "fp8_control"])
def test_planted_fault_or_control_comes_out_not_correct(kw):
    cfg = load("chipbench/tests/configs/ssm_tiny.json")
    cell = load("chipbench/tests/workloads/ssm_tiny_train.json")
    sound = hybrid_ssm.reference(cfg, cell, 9, cell["check_steps"])
    broken = hybrid_ssm.reference(cfg, cell, 9, cell["check_steps"], **kw)
    numbers, _ = check.training_numbers(broken, sound)
    correct, compared = check.judge(numbers, cell["limits"])
    assert not correct, compared


def test_calibrate_reads_the_faults_by_the_reference_key_and_judges_each():
    import jax
    from chipbench import calibrate_eva
    from chipbench.configs import hybrid_ssm_ref
    cfg = load("chipbench/tests/configs/ssm_tiny.json")
    cell = load("chipbench/tests/workloads/ssm_tiny_train.json")
    out = calibrate_eva.one_seed(cfg, cell, 9, jax.devices()[:1], True)
    assert out["program_correct"] and out["program_over"] == []
    for name in ("control",) + tuple(hybrid_ssm_ref.FAULTS):
        assert not out[name + "_correct"] and out[name + "_over"], name
        assert set(out[name + "_over"]) <= set(cell["limits"])
    json.dumps(out)


def test_index_entries_resolve_to_files_and_widths_are_the_published():
    index = load("BENCHMARK.json")
    entry, cfg, cell = run.load_cell(CELL)
    assert entry["chips"] == 1 and cell["kind"] == "train_steps"
    assert (cell["batch"], cell["seq"], cell["picked"]) == (1, 32768, 32767)
    assert set(cell["limits"]) | set(cell.get("not_compared", ())) >= {
        "grad_norm_gap", "change_norm_gap", "grad_diff_median",
        "grad_diff_worst", "loss1_gap"}
    listed = run.find(index["configs"], "granite_4_0_h_micro", "config")
    assert cfg["reduced"] == listed["reduced"] == ["num_hidden_layers",
                                                   "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["source"] == listed["source"]
    # every number of the catalog's entry but the two reduced keys
    catalog = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "shared_intermediate_size": 8192,
        "vocab_size": 100352}
    assert {k: cfg[k] for k in catalog} == catalog
    assert cfg["num_hidden_layers"] == 10 and cfg["layer_types"] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["tie_word_embeddings"] and \
        cfg["position_embedding_type"] == "nope"
    for m in index["per_layer"]:
        if "kimi_vl_a3b_train_s8192" in m.get("workloads", ()) and \
                "evabyte_train_s32768" in m["workloads"] and \
                "bert_base_train_s512" in m["workloads"]:
            assert CELL in m["workloads"], m["name"]
    assert NEW <= {m["name"] for m in index["per_layer"]
                   if m.get("workloads") == [CELL]}
    assert len(index["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in index["workloads"]) == 1


def test_parameters_as_the_issue_counts_them():
    from chipbench.configs import hybrid_ssm_ref
    cfg = load("chipbench/configs/granite_4_0_h_micro.json")
    sizes = {}
    for name, (shape, _) in hybrid_ssm_ref.param_spec(cfg).items():
        n = 1
        for s in shape:
            n *= s
        sizes[name] = n
    mamba = sum(v for k, v in sizes.items() if k.startswith("layer0_"))
    attention = sum(v for k, v in sizes.items() if k.startswith("layer5_"))
    assert round(mamba / 1e6, 2) == 76.18
    assert round(attention / 1e6, 2) == 60.82
    assert sizes["embed_weight"] == 100352 * 2048
    assert round(sum(sizes.values()) / 1e6, 1) == 952.0


def test_flops_against_hand_worked_numbers():
    cfg = load("chipbench/configs/granite_4_0_h_micro.json")
    assert flops_ssm.layer_kinds(cfg) == (9, 1)
    assert flops_ssm.layer_weights(cfg, "mamba") == \
        2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192
    assert flops_ssm.layer_weights(cfg, "attention") == \
        2048 * 3072 + 2048 * 2048 + 3 * 2048 * 8192
    f, b = flops_ssm.scan_forward_cost(1, 32768, cfg)
    pairs = 128 * 256 * 257 // 2        # (i, j <= i) of 128 chunks
    assert f == 2.0 * pairs * 128 + 64 * (2.0 * pairs * 64
                                          + 4.0 * 32768 * 64 * 128)
    assert f == pytest.approx(104.3e9, rel=1e-3)
    assert b == 32768 * ((2 * 4096 + 256) * 2 + 2 * 64 * 4)
    fb, bb = flops_ssm.scan_backward_cost(1, 32768, cfg)
    assert fb == 2 * f and bb == 32768 * ((3 * 4096 + 512) * 2 + 4 * 64 * 4)
    # memory bounds the scan forward, and barely the backward
    assert flops.roofline_seconds(f, b, PEAKS) == pytest.approx(b / 819e9)
    assert flops.roofline_seconds(fb, bb, PEAKS) == pytest.approx(bb / 819e9)
    g, gb = flops_ssm.grouped_forward_cost(1, 32768, cfg)
    assert g == 32 * (32768 * 32769 // 2) * 4 * 64
    assert g / 32768 == pytest.approx(134e6, rel=1e-2)   # a token
    assert gb == 32768 * (2 * 32 + 2 * 8) * 64 * 2
    assert flops_ssm.grouped_backward_cost(1, 32768, cfg) == (2 * g, 2 * gb)
    step = flops_ssm.train_flops_per_step(cfg, 1, 32768)
    assert step == pytest.approx(203e12, rel=1e-2)
    head = 2.0 * 32767 * 2048 * 100352
    assert step == 3 * (2.0 * 32768 * (9 * 76152832 + 60817408) + 9 * f + g
                        + head)


def _obs(events=(), text="", busy_s=1.0, **more):
    cfg = load("chipbench/configs/granite_4_0_h_micro.json")
    return dict({"kind": "train", "chips": 1, "step_text": text, "cfg": cfg,
                 "batch": 1, "seq": 32768, "steps": 20, "window_s": 45.0,
                 "peaks": PEAKS,
                 "trace": {"by_name": {}, "events": list(events),
                           "busy_s": busy_s, "devices": None}}, **more)


TEXT = """
ENTRY %main () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/mixer_/ssm/ssm_conv/mul"}
  %custom-call.2 = f32[8]{0} custom-call(), metadata={op_name="jit(step)/jvp(net0)/layer1_/mixer_/ssm/ssm_scan/ssd_scan_fwd/pallas_call"}
  %custom-call.3 = f32[8]{0} custom-call(), metadata={op_name="jit(step)/transpose(jvp(net0))/layer1_/mixer_/ssm/ssm_scan/ssd_scan_bwd/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/mixer_/ssm/reduce_sum"}
  %custom-call.5 = f32[8]{0} custom-call(), metadata={op_name="jit(step)/jvp(net0)/layer5_/mixer_/attention/flash_grouped_fwd/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name="jit(step)/jvp(net0)/layer1_/ffn_/dot_general"}
}
"""


def test_new_readers_on_a_made_up_trace(monkeypatch):
    one = [("fusion.1", 0.0, 0.002), ("custom-call.2", 0.002, 0.012),
           ("custom-call.3", 0.012, 0.032), ("fusion.4", 0.032, 0.040),
           ("custom-call.5", 0.040, 0.240), ("fusion.6", 0.240, 1.0)]
    events = one + [(n, s + 1.0, e + 1.0) for n, s, e in one]
    obs = _obs(events, TEXT, busy_s=2.0)
    cfg = obs["cfg"]

    def least(*costs):
        return sum(flops.roofline_seconds(*cost(1, 32768, cfg), PEAKS)
                   for cost in costs)

    # two steps traced: 0.060 s under ``ssm_scan`` against nine layers'
    # least time, 0.400 s under ``attention`` against the one layer's
    assert run.load_reader("ssd_roofline_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 9 * 2 * least(
            flops_ssm.scan_forward_cost, flops_ssm.scan_backward_cost) / 0.06)
    assert run.load_reader("gqa_roofline_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 2 * least(
            flops_ssm.grouped_forward_cost,
            flops_ssm.grouped_backward_cost) / 0.4)
    assert run.load_reader("ssm_scope_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 0.080 / 2.0)
    assert run.load_reader("ssm_scan_scope_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 0.060 / 2.0)
    assert run.load_reader("ssm_conv_scope_pct.train", METRICS)(obs) == \
        pytest.approx(100.0 * 0.004 / 2.0)
    assert run.load_reader("step_mfu_pct.ssm_train", METRICS)(obs) == \
        pytest.approx(100.0 * 20 * flops_ssm.train_flops_per_step(
            cfg, 1, 32768) / (45.0 * 197e12))
    from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_DISPATCHED", {
        "packed": 0, "flash": 0, "latent": 0, "eva": 0, "grouped": 3,
        "xla": 1})
    assert run.load_reader("attention_grouped_pct.train", METRICS)(obs) == 75.0
    monkeypatch.setattr(nn_ops, "_SSM_SCANS", {"kernel": 9, "xla": 3})
    assert run.load_reader("ssm_scan_kernel_pct.train", METRICS)(obs) == 75.0


def test_new_readers_return_nothing_where_there_is_nothing_to_read(
        monkeypatch):
    """A program from before this configuration (no ``grouped`` path, no
    scan counter, no ``ssm`` scope), another configuration, a rehearsal."""
    from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_DISPATCHED", {"packed": 2, "xla": 0})
    monkeypatch.delattr(nn_ops, "ssm_scan_stats")
    bare = _obs(text="ENTRY %main () -> f32[] {\n}\n")
    eva = dict(bare, cfg=load("chipbench/configs/evabyte.json"))
    for name in sorted(NEW - {"step_mfu_pct.ssm_train"}):
        assert run.load_reader(name, METRICS)(bare) is None, name
    for name in ("step_mfu_pct.ssm_train", "ssd_roofline_pct.train",
                 "gqa_roofline_pct.train"):
        assert run.load_reader(name, METRICS)(eva) is None, name
    assert run.load_reader("step_mfu_pct.ssm_train", METRICS)(
        dict(bare, peaks=None)) is None
