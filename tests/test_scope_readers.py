"""The names the program writes on its traced programs, and the benchmark's
readers of them (``chipbench/scopes.py``, ``chipbench/layer_metrics/``).

Two halves. The program's half: a step traced through ``ShardedTrainer``
carries ``attention`` (forward and transposed), ``optimizer`` and every
block's name in its op metadata, whatever implements the attention; a
``CachedOp``'s module is named after the op. The readers' half: each new
per-layer reader on a synthetic ``obs`` (a ten-instruction ``step_text``
and a ``by_name`` made by hand), including where it must return ``None``.
Nothing here gives a device time: the seconds are made up.
"""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, nd, parallel  # noqa: E402
from mxnet_tpu.base import PROGRAM_SCOPES  # noqa: E402
from mxnet_tpu.cached_op import CachedOp  # noqa: E402
from mxnet_tpu.models.bert import bert_tiny  # noqa: E402
from mxnet_tpu.ops import nn as nn_ops  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402

from chipbench import scopes  # noqa: E402
from chipbench.run import load_reader  # noqa: E402

METRIC_DIR = os.path.join(ROOT, "chipbench", "layer_metrics")


# ---------------------------------------------------------------------------
# the program's half
# ---------------------------------------------------------------------------

class _Step(gluon.HybridBlock):
    """A scalar out of BERT: the trainer's loss is the identity."""

    def __init__(self, bert):
        super().__init__()
        with self.name_scope():
            self.bert = bert

    def hybrid_forward(self, F, tokens, segments, valid):
        _, _, mlm, nsp = self.bert(tokens, segments, valid)
        return F.mean(mlm) + F.mean(nsp)


def _step_locations():
    """``op_name`` of every operation of a tiny BERT step as lowered."""
    net = bert_tiny(vocab_size=100, max_length=128)
    net.initialize(mx.init.Normal(0.02))
    trainer = parallel.ShardedTrainer(
        _Step(net), lambda out, _label: out, "adam",
        {"learning_rate": 1e-4}, mesh=parallel.make_mesh(dp=1))
    rows, seq = 2, 128
    data = (nd.array(np.random.randint(0, 100, (rows, seq)).astype("float32")),
            nd.array(np.zeros((rows, seq), "float32")),
            nd.array(np.full((rows,), 100, "float32")))
    text = trainer.lower_step(
        data, nd.array(np.zeros((rows,), "float32"))).as_text(debug_info=True)
    return set(re.findall(r'"(jit\(step\)/[^"]*)"', text))


@pytest.mark.parametrize("path", ["xla", "flash_interpret"])
def test_traced_step_carries_the_programs_scopes(path, request):
    if path == "flash_interpret":
        request.getfixturevalue("chip_present_interpreted")
    names = _step_locations()
    layers = {}
    for name in names:
        layers.setdefault(scopes.layer_of(name), []).append(name)
    attention = layers[scopes.ATTENTION]
    assert any("/jvp(" in n and "/attention/" in n for n in attention)
    assert any("/transpose(jvp(" in n and "/attention/" in n
               for n in attention)
    assert any(n.startswith("jit(step)/optimizer/")
               for n in layers[scopes.OPTIMIZER])
    # a block's own name, nested under its parents'
    assert any(re.search(r"bertmodel\d+_encoder_layer1_ffn",
                         "/".join(scopes.scope_path(n)))
               for n in layers[scopes.BLOCKS])
    kernels = {k for n in attention
               for k in ("flash_bshd_fwd", "flash_bshd_bwd", "flash_bshd_dq",
                         "flash_bshd_dkv")
               if "/attention/%s/" % k in n}
    # 128 positions are one key block: forward and the one fused backward
    assert kernels == ({"flash_bshd_fwd", "flash_bshd_bwd"}
                       if path == "flash_interpret" else set())
    # the backward rule of the kernels' custom_vjp keeps the call site's scope
    if path == "flash_interpret":
        assert any("transpose(jvp(" in n and "/attention/flash_bshd_bwd/" in n
                   for n in attention)


def test_eager_block_call_enters_no_scope_and_reserved_names_are_kept(
        monkeypatch):
    import jax
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    dense = gluon.nn.Dense(4, in_units=4, prefix="attention_")
    dense.initialize()
    assert dense.name in PROGRAM_SCOPES
    x = nd.ones((2, 4))
    dense(x)                                    # eager: op by op, no scope
    assert entered == []
    jax.jit(lambda v: dense(nd.NDArray(v))._data)(x._data)
    assert entered == ["attention_"]            # never the dispatcher's word


@pytest.mark.parametrize("name,module", [
    ("lm0.decode", "jit_lm0.decode"),
    ("my net/prefix insert", "jit_my_net_prefix_insert"),
])
def test_cached_op_module_is_named_after_the_op(name, module):
    op = CachedOp(lambda a: a * 2, name=name)
    op(nd.ones((3,)))
    sig, = op.signatures()
    text = op.lower(sig).as_text()
    assert "module @%s " % module in text
    assert "jit_pure" not in text


# ---------------------------------------------------------------------------
# the readers' half: a step text of ten instructions
# ---------------------------------------------------------------------------

def _line(name, shape, rest, op_name=None):
    meta = ', metadata={op_name="%s" source_file="x.py"}' % op_name \
        if op_name else ""
    return "  %%%s = %s %s%s" % (name, shape, rest, meta)


NET = "jit(step)/jvp(net0)/net0_layer0"
NET_T = "jit(step)/transpose(jvp(net0))/net0_layer0"
STEP_TEXT = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    # a forward matmul that holds a scalar of the optimizer's: no work
    "%fused_computation.1 (a.1: bf16[8,128]) -> bf16[8,128] {",
    _line("dot.1", "bf16[8,128]{1,0}", "dot(%a.1, %a.1)",
          NET + "/net0_layer0_ffn/dot_general"),
    _line("power.1", "f32[]", "power(%c.1, %c.2)", "jit(step)/optimizer/pow"),
    "}",
    # a weight-gradient matmul with the update fused in: the matmul names it
    "%fused_computation.5 (a.5: bf16[8,128]) -> (bf16[8,128], bf16[8,128]) {",
    _line("dot.5", "bf16[8,128]{1,0}", "dot(%a.5, %a.5)",
          NET_T + "/net0_layer0_ffn/dot_general"),
    _line("multiply.5", "bf16[8,128]{1,0}", "multiply(%dot.5, %dot.5)",
          "jit(step)/optimizer/mul"),
    "  ROOT %tuple.5 = (bf16[8,128], bf16[8,128]) tuple(%dot.5, %multiply.5)",
    "}",
    "ENTRY %main.1 (p0: bf16[8,128]) -> bf16[8,128] {",
    _line("p0", "bf16[8,128]{1,0}", "parameter(0)", "params[0]"),
    _line("fusion.1", "bf16[8,128]{1,0}",
          "fusion(%p0), kind=kOutput, calls=%fused_computation.1",
          NET + "/net0_layer0_ffn/dot_general"),
    _line("divide_subtract_fusion.5", "(bf16[8,128], bf16[8,128])",
          "fusion(%fusion.1), kind=kOutput, calls=%fused_computation.5",
          NET_T + "/net0_layer0_ffn/dot_general"),
    _line("copy.3", "bf16[8,128]{1,0}", "copy(%fusion.1)",
          NET + "/attention/reshape;" + NET + "/squeeze"),
    _line("fusion.2", "bf16[8,128]{1,0}", "fusion(%copy.3), kind=kLoop",
          NET + "/attention/jit(_where)/select_n"),
    _line("fusion.3", "bf16[8,128]{1,0}", "fusion(%fusion.2), kind=kLoop",
          NET_T + "/attention/mul"),
    _line("pad_add_fusion.1", "bf16[8,128]{1,0}",
          "fusion(%fusion.3), kind=kLoop", NET_T + "/add_any"),
    _line("divide_subtract_fusion.4", "bf16[8,128]{1,0}",
          "fusion(%pad_add_fusion.1), kind=kOutput",
          "jit(step)/optimizer/sub"),
    _line("copy-start.9", "(bf16[8,128]{1,0}, u32[])", "copy-start(%p0)"),
    _line("convert.7", "f32[]", "convert(%p0)", "lr"),
    "  ROOT %tuple.1 = (bf16[8,128]) tuple(%divide_subtract_fusion.4)",
    "}"])
# seconds over two traced steps, made up: 10 busy in all
BY_NAME = {"fusion.1": 3.0, "divide_subtract_fusion.5": 1.0, "copy.3": 0.5, "fusion.2": 1.5, "fusion.3": 1.0,
           "pad_add_fusion.1": 0.5, "divide_subtract_fusion.4": 2.0,
           "copy-start.9": 0.3, "convert.7": 0.2}
CFG = {"hidden_size": 128, "num_hidden_layers": 1}
PEAKS = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}


def _obs(**changes):
    events = [(name, float(i), float(i) + 0.1)
              for i in range(2) for name in BY_NAME]
    obs = {"kind": "train", "step_text": STEP_TEXT, "cfg": CFG,
           "peaks": PEAKS, "batch": 8, "seq": 128, "chips": 1, "spans": [],
           "trace": {"by_name": dict(BY_NAME), "busy_s": 10.0,
                     "window_s": 10.0, "events": events,
                     "custom_calls": set()}}
    obs.update(changes)
    return obs


def test_op_names_and_layers_of_the_synthetic_step():
    names = scopes.op_names(STEP_TEXT)
    assert "copy-start.9" not in names and names["convert.7"] == "lr"
    layers = {n: scopes.layer_of(names.get(n)) for n in BY_NAME}
    assert layers == {
        "fusion.1": "blocks", "divide_subtract_fusion.5": "blocks",
        "copy.3": "attention", "fusion.2": "attention",
        "fusion.3": "attention", "pad_add_fusion.1": "blocks",
        "divide_subtract_fusion.4": "optimizer", "copy-start.9": "unscoped",
        "convert.7": "unscoped"}
    assert scopes.seconds_by_layer(_obs()) == {
        "attention": 3.0, "optimizer": 2.0, "blocks": 4.5, "unscoped": 0.5}
    assert scopes.steps_traced(_obs(), "attention") == 2.0
    assert scopes.layers_held(STEP_TEXT) == {
        "fusion.1": {"blocks"},
        "divide_subtract_fusion.5": {"blocks", "optimizer"}}


@pytest.mark.parametrize("op_name,path", [
    ("jit(step)/transpose(jvp(a))/b/attention/mul", ["a", "b", "attention"]),
    ("jit(step)/jvp(a)/attention/flash_bshd_fwd/pallas_call",
     ["a", "attention", "flash_bshd_fwd"]),
    ("jit(step)/jvp(a)/b/jit(_where)/select_n", ["a", "b"]),
    ("jit(step)/jvp()/mul", []),
    ("jit(step)/while/body/mul", []),
    ("jit(step)/jvp(my_attention)/mul", ["my_attention"]),
    ("jit(step)/jvp(a)/...qd,...kd->...qk/dot_general", ["a"]),
    ("params[5]", []),
    ("", []),
])
def test_scope_path(op_name, path):
    assert scopes.scope_path(op_name) == path


@pytest.mark.parametrize("metric,expected", [
    ("attention_scope_pct.train", 30.0),
    ("optimizer_scope_pct.train", 20.0),
    ("unscoped_device_pct.train", 5.0),
    # the update that stands alone (20) and the matmul it rides in (10)
    ("optimizer_fused_pct.train", 30.0),
])
def test_scope_share_readers(metric, expected):
    read = load_reader(metric, METRIC_DIR)
    assert read(_obs()) == pytest.approx(expected)
    assert read(_obs(trace=None)) is None            # an untraced rehearsal
    assert read(_obs(step_text=None)) is None
    assert read(_obs(kind="serve")) is None
    # a program from before the scopes: nothing can be put down to a layer
    bare = STEP_TEXT.replace("/attention/", "/").replace(
        "/optimizer/", "/jvp(...qd,...kd->...qk)/")
    assert read(_obs(step_text=bare)) is None


def test_attention_roofline_reads_plain_xla_ops_under_the_scope():
    """No kernel, no ``tpu_custom_call`` and no kernel's name anywhere in
    the text: the roofline is of the work, not of what implements it."""
    assert "custom_call" not in STEP_TEXT and "flash" not in STEP_TEXT
    read = load_reader("attention_roofline_pct.train", METRIC_DIR)
    # one layer, 8 rows of 128: forward 4*8*128*128*128 FLOPs over 1e9/s,
    # backward twice that; bytes are far below; two steps; 3.0 s under scope
    least = 3 * 4.0 * 8 * 128 * 128 * 128 / 1e9
    assert read(_obs()) == pytest.approx(100.0 * least * 2 / 3.0)
    assert read(_obs(peaks=None)) is None
    assert read(_obs(trace=None)) is None
    assert read(_obs(kind="serve")) is None
    no_attention = STEP_TEXT.replace("/attention/", "/elsewhere/")
    assert read(_obs(step_text=no_attention)) is None


class _Chip:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,expected", [
    ({"bytes_in_use": 2059794432, "peak_bytes_in_use": 2326938624,
      "bytes_reserved": 13573783552, "peak_bytes_reserved": 13573783552,
      "bytes_limit": 16900000000}, 94.09),
    ({"bytes_in_use": 50, "peak_bytes_in_use": 60, "bytes_limit": 100}, None),
    (None, None),
])
def test_device_memory_held_reader(stats, expected, monkeypatch):
    from mxnet_tpu.observability import telemetry
    monkeypatch.setattr(telemetry, "_accel_devices", lambda: [_Chip(stats)])
    monkeypatch.setattr(telemetry, "_mem_peak", {})
    read = load_reader("device_memory_held_pct.train", METRIC_DIR)
    got = read(_obs())
    assert got is None if expected is None \
        else got == pytest.approx(expected, abs=0.01)
    assert read(_obs(kind="serve")) is None


def test_scheduler_self_time_reader():
    read = load_reader("scheduler_self_ms_p50.serve", METRIC_DIR)
    spans = [
        ("generation.iteration", 0.000, 0.100, {"admits": 1, "live": 2}),
        ("generation.prefill", 0.010, 0.040, {}),
        ("generation.step", 0.045, 0.090, {}),
        ("generation.emit", 0.091, 0.096, {}),
        ("generation.iteration", 0.100, 0.160, {"admits": 0, "live": 3}),
        ("generation.step", 0.102, 0.150, {}),
        ("generation.iteration", 0.160, 0.200, {"admits": 0, "live": 3}),
        ("generation.step", 0.170, 0.199, {}),
    ]
    # self times 20, 12 and 11 ms: the median
    assert read({"kind": "serve", "spans": spans}) == pytest.approx(12.0)
    assert read({"kind": "serve", "spans": spans[1:4]}) is None
    assert read({"kind": "train", "spans": spans}) is None


@pytest.mark.parametrize("path,expected", [
    ("fused", 100.0), ("split", 0.0), ("both", 50.0), ("no_counter", None),
    ("nothing_traced", None)])
def test_attention_fused_bwd_reader(path, expected, monkeypatch, request):
    """``attention_fused_bwd_pct.train`` over ``flash_backward_stats()``:
    a tiny BERT step through the packed kernels at 128 positions (one key
    block) reads 100; a backward over two key blocks reads 0; a program
    without the counter (the parent), or one that traced no head-fused
    backward (the XLA path), reads None."""
    monkeypatch.setattr(pk, "_BACKWARDS", dict.fromkeys(pk._BACKWARDS, 0))
    if path in ("fused", "both"):
        request.getfixturevalue("chip_present_interpreted")
        _step_locations()
        assert pk.flash_backward_stats() == {"fused": 2, "split": 0}
    elif path == "nothing_traced":
        _step_locations()                       # the composed softmax
    if path in ("split", "both"):
        import jax
        import jax.numpy as jnp
        for _ in range(2 if path == "both" else 1):
            jax.make_jaxpr(jax.grad(lambda a: jnp.sum(
                pk.flash_attention_packed(a, 2, None, None, False, 0.0)
                .astype(jnp.float32))))(
                    jax.ShapeDtypeStruct((1, 1024, 768), jnp.bfloat16))
    if path == "no_counter":
        monkeypatch.delattr(pk, "flash_backward_stats")
    read = load_reader("attention_fused_bwd_pct.train", METRIC_DIR)
    assert read(_obs()) == expected
    assert read(_obs(kind="serve")) is None


@pytest.mark.parametrize("path,expected", [
    ("fused", 100.0), ("split", 0.0), ("no_counter", None),
    ("nothing_traced", None)])
def test_latent_fused_bwd_reader(path, expected, monkeypatch):
    """``latent_fused_bwd_pct.train`` over ``latent_backward_stats()``: a
    latent backward traced at widths whose dQ^T fits VMEM reads 100; the
    dq + dkv pair alone reads 0; a program without the counter (the
    parent), or one that traced no latent backward (the BERT cells), reads
    None."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(pk, "_LATENT_BACKWARDS",
                        dict.fromkeys(pk._LATENT_BACKWARDS, 0))
    if path == "split":
        monkeypatch.setattr(pk, "_LATENT_VMEM_BUDGET", 0)
    if path in ("fused", "split"):
        jax.make_jaxpr(jax.grad(lambda a, b, c, d: jnp.sum(
            pk.flash_attention_latent(a, b, c, d, 2, True)
            .astype(jnp.float32))))(*(
                jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
                    (1, 256, 256), (1, 256, 2, 64), (1, 256, 512),
                    (1, 256, 64))))
    if path == "no_counter":
        monkeypatch.delattr(pk, "latent_backward_stats")
    read = load_reader("latent_fused_bwd_pct.train", METRIC_DIR)
    assert read(_obs()) == expected
    assert read(_obs(kind="serve")) is None


# the expert layer's inner scopes as the compiled step writes them: forward
# and transposed, inside the buffer-size switch's branch and outside it
MOE = "jit(step)/jvp(net0)/net0_layer1/moe_/moe_experts"
MOE_T = "jit(step)/transpose(jvp(net0))/net0_layer1/moe_/moe_experts"
MOE_TEXT = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    "%branch_1 (a.1: bf16[8,128]) -> bf16[8,128] {",
    _line("gather.1", "bf16[8,128]{1,0}", "gather(%a.1, %a.1)",
          MOE + "/cond/branch_1_fun/moe_combine/gather"),
    _line("custom-call.1", "bf16[8,128]{1,0}", "custom-call(%gather.1)",
          MOE + "/cond/branch_1_fun/moe_combine/jit(tgmm)/pallas_call"),
    _line("custom-call.2", "bf16[8,128]{1,0}", "custom-call(%a.1)",
          MOE_T + "/cond/branch_1_fun/transpose(jvp(moe_products))/jit(gmm)"
          "/pallas_call"),
    _line("fusion.7", "bf16[8,128]{1,0}", "fusion(%a.1), kind=kLoop",
          MOE + "/cond/branch_1_fun/mul"),
    "}",
    "ENTRY %main.1 (p0: bf16[8,128]) -> bf16[8,128] {",
    _line("sort.1", "s32[8]{0}", "sort(%p0)", MOE + "/moe_sort/sort"),
    _line("scatter.1", "s32[8]{0}", "scatter(%sort.1)",
          MOE + "/moe_sort/scatter"),
    _line("conditional.1", "bf16[8,128]{1,0}",
          "conditional(%p0), branch_computations={%branch_1}",
          MOE + "/cond"),
    "}"])


@pytest.mark.parametrize("metric,expected", [
    ("moe_combine_scope_pct.train", 30.0),      # gather.1 + custom-call.1
    ("moe_products_scope_pct.train", 20.0),     # custom-call.2
    ("moe_sort_scope_pct.train", 15.0),         # sort.1 + scatter.1
    ("moe_scope_pct.train", 75.0),              # the conditional holds 60
])
def test_expert_layer_scope_readers(metric, expected):
    """The three scopes inside ``moe_experts``: an instruction in a branch
    of the buffer-size switch runs inside the ``conditional``'s own event,
    and is found by its scope there; a program that writes none of the
    three (the parent of PR 32) reads None."""
    events = [("sort.1", 0.0, 1.0), ("scatter.1", 1.0, 1.5),
              ("conditional.1", 2.0, 8.0), ("gather.1", 2.0, 3.0),
              ("custom-call.1", 3.0, 5.0), ("custom-call.2", 5.0, 7.0),
              ("fusion.7", 7.0, 8.0)]
    obs = _obs(step_text=MOE_TEXT)
    obs["trace"].update(events=events, busy_s=10.0)
    read = load_reader(metric, METRIC_DIR)
    assert read(obs) == pytest.approx(expected)
    assert read(_obs(kind="serve")) is None
    assert read(_obs(trace=None)) is None
    if metric != "moe_scope_pct.train":
        before = re.sub(r"/(transpose\(jvp\()?moe_(combine|products|sort)\)*",
                        "", MOE_TEXT)
        obs["step_text"] = before
        assert read(obs) is None


@pytest.mark.parametrize("counts,expected", [
    ({"grouped": 30, "xla": 0}, 100.0), ({"grouped": 0, "xla": 30}, 0.0),
    ({"grouped": 0, "xla": 0}, None), (None, None)])
def test_moe_combine_grouped_reader(counts, expected, monkeypatch):
    """``moe_combine_grouped_pct.train`` over ``combine_stats()``: every sum
    over a token's rows the grouped product reads 100, ``segment_sum`` alone
    0; a program without the counter (the parent), or one that traced no
    expert layer (the other cells), reads None."""
    from mxnet_tpu.parallel import moe
    if counts is None:
        monkeypatch.delattr(moe, "combine_stats")
    else:
        monkeypatch.setattr(moe, "_COMBINES", counts)
    read = load_reader("moe_combine_grouped_pct.train", METRIC_DIR)
    assert read(_obs()) == expected
    assert read(_obs(kind="serve")) is None


@pytest.mark.parametrize("counts,expected", [
    ({"kernel": 27, "xla": 0}, 100.0), ({"kernel": 0, "xla": 27}, 0.0),
    ({"kernel": 9, "xla": 3}, 75.0), ({"kernel": 0, "xla": 0}, None),
    (None, None)])
def test_ssm_conv_kernel_reader(counts, expected, monkeypatch):
    """``ssm_conv_kernel_pct.train`` over ``ssm_conv_stats()``: every
    convolution through the kernels reads 100, the XLA form alone 0; a
    program without the counter (the parent), or one that traced no mixer
    (the other cells), reads None. Its index entry names the mixer's layer
    and the one cell that builds a mixer."""
    import json
    if counts is None:
        monkeypatch.delattr(nn_ops, "ssm_conv_stats")
    else:
        monkeypatch.setattr(nn_ops, "_SSM_CONVS", counts)
    read = load_reader("ssm_conv_kernel_pct.train", METRIC_DIR)
    assert read(_obs()) == expected
    assert read(_obs(kind="serve")) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "ssm_conv_kernel_pct.train"]
    assert entries == [{
        "name": "ssm_conv_kernel_pct.train", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "state-space mixer",
        "moves": "train_tokens_per_s",
        "workloads": ["granite_4_0_h_micro_train_s32768"]}]


HEAD = "jit(step)/jvp(net0)/loss_head"
HEAD_TEXT = "\n".join([
    "HloModule jit_step, is_scheduled=true",
    "%body.1 (a.1: bf16[8,128]) -> bf16[8,128] {",
    _line("fusion.4", "f32[8,128]{1,0}", "fusion(%a.1), kind=kOutput",
          HEAD + "/while/body/closed_call/dot_general"),
    _line("convolution_add_fusion.1", "f32[128,128]{1,0}",
          "fusion(%fusion.4), kind=kOutput",
          HEAD + "/while/body/closed_call/add"),
    "}",
    "ENTRY %main.1 (p0: bf16[8,128]) -> bf16[8,128] {",
    _line("fusion.1", "bf16[8,128]{1,0}", "fusion(%p0), kind=kOutput",
          NET + "/net0_layer0_ffn/dot_general"),
    _line("while.1", "(bf16[8,128])", "while(%fusion.1), body=%body.1",
          HEAD + "/while"),
    _line("fusion.9", "bf16[8,128]{1,0}", "fusion(%while.1), kind=kLoop",
          "jit(step)/transpose(jvp(net0))/loss_head/mul"),
    "}"])


def test_loss_head_scope_reader():
    """``head_scope_pct.train``: the head's loop with the instructions that
    run inside its event counted once, and the backward rule's scaling;
    a program from before the scope (the parent of PR 36) reads None. Its
    index entry names the three cells whose loss is the chunked head."""
    import json
    events = [("fusion.1", 0.0, 5.0), ("while.1", 5.0, 8.0),
              ("fusion.4", 5.0, 6.0), ("convolution_add_fusion.1", 6.0, 8.0),
              ("fusion.9", 8.0, 8.5)]
    obs = _obs(step_text=HEAD_TEXT)
    obs["trace"].update(events=events, busy_s=10.0)
    read = load_reader("head_scope_pct.train", METRIC_DIR)
    assert read(obs) == pytest.approx(35.0)
    assert read(_obs(kind="serve")) is None
    assert read(_obs(trace=None)) is None
    assert read(_obs()) is None
    obs["step_text"] = HEAD_TEXT.replace("/loss_head", "").replace(
        "loss_head)", ")")
    assert read(obs) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] == "head_scope_pct.train"]
    assert entries == [{
        "name": "head_scope_pct.train", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "loss head",
        "moves": "train_tokens_per_s",
        "workloads": ["kimi_vl_a3b_train_s8192", "evabyte_train_s32768",
                      "granite_4_0_h_micro_train_s32768"]}]


@pytest.mark.parametrize("path,expected", [
    ("flash_interpret", 100.0), ("xla", 0.0), ("no_counter", None),
    ("nothing_traced", None)])
def test_attention_packed_reader_on_a_tiny_traced_step(path, expected,
                                                       monkeypatch, request):
    """``attention_packed_pct.train`` over the dispatcher's own counter:
    every attention of a tiny BERT step traced through the packed kernels
    reads 100, through the composed softmax 0; a program without the
    counter (the parent), or one that traced no attention, reads None."""
    monkeypatch.setattr(nn_ops, "_DISPATCHED",
                        dict.fromkeys(nn_ops._DISPATCHED, 0))
    if path == "flash_interpret":
        request.getfixturevalue("chip_present_interpreted")
    if path != "nothing_traced":
        _step_locations()
        counts = nn_ops.attention_dispatch_stats()
        assert sum(counts.values()) >= 2        # bert_tiny: two layers
    if path == "no_counter":
        monkeypatch.delattr(nn_ops, "attention_dispatch_stats")
    read = load_reader("attention_packed_pct.train", METRIC_DIR)
    assert read(_obs()) == expected
    assert read(_obs(kind="serve")) is None


@pytest.mark.parametrize("path", ["xla", "packed_interpret"])
def test_prefill_through_the_packed_entry_equals_the_split(path, monkeypatch,
                                                           request):
    """``TransformerLM.prefill`` (``forward_kv`` in every block) hands the
    packed projection to the attention and slices k and v for the arena
    only: the same next tokens, the same k and v as the split it replaced.
    On the CPU the packed entry IS that split (bit for bit); with the
    packed kernels interpreted the k/v are still the projection's own
    slices and the tokens agree."""
    from mxnet_tpu.models.transformer import (MultiHeadAttention,
                                              TransformerLM)
    # heads x head_dim = 128: the narrowest the head-fused kernels take
    net = TransformerLM(50, units=128, num_layers=2, num_heads=2,
                        max_len=256)
    net.initialize(mx.init.Normal(0.5))
    rows, seq = 2, 128
    tokens = nd.array(np.random.RandomState(7).randint(0, 50, (rows, seq)),
                      dtype="int32")
    lengths = nd.array(np.array([100, 128]), dtype="int32")

    def split_forward_kv(self, x, kv_mask=None):
        B, T, C = x.shape
        q, k, v = self._split_qkv(x)
        out = nd._contrib_dot_product_attention(
            q, k, v, mask=kv_mask, dropout=self._dropout,
            causal=self._causal, layout="BSHD")
        return self.proj(out.reshape((B, T, C))), k, v

    packed = MultiHeadAttention.forward_kv
    monkeypatch.setattr(MultiHeadAttention, "forward_kv", split_forward_kv)
    want_logits, want_cache = net.prefill(tokens, lengths)
    monkeypatch.setattr(MultiHeadAttention, "forward_kv", packed)
    before = nn_ops.attention_dispatch_stats()
    if path == "packed_interpret":
        request.getfixturevalue("chip_present_interpreted")
    logits, cache = net.prefill(tokens, lengths)
    took = {k: v - before[k]
            for k, v in nn_ops.attention_dispatch_stats().items()}
    assert took == {"packed": net.num_layers if path != "xla" else 0,
                    "flash": 0, "latent": 0, "eva": 0, "grouped": 0,
                    "xla": net.num_layers if path == "xla" else 0}

    np.testing.assert_array_equal(logits.asnumpy().argmax(-1),
                                  want_logits.asnumpy().argmax(-1))
    if path == "xla":
        np.testing.assert_array_equal(logits.asnumpy(),
                                      want_logits.asnumpy())
    for layer, ((k, v), (want_k, want_v)) in enumerate(
            zip(cache, want_cache)):
        assert k.shape == (rows, seq, net.num_heads, net.head_dim)
        # past the first layer k/v inherit the attention's rounding
        exact = path == "xla" or layer == 0
        for got, want in ((k, want_k), (v, want_v)):
            if exact:
                np.testing.assert_array_equal(got.asnumpy(), want.asnumpy())
            else:
                np.testing.assert_allclose(got.asnumpy(), want.asnumpy(),
                                           atol=2e-3, rtol=2e-3)
