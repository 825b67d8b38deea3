"""The byte-level decoder with EVA attention (``models/eva_lm.py``) against
its plain reference (``chipbench/configs/eva_lm_ref.py``, which imports
nothing of the program), at a small size on the CPU: loss and every leaf's
gradient; the XLA form and the EVA flash kernels (interpret mode) against
each other and against a query-by-query oracle; chunks of one position as
plain causal attention; the dispatcher's rows; recomputation; the
multi-byte loss; the norm's unit offset."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.configs import eva_lm, eva_lm_ref as ref
from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
from mxnet_tpu.parallel.functional import functionalize

TINY = dict(
    vocab_size=32, hidden_size=64, intermediate_size=160,
    num_hidden_layers=2, num_attention_heads=4, window_size=32, chunk_size=4,
    num_pred_heads=2, rope_theta=100000, rms_norm_eps=1e-5, init_std=0.05,
    loss_chunk=16, param_dtype="float32",
    optimizer={"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
               "beta2": 0.999, "epsilon": 1e-8})
SEQ = 160           # 5 windows


def _tokens(rows=2, seq=SEQ, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"],
                                                (rows, seq))


def _program_loss_and_grads(cfg, toks, seed=7):
    """Through ``functionalize``, as the trainer calls the model."""
    net, names = eva_lm.build_net(cfg, seed, "float32")
    pure, params = functionalize(net, train=True)
    values = [p.data()._data for p in params]
    tokens, labels = eva_lm.as_program_batch(toks, cfg["num_pred_heads"])

    def loss(v):
        outs, _ = pure(jax.random.PRNGKey(0), v, jnp.asarray(tokens),
                       jnp.asarray(labels))
        return outs[0]

    value, grads = jax.jit(jax.value_and_grad(loss))(values)
    short = {full: s for s, full in names.items()}
    return float(value), {short[p.name]: g for p, g in zip(params, grads)}


@pytest.fixture(scope="module")
def model_and_reference():
    toks = _tokens()
    value, grads = _program_loss_and_grads(TINY, toks)
    weights = ref.make_params(TINY, 7, "float32")
    ref_value, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, toks, TINY))(weights)
    return {"loss": value, "grads": grads, "ref_loss": float(ref_value),
            "ref_grads": ref_grads, "weights": weights, "tokens": toks}


def test_loss_matches_the_reference(model_and_reference):
    m = model_and_reference
    assert m["loss"] == pytest.approx(m["ref_loss"], rel=1e-6)
    assert m["loss"] == pytest.approx(np.log(32), rel=0.05)


@pytest.mark.parametrize("leaf", sorted(ref.param_spec(TINY)))
def test_gradient_matches_the_reference(model_and_reference, leaf):
    got = model_and_reference["grads"][leaf]
    want = model_and_reference["ref_grads"][leaf]
    assert float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) <= \
        2e-5 * float(jnp.linalg.norm(want))


def test_the_reference_walked_a_layer_at_a_time_is_the_whole(
        model_and_reference):
    m = model_and_reference
    value, grads = ref.loss_and_gradient(m["weights"], m["tokens"], TINY)
    assert value == pytest.approx(m["ref_loss"], rel=1e-6)
    for k, want in m["ref_grads"].items():
        assert float(jnp.linalg.norm(grads[k] - want)) <= \
            1e-5 * float(jnp.linalg.norm(want)), k


def test_recomputation_gives_the_same_gradients_to_the_last_bit(
        model_and_reference):
    value, grads = _program_loss_and_grads(
        dict(TINY, recompute=True), model_and_reference["tokens"])
    assert value == model_and_reference["loss"]
    for k, want in model_and_reference["grads"].items():
        assert np.array_equal(np.asarray(grads[k]), np.asarray(want)), k


def test_recomputed_layers_are_checkpoints_in_the_traced_program():
    def text(recompute):
        net, _ = eva_lm.build_net(dict(TINY, recompute=recompute), 7,
                                  "float32")
        pure, params = functionalize(net, train=True)
        tokens, labels = eva_lm.as_program_batch(_tokens(), 2)
        return str(jax.make_jaxpr(jax.grad(lambda v: pure(
            jax.random.PRNGKey(0), v, jnp.asarray(tokens),
            jnp.asarray(labels))[0][0]))([p.data()._data for p in params]))
    assert "remat" not in text(False)
    recomputed = text(True)
    assert recomputed.count("remat2[") == TINY["num_hidden_layers"]
    # besides its input a layer keeps what the kernels tag by name
    assert "save_only_these_names" in recomputed


def test_logits_have_a_row_of_the_vocabulary_for_each_prediction_head():
    net, _ = eva_lm.build_net(TINY, 7, "float32")
    toks = _tokens(rows=1)
    logits = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert logits.shape == (1, SEQ, 2, 32)
    weights = ref.make_params(TINY, 7, "float32")
    x = weights["embed_weight"][jnp.asarray(toks[0])]
    for i in range(TINY["num_hidden_layers"]):
        x = ref.layer(ref.layer_leaves(weights, i), x, TINY)
    want = ref.rms_norm1(x, weights["norm_offset"], 1e-5) \
        @ weights["head_weight"].T
    np.testing.assert_allclose(logits[0].reshape(SEQ, 64), want, atol=2e-5)


@pytest.mark.parametrize("fault", ref.FAULTS + ("fp8",))
def test_planted_faults_and_the_control_move_the_reference(
        model_and_reference, fault):
    kw = {"precision": "fp8"} if fault == "fp8" else {"fault": fault}
    m = model_and_reference
    broken = float(ref.loss(m["weights"], m["tokens"], TINY, **kw))
    assert abs(broken - m["ref_loss"]) > 1e-5 * m["ref_loss"]


# ---- the attention op: XLA form, kernels, oracle ---------------------------

def _operands(batch, seq, heads, dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v, g = (jax.random.normal(keys[i], (batch, seq, heads * dim),
                                    jnp.float32) for i in range(4))
    mu, phi = (jax.random.normal(keys[4 + i], (heads, dim), jnp.float32)
               for i in range(2))
    return q, k, v, mu, phi, g


def _oracle_row(q, k, v, mu, phi, heads, window, chunk, t):
    """EVA attention's output for ONE query of the first row, written as
    the equations read, in numpy float64."""
    q, k, v, mu, phi = (np.asarray(a, np.float64) for a in (q, k, v, mu, phi))
    dim = q.shape[-1] // heads
    scale = 1.0 / np.sqrt(dim)
    out = np.zeros((heads, dim))
    w = t // window
    for h in range(heads):
        cols = slice(h * dim, (h + 1) * dim)
        keys = [k[0, m, cols] for m in range(w * window, t + 1)]
        vals = [v[0, m, cols] for m in range(w * window, t + 1)]
        for c in range(w * window // chunk):
            kc, vc = (a[0, c * chunk:(c + 1) * chunk, cols] for a in (k, v))

            def soft(vector):
                e = np.exp(scale * kc @ vector - np.max(scale * kc @ vector))
                return e / e.sum()
            keys.append(soft(mu[h]) @ kc)
            vals.append(soft(phi[h]) @ vc)
        s = scale * np.stack(keys) @ q[0, t, cols]
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ np.stack(vals)
    return out.reshape(-1)


@pytest.mark.parametrize("t", [0, 31, 32, 63, 67, 68, 159], ids=[
    "first", "first_window_last", "second_window_first", "window_last",
    "before_chunk_edge", "at_chunk_edge", "last"])
def test_xla_form_matches_the_equations_query_by_query(t):
    q, k, v, mu, phi, _ = _operands(1, SEQ, 2, 16)
    out = nn_ops.xla_eva_attention(q, k, v, mu, phi, 2, 32, 4)
    want = _oracle_row(q, k, v, mu, phi, 2, 32, 4, t)
    np.testing.assert_allclose(out[0, t], want, atol=2e-6)


def test_one_window_is_plain_causal_attention():
    q, k, v, mu, phi, _ = _operands(2, 64, 2, 16)
    out = nn_ops.xla_eva_attention(q, k, v, mu, phi, 2, 64, 4)
    heads = lambda a: a.reshape(2, 64, 2, 16).transpose(0, 2, 1, 3)
    want = nn_ops.xla_attention(heads(q), heads(k), heads(v), causal=True)
    np.testing.assert_allclose(out, want.transpose(0, 2, 1, 3).reshape(
        2, 64, 32), atol=2e-6)


def test_chunks_of_one_position_are_plain_causal_attention():
    """A one-position pool is the position: every earlier window is seen
    exactly, so the whole is causal softmax attention over the sequence."""
    q, k, v, mu, phi, _ = _operands(2, 128, 2, 16)
    out = nn_ops.xla_eva_attention(q, k, v, mu, phi, 2, 32, 1)
    heads = lambda a: a.reshape(2, 128, 2, 16).transpose(0, 2, 1, 3)
    want = nn_ops.xla_attention(heads(q), heads(k), heads(v), causal=True)
    np.testing.assert_allclose(out, want.transpose(0, 2, 1, 3).reshape(
        2, 128, 32), atol=2e-6)


KERNEL_CASES = {
    # name: (batch, seq, heads, window, chunk, blocks)
    "one_window": (1, 512, 1, 512, 4, (256, 128, 128)),
    "windows_of_two_blocks": (2, 2048, 2, 256, 2, (128, 128, 128)),
    "uneven_blocks": (1, 4096, 1, 512, 4, (256, 128, 256)),
    "a_window_a_block": (1, 2048, 1, 512, 4, (512, 256, 128)),
    "chunks_of_one": (1, 512, 1, 128, 1, (128, 128, 256)),
}


@pytest.fixture(scope="module")
def kernel_cases():
    """Output and the five gradients of each case, from the XLA form and
    from the kernels in interpret mode."""
    out = {}
    for name, (batch, seq, heads, window, chunk, blocks) in \
            KERNEL_CASES.items():
        q, k, v, mu, phi, g = _operands(batch, seq, heads, 128)

        def xla(q, k, v, mu, phi):
            return nn_ops.xla_eva_attention(q, k, v, mu, phi, heads, window,
                                            chunk)

        def kernels(q, k, v, mu, phi):
            kt, vt = nn_ops.eva_chunk_summaries(k, v, mu, phi, heads, chunk)
            return pk.flash_attention_eva(q, k, v, kt, vt, heads, window,
                                          blocks, interpret=True)

        sides = []
        for f in (xla, kernels):
            o, pull = jax.vjp(f, q, k, v, mu, phi)
            sides.append((o,) + pull(g))
        out[name] = sides
    return out


@pytest.mark.parametrize("which", range(6),
                         ids=["out", "dq", "dk", "dv", "dmu", "dphi"])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_eva_flash_kernels_match_the_xla_form(kernel_cases, case, which):
    want, got = (side[which] for side in kernel_cases[case])
    assert got.shape == want.shape
    # a pool of one position has nothing to learn
    assert float(jnp.abs(want).max()) > 0 or (
        case in ("chunks_of_one", "one_window") and which >= 4)
    assert float(jnp.abs(got - want).max()) <= \
        5e-6 * max(float(jnp.abs(want).max()), 1.0)


def test_kernels_with_chunks_of_one_are_plain_causal_attention(kernel_cases):
    batch, seq, heads, *_ = KERNEL_CASES["chunks_of_one"]
    q, k, v, *_ = _operands(batch, seq, heads, 128)
    want = nn_ops.xla_attention(q[:, None], k[:, None], v[:, None],
                                causal=True)[:, 0]
    got = kernel_cases["chunks_of_one"][1][0]
    assert float(jnp.abs(got - want).max()) <= 5e-6


def test_tile_plan_counts_the_grid_steps_and_the_live_ones():
    """4 windows of 512 = 2 query blocks x 4 position blocks each, 512
    summaries in 2 blocks of 256 (a window's 128 summaries half a block)."""
    q = jnp.zeros((1, 2048, 128), jnp.float32)
    kt = jnp.zeros((1, 512, 128), jnp.float32)
    plan = pk._EvaPlan(q, kt, 1, 512, (256, 128, 256))
    assert (plan.n_q, plan.n_s, plan.n_w, plan.per_window) == (8, 2, 4, 128)
    assert [int(plan.live_summaries(i)) for i in range(8)] == \
        [0, 0, 128, 128, 256, 256, 384, 384]
    # forward and the backward over its grid: 8 x 6 steps each; live:
    # summaries 0,0,1,1,1,1,2,2 and positions 2,4,2,4,2,4,2,4; dsum: block 0
    # seen by query blocks 2.., block 1 by 6..
    assert plan.tiles() == (2 * 48 + 16, 2 * (8 + 24) + (6 + 2))
    before = pk.eva_tile_stats()
    jax.make_jaxpr(jax.grad(lambda q: pk.flash_attention_eva(
        q, q, q, kt, kt, 1, 512, (256, 128, 256), interpret=True).sum()))(q)
    after = pk.eva_tile_stats()
    assert (after["stepped"] - before["stepped"],
            after["live"] - before["live"]) == plan.tiles()


def _live_by_the_equations(plan, chunk):
    """The count :meth:`_EvaPlan.tiles` gives, from the pairs the equations
    name: query t sees position m of its own window with m <= t, and chunk c
    where the chunk lies in an earlier window."""
    t = np.arange(plan.S)
    c = np.arange(plan.n_sum)
    sees_position = (t[:, None] // plan.W == t[None, :] // plan.W) \
        & (t[None, :] <= t[:, None])
    sees_summary = c[None, :] * chunk // plan.W < t[:, None] // plan.W
    live = 0
    for i in range(plan.n_q):
        rows = slice(i * plan.blk_q, (i + 1) * plan.blk_q)
        by_summary_block = sees_summary[rows].reshape(
            plan.blk_q, plan.n_s, plan.blk_s).any((0, 2))
        by_position_block = sees_position[rows].reshape(
            plan.blk_q, -1, plan.blk_k).any((0, 2))
        # forward + backward sweep, and the dsum grid's column of block i
        live += 2 * (by_summary_block.sum() + by_position_block.sum()) \
            + by_summary_block.sum()
    return int(live)


@pytest.mark.parametrize("seq,window,chunk,blocks", [
    (2048, 512, 4, (256, 128, 256)), (2048, 512, 4, (512, 512, 128)),
    (1024, 256, 2, (128, 256, 128)), (32768, 2048, 16, (1024, 1024, 1024)),
    (32768, 2048, 16, (1024, 1024, 512)), (8192, 2048, 16, (2048, 512, 512))])
def test_the_plans_live_tiles_are_those_that_hold_a_pair_of_the_equations(
        seq, window, chunk, blocks):
    plan = pk._EvaPlan(jnp.zeros((1, seq, 128)),
                       jnp.zeros((1, seq // chunk, 128)), 1, window, blocks)
    stepped, live = plan.tiles()
    assert stepped == 2 * plan.n_q * (plan.n_s + plan.n_w) \
        + plan.n_s * plan.n_q
    assert live == _live_by_the_equations(plan, chunk)


@pytest.mark.parametrize("dead", ["summary_step_live", "position_step_live",
                                  "seen_by_query_block"])
def test_the_kernels_branch_on_the_plans_predicates(monkeypatch, dead):
    """A predicate of the plan declared dead everywhere: the counter and the
    kernels (interpret mode) both lose what it guards, the forward its
    summaries or its window, the backward the matching gradients."""
    q, k, v, mu, phi, g = _operands(1, 512, 1, 128, seed=2)
    kt, vt = nn_ops.eva_chunk_summaries(k, v, mu, phi, 1, 4)

    def run():
        out, pull = jax.vjp(lambda *a: pk.flash_attention_eva(
            *a, 1, 128, (128, 128, 128), interpret=True), q, k, v, kt, vt)
        return (out,) + pull(g)

    plan = pk._EvaPlan(q, kt, 1, 128, (128, 128, 128))
    whole, counted = run(), plan.tiles()
    monkeypatch.setattr(pk._EvaPlan, dead,
                        lambda self, a, b: (a < 0) & (b < 0))
    cut = run()
    assert plan.tiles()[1] < counted[1] and plan.tiles()[0] == counted[0]
    out, dq, dk, dv, dkt, dvt = (
        float(jnp.abs(a - b).max()) for a, b in zip(cut, whole))
    if dead == "summary_step_live":         # the first window only is whole
        assert out > 1e-3 and dq > 1e-3 and float(
            jnp.abs(cut[0][:, :128] - whole[0][:, :128]).max()) == 0
    elif dead == "position_step_live":      # no window, no dk, no dv
        assert out > 1e-3 and float(jnp.abs(cut[2]).max()) == 0 \
            and float(jnp.abs(cut[3]).max()) == 0
    else:                                   # only the summaries' gradients
        assert out == dq == dk == dv == 0
        assert float(jnp.abs(cut[4]).max()) == 0 and dkt > 0 and dvt > 0


# ---- the dispatcher ---------------------------------------------------------

@pytest.mark.parametrize("shape,accelerator,want", [
    ((32768, 128, 2048, 16), True, "eva"),
    ((2048, 128, 2048, 16), True, "eva"),
    ((4096, 256, 512, 16), True, "eva"),
    ((32768, 128, 2048, 16), False, "xla"),     # no accelerator
    ((32768, 64, 2048, 16), True, "xla"),       # head width not of 128
    ((3072, 128, 2048, 16), True, "xla"),       # S not of the window
    ((4096, 128, 192, 16), True, "xla"),        # window not of the block
    ((4096, 128, 2048, 24), True, "xla"),       # window not of the chunk
    ((4096, 128, 1024, 64), True, "xla"),       # 64 summaries: not of 128
], ids=["published", "one_window", "wide_heads", "cpu", "narrow_heads",
        "ragged_sequence", "ragged_window", "ragged_chunk", "few_summaries"])
def test_attention_path_rows_of_the_eva_form(monkeypatch, shape, accelerator,
                                             want):
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: accelerator)
    assert nn_ops._attention_path("eva", shape) == want


def test_dispatcher_counts_the_eva_kernels(chip_present_interpreted):
    q, k, v, mu, phi, _ = _operands(1, 512, 1, 128)
    before = nn_ops.attention_dispatch_stats()
    got = nn_ops.eva_attention.fn(q, k, v, mu, phi, 1, 128, 1)
    after = nn_ops.attention_dispatch_stats()
    assert after["eva"] == before["eva"] + 1 and after["xla"] == before["xla"]
    np.testing.assert_allclose(
        got, nn_ops.xla_eva_attention(q, k, v, mu, phi, 1, 128, 1), atol=5e-6)
    text = str(jax.make_jaxpr(lambda *a: nn_ops.eva_attention.fn(
        *a, 1, 128, 1))(q, k, v, mu, phi))
    assert "flash_eva_fwd" in text


def test_cpu_takes_the_xla_form_and_counts_it():
    q, k, v, mu, phi, _ = _operands(1, 64, 2, 16)
    before = nn_ops.attention_dispatch_stats()
    nn_ops.eva_attention.fn(q, k, v, mu, phi, 2, 32, 4)
    after = nn_ops.attention_dispatch_stats()
    assert after["xla"] == before["xla"] + 1 and after["eva"] == before["eva"]


# ---- the loss and the norm --------------------------------------------------

@pytest.mark.parametrize("head", range(3))
def test_labels_have_no_target_at_a_rows_tail(head):
    toks = _tokens(rows=2, seq=12)
    labels = ref.make_labels(toks, 3)
    assert labels.shape == (2, 12, 3)
    assert (labels[:, 12 - 1 - head:, head] == -1).all()
    assert (labels[:, :12 - 1 - head, head] == toks[:, 1 + head:]).all()


@pytest.mark.parametrize("chunk", [8, 24, 1000])
def test_cross_entropy_a_head_and_a_chunk_at_a_time_matches_the_whole(chunk):
    rng = np.random.default_rng(3)
    hidden = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(3 * 10, 16)), jnp.float32)
    labels = jnp.asarray(ref.make_labels(rng.integers(0, 10, (2, 24)), 3))

    def whole(hidden, weight):
        logp = jax.nn.log_softmax((hidden @ weight.T).reshape(2, 24, 3, 10))
        ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                 -1)[..., 0]
        valid = labels >= 0
        return jnp.mean(-(ll * valid).sum((0, 1)) / valid.sum((0, 1)))

    def chunked(hidden, weight):
        return nn_ops.chunked_softmax_cross_entropy.fn(hidden, weight, labels,
                                                       chunk=chunk)

    want, want_grads = jax.value_and_grad(whole, (0, 1))(hidden, weight)
    got, got_grads = jax.value_and_grad(chunked, (0, 1))(hidden, weight)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("unit_offset", [False, True])
def test_rms_norm_with_and_without_the_unit_offset(unit_offset):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(3, 7, 16)), jnp.float32)
    gamma = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    got = nn_ops.RMSNorm.fn(x, gamma, eps=1e-5, unit_offset=unit_offset)
    want = ref.rms_norm1(x, gamma if unit_offset else gamma - 1.0, 1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    from mxnet_tpu.models.mla_moe import RMSNorm
    block = RMSNorm(16, unit_offset=unit_offset)
    block.initialize()
    assert float(block.gamma.data().asnumpy()[0]) == (0.0 if unit_offset
                                                      else 1.0)


def test_trainer_steps_in_bfloat16_with_recomputed_layers():
    cfg = dict(TINY, param_dtype="bfloat16", recompute=True)
    cell = {"batch": 2, "seq": SEQ, "pool": 2, "dp": 1}
    system = eva_lm.build(cfg, cell, 11, jax.devices()[:1])
    losses = [float(system.step(i).asnumpy()) for i in range(3)]
    assert all(np.isfinite(losses)) and losses[0] == pytest.approx(
        np.log(32), rel=0.1)
    assert all(v.dtype == jnp.bfloat16 for v in system.trainer._values)
    assert set(system.first_gradient_norms()) == set(ref.param_spec(TINY))
    system.close()
