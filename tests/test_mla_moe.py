"""The latent-attention decoder with held experts (``models/mla_moe.py``)
against its plain reference (``chipbench/configs/mla_moe_ref.py``, which
imports nothing of the program), at a small size on the CPU: forward, loss
and every gradient; the latent flash kernels in interpret mode against the
XLA form; the held-expert layer's shares adding up to the uncut layer;
droplessness; the chunked loss; the trainer's step with its counter."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.configs import mla_moe, mla_moe_ref as ref
from mxnet_tpu import parallel
from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
from mxnet_tpu.parallel import moe
from mxnet_tpu.parallel.functional import functionalize

TINY = dict(
    vocab_size=64, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=48, num_hidden_layers=3, num_attention_heads=4,
    n_shared_experts=2, n_routed_experts=4, router_width=16,
    experts_held_first=4, routed_scaling_factor=2.446, kv_lora_rank=32,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=24,
    num_experts_per_tok=4, first_k_dense_replace=1, norm_topk_prob=True,
    rms_norm_eps=1e-5, rope_theta=800000, loss_chunk=16,
    param_dtype="float32",
    optimizer={"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
               "beta2": 0.999, "epsilon": 1e-8})


def _tokens(rows=2, seq=32, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"],
                                                (rows, seq))


@pytest.fixture(scope="module")
def model_and_reference():
    """Loss and gradients of the program (through ``functionalize``, as the
    trainer calls it) and of the reference, on the same seeded weights with
    a non-zero router bias."""
    net, names = mla_moe.build_net(TINY, 7, "float32")
    pure, params = functionalize(net, train=True)
    values = [p.data()._data for p in params]
    toks = _tokens()
    tokens, labels = mla_moe.as_program_batch(toks)

    def loss(v):
        outs, aux = pure(jax.random.PRNGKey(0), v, jnp.asarray(tokens),
                         jnp.asarray(labels))
        return outs[0], aux

    (value, aux), grads = jax.value_and_grad(loss, has_aux=True)(values)
    weights = ref.make_params(TINY, 7, "float32")
    assert float(jnp.abs(weights["layer1_moe_router_bias"]).max()) > 0
    trained = {k: v for k, v in weights.items() if ref.takes_gradient(k)}
    fixed = {k: v for k, v in weights.items() if not ref.takes_gradient(k)}
    targets = float(toks.shape[0] * (toks.shape[1] - 1))
    ref_value, ref_grads = jax.value_and_grad(lambda t: sum(
        ref.row_loss(dict(t, **fixed), jnp.asarray(row), TINY, targets)
        for row in toks))(trained)
    short = {full: s for s, full in names.items()}
    return {"loss": float(value), "aux": aux, "ref_loss": float(ref_value),
            "grads": {short[p.name]: g for p, g in zip(params, grads)},
            "ref_grads": ref_grads, "weights": weights, "tokens": toks}


def test_loss_matches_the_reference(model_and_reference):
    m = model_and_reference
    assert m["loss"] == pytest.approx(m["ref_loss"], rel=1e-6)
    assert m["loss"] == pytest.approx(np.log(64), rel=0.05)


@pytest.mark.parametrize("leaf", sorted(
    k for k in ref.param_spec(TINY) if ref.takes_gradient(k)))
def test_gradient_matches_the_reference(model_and_reference, leaf):
    got = model_and_reference["grads"][leaf]
    want = model_and_reference["ref_grads"][leaf]
    assert float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) <= \
        2e-5 * float(jnp.linalg.norm(want))


def test_router_bias_takes_no_gradient_and_counter_counts(model_and_reference):
    m = model_and_reference
    for leaf, g in m["grads"].items():
        if not ref.takes_gradient(leaf):
            assert float(jnp.abs(g).max()) == 0.0
    # the auxiliary output: rows routed to each held expert, a layer
    tokens = m["tokens"].size
    for rows in m["aux"]:
        assert rows.shape == (4,) and 0 < float(rows.sum()) <= tokens * 4


# ---- the latent flash kernels, interpret mode, against the XLA form --------

def _latent_operands(batch, seq, heads=2, nope=128, rope=64, v_dim=128):
    ks = jax.random.split(jax.random.PRNGKey(seq), 5)
    shapes = [(batch, seq, heads * nope), (batch, seq, heads, rope),
              (batch, seq, heads * (nope + v_dim)), (batch, seq, rope),
              (batch, seq, heads * v_dim)]
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(ks, shapes)]


# (seq, blocks, causal): blocks equal, queries fewer and more than keys; three
# and four blocks, so that some live tiles straddle the diagonal and others lie
# wholly below it; "4kb_q128_k256": two query blocks whose first key block is
# their only live one
_LATENT_CASES = {
    "2kb": (256, (128, 128), True), "3kb": (384, (128, 128), True),
    "q256_k128": (256, (256, 128), True), "q128_k256": (256, (128, 256), True),
    "4kb_q256_k128": (512, (256, 128), True),
    "4kb_q128_k256": (512, (128, 256), True),
    "3kb_full": (384, (128, 128), False),
    "q256_k128_full": (256, (256, 128), False),
    "q128_k256_full": (256, (128, 256), False)}


def _latent_lse(q_nope, q_rope, kv, k_rope, heads, causal, nope=128):
    """Log-sum-exp of each (row, head)'s scaled scores, (B * H, 1, S)."""
    B, S, _ = q_nope.shape
    qn = q_nope.reshape(B, S, heads, nope)
    kn = kv.reshape(B, S, heads, -1)[..., :nope]
    s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn, precision="highest")
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope, precision="highest"))
    s = s / np.sqrt(nope + q_rope.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1).reshape(B * heads, 1, S)


@pytest.fixture(scope="module")
def latent_cases():
    out = {}
    for seq, blocks, causal in _LATENT_CASES.values():
        *ops, w = _latent_operands(2, seq)

        def flash(*a):
            return jnp.sum(pk.flash_attention_latent(
                *a, 2, causal, blocks, True) * w)

        def plain(*a):
            return jnp.sum(nn_ops.xla_latent_attention(*a, 2, causal) * w)

        lse = pk._latent_fwd_impl(ops[0], jnp.transpose(ops[1], (0, 2, 1, 3)),
                                  ops[2], ops[3], 2, causal, blocks, True)[1]
        got = (pk.flash_attention_latent(*ops, 2, causal, blocks, True),) \
            + jax.grad(flash, argnums=(0, 1, 2, 3))(*ops) + (lse,)
        want = (nn_ops.xla_latent_attention(*ops, 2, causal),) \
            + jax.grad(plain, argnums=(0, 1, 2, 3))(*ops) \
            + (_latent_lse(*ops, 2, causal),)
        out[(seq, blocks, causal)] = (got, want)
    return out


@pytest.mark.parametrize("which", range(6), ids=[
    "forward", "dq_nope", "dq_rope", "dkv", "dk_rope", "lse"])
@pytest.mark.parametrize("seq,blocks,causal", list(_LATENT_CASES.values()),
                         ids=list(_LATENT_CASES))
def test_latent_flash_kernels_match_the_xla_form(latent_cases, seq, blocks,
                                                 causal, which):
    got, want = latent_cases[(seq, blocks, causal)]
    scale = float(jnp.abs(want[which]).max())
    assert float(jnp.abs(got[which] - want[which]).max()) <= 5e-6 * scale


@pytest.mark.parametrize("seq,blocks,causal,want", [
    (8192, (1024, 1024), True, (36, 8)), (512, (256, 128), True, (6, 4)),
    (512, (128, 256), True, (6, 4)), (512, (256, 128), False, (8, 0))],
    ids=["cell", "q256_k128", "q128_k256", "q256_k128_full"])
def test_latent_forward_counts_its_tiles(seq, blocks, causal, want):
    """``latent_forward_stats()``: the live tiles of one (row, head) and
    those of them masked (straddling the diagonal), once a trace. At the
    cell's 8,192 / 1,024 / 1,024 the call is traced only (36 of 64 tiles
    live, the 8 on the diagonal masked); the small sizes run in interpret
    mode."""
    *ops, _ = _latent_operands(1, seq)
    before = pk.latent_forward_stats()
    if seq > 1024:
        shapes = [jax.ShapeDtypeStruct(x.shape, jnp.bfloat16) for x in ops]
        jax.eval_shape(functools.partial(
            pk.flash_attention_latent, num_heads=2, causal=causal,
            blocks=blocks), *shapes)
    else:
        pk.flash_attention_latent(*ops, 2, causal, blocks, True)
    after = pk.latent_forward_stats()
    assert (after["live"] - before["live"],
            after["masked"] - before["masked"]) == want


def test_dispatcher_counts_the_latent_kernels(request):
    """Where the kernels are usable the op takes them (``latent``); off the
    chip it takes the XLA form (``xla``): the same result."""
    *ops, _ = _latent_operands(1, 128)
    before = nn_ops.attention_dispatch_stats()
    plain = nn_ops.latent_attention.fn(*ops, num_heads=2)
    request.getfixturevalue("chip_present_interpreted")
    flash = nn_ops.latent_attention.fn(*ops, num_heads=2)
    after = nn_ops.attention_dispatch_stats()
    assert after["latent"] == before["latent"] + 1
    assert after["xla"] == before["xla"] + 1
    assert (after["packed"], after["flash"]) == (before["packed"],
                                                 before["flash"])
    np.testing.assert_allclose(flash, plain, atol=5e-6)
    # widths the kernels do not take stay on the XLA form
    assert not pk.flash_attention_latent_usable(128, 24, 8, 16)
    assert pk.flash_attention_latent_usable(8192, 128, 64, 128)


# ---- the held-expert layer -------------------------------------------------

def _layer_weights(seed, experts=16, d=64, f=48):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = {"moe_router_weight": 0.5 * jax.random.normal(ks[0], (experts, d)),
         "moe_router_bias": 0.3 * jax.random.normal(ks[1], (experts,)),
         "moe_expert_gate_weight": 0.1 * jax.random.normal(ks[2], (experts, d, f)),
         "moe_expert_up_weight": 0.1 * jax.random.normal(ks[3], (experts, d, f)),
         "moe_expert_down_weight": 0.1 * jax.random.normal(ks[4], (experts, f, d))}
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    x = jax.random.normal(ks[5], (96, d), jnp.float32)
    return p, x


def _held(p, x, first, count, top_k=4):
    sl = slice(first, first + count)
    return moe.held_experts_ffn(
        x, p["moe_router_weight"], p["moe_router_bias"],
        p["moe_expert_gate_weight"][sl], p["moe_expert_up_weight"][sl],
        p["moe_expert_down_weight"][sl], first=first, top_k=top_k,
        scale=2.446)


def test_the_shares_add_up_to_the_uncut_layer():
    """Over the 4 shares of a 16-expert layer, the routed parts summed and
    the shared expert counted once are the uncut layer of the reference."""
    p, x = _layer_weights(3)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    p.update({"moe_shared_gate_weight": 0.1 * jax.random.normal(ks[0], (96, 64), jnp.float32),
              "moe_shared_up_weight": 0.1 * jax.random.normal(ks[1], (96, 64), jnp.float32),
              "moe_shared_down_weight": 0.1 * jax.random.normal(ks[2], (64, 96), jnp.float32)})
    whole, rows_whole = ref.routed_part(p, "", x, TINY, 0, "float32")
    whole = whole + ref.shared_part(p, "", x, "float32")
    parts, rows = zip(*(_held(p, x, first, 4) for first in (0, 4, 8, 12)))
    shared = moe.gated_ffn(x, p["moe_shared_gate_weight"],
                           p["moe_shared_up_weight"],
                           p["moe_shared_down_weight"])
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_array_equal(np.concatenate(rows), rows_whole)
    assert int(sum(r.sum() for r in rows)) == 96 * 4    # nothing dropped


@pytest.mark.parametrize("first", [0, 4, 8, 12])
def test_one_share_and_its_gradients_match_the_reference(first):
    p, x = _layer_weights(5)
    cut = dict(p)
    for k in ("gate", "up", "down"):
        name = "moe_expert_%s_weight" % k
        cut[name] = p[name][first:first + 4]

    def program(x, w):      # _held slices the uncut stacks itself
        return jnp.sum(jnp.sin(_held(dict(p, **w), x, first, 4)[0]))

    def reference(x, w):
        return jnp.sum(jnp.sin(ref.routed_part(
            dict(cut, **w), "", x, TINY, first, "float32")[0]))

    trained = {k: v for k, v in p.items() if k != "moe_router_bias"}
    cut_trained = {k: cut[k] for k in trained}
    got = jax.grad(program, argnums=(0, 1))(x, trained)
    want = jax.grad(reference, argnums=(0, 1))(x, cut_trained)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-6)
    for k in trained:
        g = got[1][k]
        if k != "moe_router_weight":
            g = g[first:first + 4]
        np.testing.assert_allclose(g, want[1][k], rtol=2e-4, atol=2e-6)


def _steer(p, favoured):
    """A bias that makes every token choose exactly ``favoured``."""
    bias = np.full((16,), -10.0, np.float32)
    bias[list(favoured)] = 10.0
    return dict(p, moe_router_bias=jnp.asarray(bias))


def test_dropless_every_token_to_one_held_expert():
    p, x = _layer_weights(6)
    q = _steer(p, (5, 0, 1, 2))             # of experts 4..7 only 5 is held
    y, rows = _held(q, x, 4, 4)
    np.testing.assert_array_equal(rows, [0, 96, 0, 0])
    want, _ = ref.routed_part(
        {k: (v[4:8] if "expert" in k else v) for k, v in q.items()}, "", x,
        TINY, 4, "float32")
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0     # no token dropped


def test_dropless_every_choice_held_takes_the_worst_case_buffers():
    """All four choices of every token held here: 4 x tokens rows, the
    largest the layer can be asked for, none dropped."""
    p, x = _layer_weights(7)
    q = _steer(p, (4, 5, 6, 7))
    y, rows = _held(q, x, 4, 4)
    np.testing.assert_array_equal(rows, [96, 96, 96, 96])
    want, _ = ref.routed_part(
        {k: (v[4:8] if "expert" in k else v) for k, v in q.items()}, "", x,
        TINY, 4, "float32")
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-6)


def test_tokens_routed_to_experts_not_held_leave_the_shared_part_intact():
    net, _ = mla_moe.build_net(TINY, 11, "float32")
    layer = net.expert_layers[0]
    bias = np.full((16,), -10.0, np.float32)
    bias[[0, 1, 2, 3]] = 10.0               # held: 4..7
    layer.router_bias.set_data(mx.nd.array(bias))
    x = mx.nd.array(np.random.default_rng(1).normal(size=(2, 8, 64))
                    .astype(np.float32))
    y = layer(x)
    np.testing.assert_array_equal(layer.routed_rows.asnumpy(), [0, 0, 0, 0])
    routed, _ = moe.held_experts_ffn(
        x._data.reshape(16, 64), layer.router_weight.data()._data,
        layer.router_bias.data()._data,
        layer.expert_gate_weight.data()._data,
        layer.expert_up_weight.data()._data,
        layer.expert_down_weight.data()._data, first=4, top_k=4, scale=2.446)
    assert float(jnp.abs(routed).max()) == 0.0          # exactly zero
    shared = moe.gated_ffn(x._data, layer.shared_gate_weight.data()._data,
                           layer.shared_up_weight.data()._data,
                           layer.shared_down_weight.data()._data)
    np.testing.assert_allclose(y.asnumpy(), shared, rtol=1e-6, atol=1e-7)


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    p, x = _layer_weights(8)
    chosen, weights = moe.route_top_k(
        x, p["moe_router_weight"], p["moe_router_bias"], 4, scale=2.446)
    scores = jax.nn.sigmoid(x @ p["moe_router_weight"].T)
    want = np.argsort(-(scores + p["moe_router_bias"]), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, 2.446 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    by_score = np.argsort(-np.asarray(scores), axis=-1)[:, :4]
    assert (np.sort(by_score, -1) != np.sort(want, -1)).any()   # bias matters


@pytest.mark.parametrize("tokens, top_k, held, experts, want", [
    (16384, 6, 8, 64, (24576, 49152, 98304)),   # twice the uniform share,
    (32768, 6, 8, 64, (49152, 98304, 196608)),  # doubling up to the worst
    (96, 4, 4, 16, (256, 384)),         # rounded up to the 128-row tile
    (96, 4, 16, 16, (384,)),            # every expert held: the worst only
    (128, 1, 2, 4, (128,)),
])
def test_buffer_sizes_follow_the_operands(tokens, top_k, held, experts, want):
    assert moe._tiers(tokens, top_k, held, experts) == want


@pytest.mark.parametrize("steered, want_size", [
    ("uniform", 128), ("some_tokens", 256), ("one_held_choice", 512),
    ("both_choices_held", 1024)])
def test_every_buffer_size_gives_the_worst_case_buffers_result(
        monkeypatch, steered, want_size):
    """512 tokens, 2 of 32 experts held, 2 a token: buffers of 128, 256,
    512 and 1024 rows. Whichever the routing picks, result and gradients
    are those of the worst-case buffer alone."""
    p, _ = _layer_weights(9, experts=32)
    x = jax.random.normal(jax.random.PRNGKey(10), (512, 64), jnp.float32)
    bias = np.zeros((32,), np.float32)
    if steered == "some_tokens":        # 150 tokens pulled to expert 0
        w0 = p["moe_router_weight"][0]
        x = x.at[:150].add(20.0 * w0 / jnp.linalg.norm(w0))
    elif steered == "one_held_choice":
        bias[[0, 5]] = 10.0
    elif steered == "both_choices_held":
        bias[[0, 1]] = 10.0
    p["moe_router_bias"] = jnp.asarray(bias)
    sizes = moe._tiers(512, 2, 2, 32)
    assert sizes == (128, 256, 512, 1024)

    def loss(x, w):
        y, rows = _held(dict(p, **w), x, 0, 2, top_k=2)
        return jnp.sum(jnp.sin(y)), rows

    trained = {k: v for k, v in p.items() if k != "moe_router_bias"}
    (got, rows), got_grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, trained)
    assert min(m for m in sizes if m >= int(rows.sum())) == want_size
    monkeypatch.setattr(moe, "_tiers", lambda *a: (1024,))
    (want, _), want_grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, trained)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# ---- the combine over the buffer's rows --------------------------------------

def _a_choice_at_a_time(x, router_w, router_b, w_gate, w_up, w_down, top_k):
    """The combine as it stood before the buffer's rows sized it, kept as
    the oracle: a choice at a time over whole (T, d) arrays, zero where the
    choice is not held, each token through its own expert's matrices."""
    chosen, weights = moe.route_top_k(x, router_w, router_b, top_k, 2.446)
    held = chosen < w_gate.shape[0]
    y = 0.0
    for k in range(top_k):
        e = jnp.minimum(chosen[:, k], w_gate.shape[0] - 1)
        up = jax.nn.silu(jnp.einsum("td,tdf->tf", x, w_gate[e])) \
            * jnp.einsum("td,tdf->tf", x, w_up[e])
        out = jnp.einsum("tf,tfd->td", up, w_down[e])
        y = y + jnp.where(held[:, k, None], out, 0) * weights[:, k, None]
    return y


def _steered_layer(tokens, d, f, experts, held_choices):
    """Operands of a layer that holds experts 0 and 1 of ``experts``, two
    choices a token, whose router sends token t to held expert g exactly
    where ``held_choices[t, g]`` (feature g of x is the switch: its score
    is 1 or 0); ``None`` leaves the router as drawn."""
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    x = jax.random.normal(ks[0], (tokens, d), jnp.float32)
    router_w = 0.1 * jax.random.normal(ks[1], (experts, d), jnp.float32)
    if held_choices is not None:
        switch = jnp.where(jnp.asarray(held_choices), 1.0, -1.0)
        x = x.at[:, :2].set(switch)
        router_w = router_w.at[:, :2].set(0.0).at[0, 0].set(40.0) \
            .at[1, 1].set(40.0).at[:2, 2:].set(0.0)
    w_gate, w_up, w_down = (
        0.1 * jax.random.normal(k, shape, jnp.float32) for k, shape in zip(
            ks[2:], [(2, d, f), (2, d, f), (2, f, d)]))
    return x, router_w, jnp.zeros((experts,), jnp.float32), w_gate, w_up, \
        w_down


def _held_choices(case, tokens):
    """Which of the two held experts each token is sent to, by case."""
    want = np.zeros((tokens, 2), bool)
    if case == "uniform":
        return None
    if case == "every_choice_held":         # the worst-case buffer
        want[:] = True
    elif case == "both_choices_on_a_block_boundary":
        want[[127, 128]] = True             # token blocks are 128 tokens
        want[np.arange(5, tokens, 37), 0] = True
    elif case == "rows_at_a_buffer_size":
        want[:128, 1] = True
    elif case == "rows_one_over_a_buffer_size":
        want[:128, 1] = True
        want[300, 0] = True
    else:
        assert case == "none_held"
    return want


def _assert_matches_the_oracle(operands, want_rows):
    def layer(x, router_w, w_gate, w_up, w_down):
        y, rows = moe.held_experts_ffn(
            x, router_w, operands[2], w_gate, w_up, w_down, first=0, top_k=2,
            scale=2.446)
        return jnp.sum(jnp.sin(y)), (y, rows)

    def oracle(x, router_w, w_gate, w_up, w_down):
        y = _a_choice_at_a_time(x, router_w, operands[2], w_gate, w_up,
                                w_down, 2)
        return jnp.sum(jnp.sin(y)), y

    trained = operands[:2] + operands[3:]
    (_, (y, rows)), grads = jax.value_and_grad(
        layer, argnums=(0, 1, 2, 3, 4), has_aux=True)(*trained)
    (_, y_want), grads_want = jax.value_and_grad(
        oracle, argnums=(0, 1, 2, 3, 4), has_aux=True)(*trained)
    if want_rows is not None:
        assert int(rows.sum()) == want_rows
    for got, want in zip((y,) + grads, (y_want,) + grads_want):
        assert np.isfinite(got).all()
        # float32 sums in another order: a few units of the largest's last place
        np.testing.assert_allclose(
            got, want, rtol=0, atol=4e-6 * max(1.0, float(jnp.abs(want).max())))
    return rows


@pytest.mark.parametrize("case, want_rows", [
    ("uniform", None), ("every_choice_held", 1024), ("none_held", 0),
    ("both_choices_on_a_block_boundary", 4 + 14),
    ("rows_at_a_buffer_size", 128), ("rows_one_over_a_buffer_size", 129)])
def test_combine_over_the_buffers_rows_matches_a_choice_at_a_time(
        case, want_rows):
    """Forward and all five gradients of the layer (one permutation into
    token order, a sum over each token's adjacent rows, the weights'
    gradient a row sum in the sorted buffer) against the per-choice combine,
    float32. 512 tokens, 2 of 32 experts held: buffers of 128 to 1024
    rows. Off the chip the sums are ``segment_sum``."""
    assert moe._tiers(512, 2, 2, 32) == (128, 256, 512, 1024)
    before = moe.combine_stats()
    _assert_matches_the_oracle(
        _steered_layer(512, 64, 48, 32, _held_choices(case, 512)), want_rows)
    after = moe.combine_stats()
    assert after["grouped"] == before["grouped"]
    assert after["xla"] > before["xla"]


@pytest.fixture
def megablox_interpreted(monkeypatch):
    """The chip's path off the chip: the Pallas grouped products take every
    call whose sizes tile, interpreted."""
    backend = moe._megablox()
    monkeypatch.setattr(moe, "_megablox_usable", lambda m, k, n: (
        m % 128 == 0 and k % 128 == 0 and n % 128 == 0))
    for kernel in ("gmm", "tgmm"):
        monkeypatch.setattr(backend, kernel, functools.partial(
            getattr(backend, kernel), interpret=True))


@pytest.mark.parametrize("case", [
    "uniform", "both_choices_on_a_block_boundary", "undefined_rows_are_nan"])
def test_grouped_combine_matches_a_choice_at_a_time(
        monkeypatch, megablox_interpreted, case):
    """The same where the sums are the grouped product over blocks of 128
    tokens (256 tokens: two blocks; 128 wide). In the last case every row
    past the groups of every grouped product's result is NaN, as undefined
    memory may be on the chip: nothing sums over one."""
    if case == "undefined_rows_are_nan":
        real = moe._gmm_call

        def planted(lhs, rhs, sizes, transpose_rhs):
            live = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
            return jnp.where(live[:, None],
                             real(lhs, rhs, sizes, transpose_rhs), jnp.nan)
        monkeypatch.setattr(moe, "_gmm_call", planted)
    before = moe.combine_stats()
    rows = _assert_matches_the_oracle(_steered_layer(
        256, 128, 128, 8, None if case != "both_choices_on_a_block_boundary"
        else _held_choices(case, 256)), None)
    assert 0 < int(rows.sum()) < 256        # dead rows in the first buffer
    after = moe.combine_stats()
    assert after["xla"] == before["xla"]
    assert after["grouped"] > before["grouped"]


# ---- the chunked loss ------------------------------------------------------

@pytest.mark.parametrize("chunk", [16, 64, 1000])
def test_chunked_cross_entropy_matches_the_whole(chunk):
    ks = jax.random.split(jax.random.PRNGKey(chunk), 3)
    h = jax.random.normal(ks[0], (2, 32, 24), jnp.float32)
    w = jax.random.normal(ks[1], (50, 24), jnp.float32)
    labels = jax.random.randint(ks[2], (2, 32), 0, 50).at[:, -1].set(-1)

    def whole(h, w):
        logp = jax.nn.log_softmax(h @ w.T, axis=-1)
        ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)
        return -jnp.sum(jnp.where(labels >= 0, ll[..., 0], 0.0)) / 62.0

    def chunked(h, w):
        return nn_ops.chunked_softmax_cross_entropy.fn(h, w, labels,
                                                       chunk=chunk)

    got = jax.value_and_grad(chunked, argnums=(0, 1))(h, w)
    want = jax.value_and_grad(whole, argnums=(0, 1))(h, w)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_rms_norm_and_rotary_ops_match_the_reference():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 10, 4, 8), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(2), (8,), jnp.float32)
    np.testing.assert_allclose(nn_ops.RMSNorm.fn(x, g, eps=1e-5),
                               ref.rms_norm(x, g, 1e-5), rtol=1e-6)
    got = nn_ops.rotary_embedding.fn(x, theta=800000.0)
    want = jnp.stack([ref.rotary(x[b], 800000) for b in range(3)])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # a rotation: norms are kept, position 0 is left alone
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])


# ---- through the trainer ---------------------------------------------------

def test_trainer_steps_in_bfloat16_and_keeps_the_counter_on_the_device():
    cfg = dict(TINY, param_dtype="bfloat16")
    cell = {"batch": 2, "seq": 32, "pool": 2}
    system = mla_moe.build(cfg, cell, 13, jax.devices())
    fixed = "router_bias"       # chooses and does not weigh: no gradient
    bias = [np.asarray(v, np.float32) for p, v in zip(
        system.trainer._params, system.trainer._values)
        if p.name.endswith(fixed)]
    text = system.trainer.lower_step(
        system._data(0), mx.nd.array(system._label)).as_text(debug_info=True)
    for scope in ("attention", "moe_router", "moe_experts", "moe_shared",
                  "optimizer"):
        assert "/%s/" % scope in text, scope
    losses = [float(system.step(i).asnumpy()) for i in range(3)]
    assert all(np.isfinite(losses)) and losses[0] == pytest.approx(
        np.log(64), rel=0.1)
    rows = system.routed_rows()
    assert len(rows) == 2 and all(len(r) == 4 for r in rows)
    assert all(0 < sum(r) <= 64 * 4 for r in rows)
    after = [np.asarray(v, np.float32) for p, v in zip(
        system.trainer._params, system.trainer._values)
        if p.name.endswith(fixed)]
    assert len(bias) == 2
    for a, b in zip(bias, after):
        np.testing.assert_array_equal(a, b)     # never trained
    # against the reference's three steps, in bfloat16 storage
    want = mla_moe.reference(cfg, cell, 13, 3)
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-2)
    assert set(system.first_gradient_norms()) == set(want["grad_norms"])
    assert not any(k.endswith(fixed) for k in want["grad_norms"])
    assert any(k.endswith("router_weight") for k in want["grad_norms"])


def test_planted_faults_move_the_reference():
    """The two faults the calibration plants are faults: each moves the
    first gradient of the leaves it touches."""
    cell = {"batch": 2, "seq": 32, "pool": 2}
    sound = mla_moe.reference(TINY, cell, 5, 1)
    # (at this size the scores are nearly flat, so only the rotary key's own
    # rows of the joint projection feel the rotation: the last 8 of 40)
    for fault, leaf, rows in (
            ("expert_dropped", "layer1_moe_expert_up_weight", slice(None)),
            ("no_rope_on_shared_key", "layer0_attn_kva_weight",
             slice(32, 40))):
        broken = mla_moe.reference(TINY, cell, 5, 1, fault=fault)
        a = broken["first_gradient"][leaf].astype(np.float32)[rows]
        b = sound["first_gradient"][leaf].astype(np.float32)[rows]
        assert np.linalg.norm(a - b) > 0.05 * np.linalg.norm(b), fault
