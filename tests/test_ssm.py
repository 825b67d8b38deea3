"""The hybrid state-space / attention decoder (``models/hybrid_ssm.py``)
against its plain reference (``chipbench/configs/hybrid_ssm_ref.py``, which
imports nothing of the program), at a small size on the CPU: loss and every
leaf's gradient; the chunked XLA form against the position-by-position
recurrence; the scan kernels, the convolution kernels and the grouped-query
kernels (interpret mode) against the XLA forms, forward and every gradient;
the degenerate scan; the tied head; the dispatcher's rows and the counters; planted faults;
recomputation under the trainer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.configs import hybrid_ssm, hybrid_ssm_ref as ref, mla_moe
from mxnet_tpu.ops import nn as nn_ops, pallas_kernels as pk
from mxnet_tpu.parallel.functional import functionalize

TINY = dict(
    vocab_size=48, hidden_size=64, shared_intermediate_size=160,
    num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
    mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=8, mamba_n_groups=1, num_attention_heads=4,
    num_key_value_heads=2, attention_multiplier=0.0625,
    residual_multiplier=0.22, embedding_multiplier=12, logits_scaling=8,
    rms_norm_eps=1e-5, loss_chunk=16, param_dtype="float32",
    optimizer={"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
               "beta2": 0.999, "epsilon": 1e-8})
SEQ = 40            # 5 chunks


def _tokens(rows=2, seq=SEQ, seed=0):
    return np.random.default_rng(seed).integers(0, TINY["vocab_size"],
                                                (rows, seq))


def _program(cfg, seed=7):
    """``(loss(values, tokens, labels), values, short names)`` through
    ``functionalize``, as the trainer calls the model."""
    net, names = hybrid_ssm.build_net(cfg, seed, "float32")
    pure, params = functionalize(net, train=True)
    values = [p.data()._data for p in params]
    short = {full: s for s, full in names.items()}

    def loss(v, tokens, labels):
        outs, _ = pure(jax.random.PRNGKey(0), v, tokens, labels)
        return outs[0]

    return loss, values, [short[p.name] for p in params]


@pytest.fixture(scope="module")
def model_and_reference():
    toks = _tokens()
    loss, values, names = _program(TINY)
    tokens, labels = (jnp.asarray(a) for a in mla_moe.as_program_batch(toks))
    value, grads = jax.jit(jax.value_and_grad(loss))(values, tokens, labels)
    weights = ref.make_params(TINY, 7, "float32")
    ref_value, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, toks, TINY))(weights)
    return {"loss": float(value), "grads": dict(zip(names, grads)),
            "ref_loss": float(ref_value), "ref_grads": ref_grads,
            "weights": weights, "tokens": toks,
            "program": lambda v: loss(v, tokens, labels), "values": values}


def test_loss_matches_the_reference(model_and_reference):
    m = model_and_reference
    assert m["loss"] == pytest.approx(m["ref_loss"], rel=1e-6)
    assert m["loss"] == pytest.approx(np.log(48), rel=0.05)


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
    for leaf, want in m["ref_grads"].items():
        assert float(jnp.linalg.norm(grads[leaf] - want)) <= \
            1e-5 * float(jnp.linalg.norm(want)), leaf


def test_the_tied_heads_gradient_is_the_lookups_plus_the_heads(
        model_and_reference):
    """With the head untied in the reference (a second leaf holding the same
    values), the embedding's gradient in the program is the sum of the
    two."""
    m = model_and_reference
    cfg, toks = TINY, jnp.asarray(m["tokens"])
    labels = jnp.asarray(ref.make_labels(m["tokens"]))

    def untied(embed, head):
        w = dict(m["weights"], embed_weight=embed)
        x = ref.embed(w, toks, cfg)
        for i, kind in enumerate(ref.layer_types(cfg)):
            x = jax.vmap(lambda row, lw=ref.layer_leaves(w, i), kind=kind:
                         ref.layer(lw, row, cfg, kind))(x)
        return ref.head_loss(dict(w, embed_weight=head), x, labels, cfg)

    e = m["weights"]["embed_weight"]
    d_embed, d_head = jax.grad(untied, argnums=(0, 1))(e, e)
    assert float(jnp.linalg.norm(d_embed)) > 0
    assert float(jnp.linalg.norm(d_head)) > 0
    want = d_embed + d_head
    got = m["grads"]["embed_weight"]
    assert float(jnp.linalg.norm(got - want)) <= \
        2e-5 * float(jnp.linalg.norm(want))


def test_the_one_pass_heads_gradient_follows_the_cotangent_through_the_tie(
        model_and_reference):
    """The head's gradient is formed in the forward's loop at a cotangent
    of 1 and scaled by the backward rule: half the loss gives half of every
    leaf's gradient, the tied embedding's sum of two among them, to the bit;
    and the loss outside a gradient (the loss-only loop) is the
    differentiated one (the layers before it are compiled otherwise there:
    the head alone to the bit in ``tests/test_loss_head.py``)."""
    m = model_and_reference
    loss, values = m["program"], m["values"]
    half = jax.jit(jax.grad(lambda v: 0.5 * loss(v)))(values)
    for (name, want), got in zip(m["grads"].items(), half):
        np.testing.assert_array_equal(np.asarray(got),
                                      0.5 * np.asarray(want), name)
    assert float(jax.jit(loss)(values)) == pytest.approx(m["loss"], rel=1e-6)


# ---- the scan ---------------------------------------------------------------

def _scan_operands(rows, seq, heads, p, n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(rows, seq, heads * p)), dtype),
            jnp.asarray(rng.uniform(1e-3, 0.3, size=(rows, seq, heads)),
                        jnp.float32),
            -jnp.asarray(rng.uniform(1, 16, size=(heads,)), jnp.float32),
            jnp.asarray(rng.normal(size=(rows, seq, n)), dtype),
            jnp.asarray(rng.normal(size=(rows, seq, n)), dtype))


def _recurrence(x, dt, a, b, c, heads):
    """The reference's position-by-position recurrence, row by row."""
    rows, seq, _ = x.shape
    return jax.vmap(lambda xr, dr, br, cr: ref.state_recurrence(
        xr.reshape(seq, heads, -1), dr, a, br, cr, seq))(
            x, dt, b, c).reshape(x.shape)


@pytest.fixture(scope="module")
def recurrence_of_48():
    ops = _scan_operands(2, 48, 3, 8, 16)
    w = jnp.asarray(np.random.default_rng(1).normal(size=ops[0].shape),
                    jnp.float32)
    return ops, w, jax.value_and_grad(lambda *o: jnp.sum(
        _recurrence(*o, 3) * w), argnums=range(5))(*ops)


@pytest.mark.parametrize("chunk", [4, 8, 24, 48])
def test_chunked_form_is_the_recurrence(recurrence_of_48, chunk):
    """48 positions in chunks of 4, 8, 24 and 48 (twelve chunks to one):
    the result and every gradient."""
    ops, w, want = recurrence_of_48
    got = jax.value_and_grad(lambda *o: jnp.sum(
        nn_ops.xla_ssm_scan(*o, 3, chunk) * w), argnums=range(5))(*ops)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name, g, r in zip("x dt a b c".split(), got[1], want[1]):
        assert float(jnp.linalg.norm(g - r)) <= \
            2e-5 * float(jnp.linalg.norm(r)), name


def test_a_sequence_that_is_not_whole_chunks_is_padded():
    x, dt, a, b, c = _scan_operands(1, 21, 2, 8, 16)
    ones, zeros = (jnp.ones((2,), jnp.float32),
                   jnp.zeros((2,), jnp.float32))
    got = nn_ops.ssm_scan.fn(x, dt, jnp.log(-a), b, c, zeros, num_heads=2,
                             chunk=8)
    want = _recurrence(x, dt, a, b, c, 2)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    skip = nn_ops.ssm_scan.fn(x, dt, jnp.log(-a), b, c, ones, num_heads=2,
                              chunk=8)
    np.testing.assert_allclose(skip, want + x, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [4, 16])
def test_a_decay_of_one_and_a_state_of_width_one_is_a_cumulative_sum(chunk):
    """A = 0 (no decay), N = 1 with b = c = 1: y_t = sum_{s <= t} dt_s
    x_s."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 32, 2 * 4)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.1, 1.0, size=(2, 32, 2)), jnp.float32)
    one = jnp.ones((2, 32, 1), jnp.float32)
    got = nn_ops.xla_ssm_scan(x, dt, jnp.zeros((2,)), one, one, 2, chunk)
    want = jnp.cumsum(x * jnp.repeat(dt, 4, axis=-1), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


KERNEL = dict(rows=2, seq=384, heads=4, p=64, n=128, chunk=128)


@pytest.fixture(scope="module")
def scan_kernel_and_xla():
    k = KERNEL
    ops = _scan_operands(k["rows"], k["seq"], k["heads"], k["p"], k["n"])
    w = jnp.asarray(np.random.default_rng(1).normal(size=ops[0].shape),
                    jnp.float32)

    def through_kernel(x, dt, a, b, c):
        cs = nn_ops._chunk_log_decay(dt, a, k["chunk"])
        return jnp.sum(pk.ssm_scan(x, dt, cs, b, c, k["heads"], k["chunk"],
                                   interpret=True) * w)

    def through_xla(x, dt, a, b, c):
        return jnp.sum(nn_ops.xla_ssm_scan(x, dt, a, b, c, k["heads"],
                                           k["chunk"]) * w)

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=range(5)))(*ops)
    return grad(through_kernel), grad(through_xla)


@pytest.mark.parametrize("which", range(6),
                         ids=["y", "dx", "ddt", "da", "db", "dc"])
def test_scan_kernels_match_the_xla_form(scan_kernel_and_xla, which):
    """Three chunks of 128, two rows, four heads of 64 (two lane pairs) over
    a state of 128: the forward through the weighted sum, and the backward
    kernel's five gradients (dt's and the log-decay's from both layouts)."""
    (value, grads), (want_value, want_grads) = scan_kernel_and_xla
    if which == 0:
        assert float(value) == pytest.approx(float(want_value), rel=1e-5)
        return
    got, want = grads[which - 1], want_grads[which - 1]
    assert float(jnp.linalg.norm(got - want)) <= \
        1e-4 * float(jnp.linalg.norm(want))


def test_scan_kernel_in_bfloat16_is_near_the_recurrence():
    k = KERNEL
    x, dt, a, b, c = _scan_operands(1, 256, 2, 64, 128, dtype=jnp.bfloat16)
    cs = nn_ops._chunk_log_decay(dt, a, k["chunk"])
    got = pk.ssm_scan(x, dt, cs, b, c, 2, k["chunk"], interpret=True)
    want = _recurrence(*(o.astype(jnp.float32) for o in (x, dt, a, b, c)), 2)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.linalg.norm(got.astype(jnp.float32) - want)) <= \
        2e-2 * float(jnp.linalg.norm(want))


@pytest.mark.parametrize("seq,heads,head_dim,state,chunk,usable", [
    (32768, 64, 64, 128, 256, True), (32768, 64, 128, 128, 256, False),
    (32768, 64, 64, 64, 256, False), (32768, 64, 64, 128, 64, False),
    (1000, 64, 64, 128, 256, False), (256, 3, 64, 128, 128, False)],
    ids=["published", "head_128", "state_64", "chunk_64", "ragged",
         "odd_heads"])
def test_what_the_scan_kernels_take(seq, heads, head_dim, state, chunk,
                                    usable):
    assert pk.ssm_scan_usable(seq, heads, head_dim, state, chunk) is usable


def test_the_scan_is_counted_where_it_is_decided(chip_present_interpreted):
    """On the CPU the op takes the XLA form; with a chip present the
    kernels, unless the shapes rule them out; once a trace either way."""
    x, dt, a, b, c = _scan_operands(1, 256, 2, 64, 128)
    d = jnp.ones((2,), jnp.float32)
    before = nn_ops.ssm_scan_stats()
    got = nn_ops.ssm_scan.fn(x, dt, jnp.log(-a), b, c, d, num_heads=2,
                             chunk=128)
    assert nn_ops.ssm_scan_stats() == {"kernel": before["kernel"] + 1,
                                   "xla": before["xla"]}
    small = _scan_operands(1, 32, 2, 8, 16)
    nn_ops.ssm_scan.fn(small[0], small[1], jnp.log(-small[2]), small[3],
                       small[4], d, num_heads=2, chunk=8)
    assert nn_ops.ssm_scan_stats() == {"kernel": before["kernel"] + 1,
                                   "xla": before["xla"] + 1}
    want = _recurrence(x, dt, a, b, c, 2) + x
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_convolution_is_causal_and_starts_from_nothing():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(6,)), jnp.float32)
    want = np.zeros((2, 12, 6)) + np.asarray(bias)
    for t in range(12):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(w)[:, k] * np.asarray(x)[:, t - 3 + k]
    np.testing.assert_allclose(nn_ops.causal_conv1d.fn(x, w, bias),
                               jax.nn.silu(want), rtol=1e-5, atol=1e-5)


CONV_CASES = {     # rows, seq, channels, taps, (positions, columns) a grid step
    "one_block": (1, 64, 128, 4, (64, 128)),
    "history_crosses_blocks": (1, 160, 128, 4, (32, 128)),
    "tiles_inside_blocks": (1, 192, 128, 4, (96, 128)),
    "unrolled_tiles": (1, 512, 128, 4, (256, 128)),
    "two_taps": (1, 96, 128, 2, (32, 128)),
    "eight_taps": (1, 64, 128, 8, (32, 128)),
    "two_column_blocks": (1, 64, 256, 4, (32, 128)),
    "batch_2": (2, 64, 128, 4, (32, 128)),
    "padded": (2, 100, 128, 4, (64, 128)),
}


def _conv_operands(rows, seq, channels, taps, dtype=jnp.float32, seed=8):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(rows, seq, channels)), dtype),
            jnp.asarray(rng.uniform(-.5, .5, (channels, taps)), dtype),
            jnp.asarray(rng.uniform(-.5, .5, (channels,)), dtype))


@pytest.fixture(scope="module")
def conv_kernel_and_xla():
    """``name -> {"y", "dx", "dw", "db", "y_bf16"}``, each a (kernel, XLA
    form) pair, both jitted (XLA:CPU contracts a fused multiply-add the same
    way in both), worked out once a case."""
    done = {}

    def pair(name):
        if name in done:
            return done[name]
        rows, seq, channels, taps, blocks = CONV_CASES[name]
        ops = _conv_operands(rows, seq, channels, taps)
        g = jnp.asarray(np.random.default_rng(9).normal(size=ops[0].shape),
                        jnp.float32)
        kernel = lambda x, w, b: pk.causal_conv1d(x, w, b, blocks=blocks,
                                                  interpret=True)

        def both(conv):
            def run(x, w, b):
                y, vjp = jax.vjp(conv, x, w, b)
                return (y,) + vjp(g.astype(y.dtype))
            return jax.jit(run)

        got, want = both(kernel)(*ops), both(nn_ops.xla_causal_conv1d)(*ops)
        half = [o.astype(jnp.bfloat16) for o in ops]
        done[name] = dict(zip(("y", "dx", "dw", "db"), zip(got, want)))
        done[name]["y_bf16"] = (jax.jit(kernel)(*half),
                                jax.jit(nn_ops.xla_causal_conv1d)(*half))
        return done[name]

    return pair


@pytest.mark.parametrize("which", ["y", "y_bf16", "dx", "dw", "db"])
@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv_kernels_match_the_xla_form(conv_kernel_and_xla, case, which):
    """The forward to the bit in float32 (the taps are added in the XLA
    form's order) and within one bfloat16 ulp in bfloat16; the backward
    kernel's three gradients, float32 sums where the XLA form adds rounded
    terms, to 1e-5 of the gradient's norm: one position block; blocks of one
    tile, so that the rows before and the dpre after a tile come from the
    neighbouring grid steps; several tiles a block, one loop iteration each
    and ``_CONV_UNROLL`` an iteration; 2, 4 and 8 taps; two
    column blocks; two rows (the carried rows start from nought in each); a
    sequence padded to whole blocks."""
    got, want = conv_kernel_and_xla(case)[which]
    assert got.shape == want.shape and got.dtype == want.dtype
    if which == "y":
        np.testing.assert_array_equal(got, want)
    elif which == "y_bf16":
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
        assert np.all(np.abs(got - want) <= ulp)
    else:
        assert float(jnp.linalg.norm(got - want)) <= \
            1e-5 * float(jnp.linalg.norm(want))


@pytest.mark.parametrize("blocks", [(64, 128), (32, 128)],
                         ids=["one_block", "two_blocks"])
def test_the_conv_kernel_is_causal_and_starts_from_nothing(blocks):
    """Position t of the kernel's output reads positions t - 3 .. t and
    nothing else (a change at position 40 moves outputs 40 .. 43 only, across
    the blocks' edge or not), and the first three positions read nought
    before the sequence."""
    x, w, bias = _conv_operands(1, 64, 128, 4)
    conv = jax.jit(lambda x: pk.causal_conv1d(x, w, bias, blocks=blocks,
                                              interpret=True))
    moved = np.asarray(conv(x.at[:, 30].add(1.0)) != conv(x))
    assert moved[:, 30:34].any(axis=(0, 2)).all()
    assert not moved[:, :30].any() and not moved[:, 34:].any()
    want = np.zeros((1, 3, 128)) + np.asarray(bias)
    for t in range(3):
        for k in range(3 - t, 4):
            want[:, t] += np.asarray(w)[:, k] * np.asarray(x)[:, t - 3 + k]
    np.testing.assert_allclose(conv(x)[:, :3], jax.nn.silu(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("shape,chip,mesh,path", [
    ((32768, 4352, 4), True, 1, "kernel"), ((32768, 4352, 4), False, 1, "xla"),
    ((32768, 4352, 4), True, 4, "xla"), ((32768, 4352, 4), True, None,
                                         "kernel"),
    ((32768, 100, 4), True, 1, "xla"), ((32768, 4352, 9), True, 1, "xla"),
    ((1000, 256, 2), True, 1, "kernel")],
    ids=["published", "no_chip", "mesh_of_four", "no_mesh", "100_channels",
         "9_taps", "ragged"])
def test_what_the_conv_kernels_take(monkeypatch, shape, chip, mesh, path):
    from mxnet_tpu import parallel
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: chip)
    before = nn_ops.ssm_conv_stats()
    if mesh is None:
        assert nn_ops._ssm_conv_path(*shape) == path
    else:
        with parallel.mesh_scope(parallel.make_mesh(
                dp=mesh, devices=jax.devices()[:mesh]), ("dp",)):
            assert nn_ops._ssm_conv_path(*shape) == path
    assert nn_ops.ssm_conv_stats() == before       # deciding is pure


def test_the_convolution_is_counted_where_it_is_decided(
        chip_present_interpreted):
    """On the CPU the op takes the XLA form; with a chip present the
    kernels, unless the shapes rule them out; once a trace either way, and
    under the ``ssm_conv`` scope."""
    x, w, bias = _conv_operands(1, 64, 128, 4)
    before = nn_ops.ssm_conv_stats()
    conv = jax.jit(nn_ops.causal_conv1d.fn)
    got = conv(x, w, bias)
    conv(x, w, bias)                                # the trace is cached
    assert nn_ops.ssm_conv_stats() == dict(before,
                                           kernel=before["kernel"] + 1)
    narrow = _conv_operands(1, 64, 100, 4)
    nn_ops.causal_conv1d.fn(*narrow)
    assert nn_ops.ssm_conv_stats() == {"kernel": before["kernel"] + 1,
                                       "xla": before["xla"] + 1}
    np.testing.assert_array_equal(
        got, jax.jit(nn_ops.xla_causal_conv1d)(x, w, bias))
    text = conv.lower(x, w, bias).as_text(debug_info=True)
    assert "ssm_conv" in text


def test_the_gated_norm_is_the_norm_of_the_gated():
    rng = np.random.default_rng(6)
    y, z = (jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    np.testing.assert_allclose(
        nn_ops.gated_rms_norm.fn(y, z, g, eps=1e-5),
        ref.rms_norm(y * jax.nn.silu(z), g, 1e-5), rtol=1e-5, atol=1e-6)


# ---- grouped-query attention -------------------------------------------------

def _repeated_kv_attention(q, k, v, heads, kv_heads):
    """``xla_attention`` with every key-value head repeated for its group."""
    rows, seq, _ = q.shape
    d = q.shape[-1] // heads

    def bhsd(a, n):
        return jnp.transpose(a.reshape(rows, seq, n, d), (0, 2, 1, 3))

    rep = heads // kv_heads
    out = nn_ops.xla_attention(
        bhsd(q, heads), jnp.repeat(bhsd(k, kv_heads), rep, axis=1),
        jnp.repeat(bhsd(v, kv_heads), rep, axis=1), causal=True)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(q.shape)


@pytest.fixture(scope="module")
def grouped_operands():
    rng = np.random.default_rng(2)
    rows, seq, heads, kv_heads, d = 2, 384, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(rows, seq, heads * d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(rows, seq, kv_heads * d)),
                        jnp.float32) for _ in range(2))
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    want = jax.value_and_grad(lambda q, k, v: jnp.sum(
        _repeated_kv_attention(q, k, v, heads, kv_heads) * w),
        argnums=(0, 1, 2))(q, k, v)
    return (q, k, v, w, heads, kv_heads), want


@pytest.mark.parametrize("form,blocks", [
    ("kernel", (128, 128)), ("kernel", (384, 128)), ("kernel", (128, 384)),
    ("xla", None)], ids=["128x128", "384x128", "128x384", "xla"])
def test_grouped_attention_is_attention_on_repeated_heads(grouped_operands,
                                                          form, blocks):
    """The grouped kernels (stacked rows, streamed key blocks, dk and dv
    summed over the group) and the XLA form against ``xla_attention`` with
    the key-value heads repeated: the result and all three gradients."""
    (q, k, v, w, heads, kv_heads), (want_value, want_grads) = grouped_operands
    if form == "kernel":
        def attend(q, k, v):
            return pk.flash_attention_grouped(q, k, v, heads, kv_heads,
                                              blocks=blocks, interpret=True)
    else:
        def attend(q, k, v):
            return nn_ops.xla_grouped_attention(q, k, v, heads, kv_heads,
                                                block=128)
    value, grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(attend(q, k, v) * w),
        argnums=(0, 1, 2))(q, k, v)
    assert float(value) == pytest.approx(float(want_value), rel=1e-5)
    for name, g, r in zip("qkv", grads, want_grads):
        assert float(jnp.linalg.norm(g - r)) <= \
            1e-5 * float(jnp.linalg.norm(r)), name


@pytest.mark.parametrize("shape,chip,path", [
    ((32768, 64, 32, 8), True, "grouped"), ((32768, 64, 32, 8), False, "xla"),
    ((32768, 64, 32, 5), True, "xla"), ((1000, 64, 32, 8), True, "xla"),
    ((256, 128, 4, 4), True, "grouped"), ((256, 512, 4, 2), True, "xla")],
    ids=["published", "no_chip", "heads_do_not_divide", "ragged",
         "one_head_a_group", "head_too_wide"])
def test_attention_path_rows_of_the_grouped_form(monkeypatch, shape, chip,
                                                 path):
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: chip)
    before = nn_ops.attention_dispatch_stats()
    assert nn_ops._attention_path("grouped", shape) == path
    assert nn_ops.attention_dispatch_stats() == before     # deciding is pure


def test_the_grouped_op_is_counted_and_scoped(chip_present_interpreted,
                                              grouped_operands):
    (q, k, v, _, heads, kv_heads), _ = grouped_operands
    before = nn_ops.attention_dispatch_stats()
    fn = lambda q, k, v: nn_ops.grouped_attention.fn(
        q, k, v, num_heads=heads, num_kv_heads=kv_heads)
    got = fn(q, k, v)
    after = nn_ops.attention_dispatch_stats()
    assert after == dict(before, grouped=before["grouped"] + 1)
    np.testing.assert_allclose(
        got, _repeated_kv_attention(q, k, v, heads, kv_heads), rtol=1e-4,
        atol=1e-5)
    text = jax.jit(fn).lower(q, k, v).as_text(debug_info=True)
    assert "attention" in text


# ---- the faults, the presets, the trainer ------------------------------------

@pytest.mark.parametrize("planted", [{"fault": f} for f in ref.FAULTS]
                         + [{"precision": "fp8"}],
                         ids=ref.FAULTS + ("fp8_control",))
def test_planted_faults_move_the_reference(model_and_reference, planted):
    """Each planted fault, and the control's precision, moves some leaf's
    gradient by more than a hundredth of its norm."""
    m = model_and_reference
    moved = jax.grad(lambda w: ref.loss(w, m["tokens"], TINY, **planted))(
        m["weights"])
    assert max(float(jnp.linalg.norm(moved[k] - want)
                     / jnp.linalg.norm(want))
               for k, want in m["ref_grads"].items()) > 1e-2


def test_the_tiny_preset_and_the_logits():
    from mxnet_tpu.models.hybrid_ssm import hybrid_ssm_tiny
    net = hybrid_ssm_tiny()
    net.initialize(mx.init.Xavier())
    toks = mx.nd.array(_tokens(seq=24), dtype="int32")
    logits = net(toks)
    assert logits.shape == (2, 24, 48)
    labels = mx.nd.array(ref.make_labels(_tokens(seq=24)), dtype="int32")
    loss = float(net(toks, labels).asnumpy())
    lp = jax.nn.log_softmax(logits._data.astype(jnp.float32), axis=-1)
    lab = labels._data
    want = -jnp.where(lab >= 0, jnp.take_along_axis(
        lp, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0], 0.0).sum() \
        / (lab >= 0).sum()
    assert loss == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
def test_trainer_step_in_bfloat16(recompute):
    """A ``ShardedTrainer`` step on the tiny configuration in bfloat16, with
    and without recomputed layers: the same first loss, and it falls."""
    from mxnet_tpu import parallel
    cfg = dict(TINY, recompute=recompute)
    net, _ = hybrid_ssm.build_net(cfg, 3, "bfloat16")
    trainer = parallel.ShardedTrainer(
        net, lambda out, _label: out, "adam", {"learning_rate": 3e-3},
        mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]),
        dtype="bfloat16")
    data = tuple(mx.nd.array(a, dtype="int32")
                 for a in mla_moe.as_program_batch(_tokens()))
    label = mx.nd.array(np.zeros((2,), np.float32))
    if recompute:
        pure, params = functionalize(net, train=True)
        values = [p.data()._data for p in params]
        text = str(jax.make_jaxpr(jax.grad(lambda v: pure(
            jax.random.PRNGKey(0), v, data[0]._data, data[1]._data)[0][0]))(
                values))
        assert text.count("remat") >= 3 or "checkpoint" in text
    losses = [float(trainer.step(data, label).asnumpy()) for _ in range(4)]
    assert losses[0] == pytest.approx(np.log(48), rel=0.05)
    assert losses[-1] < losses[0]


def test_a_model_kept_off_the_mesh_is_traced_for_the_mesh():
    """The Gluon model's own copy on ANOTHER device than the trainer's mesh
    (the benchmark's builder keeps it on the host, where 4 bytes a parameter
    have room): ``functionalize`` labels the traced inputs with the context
    the block's copy is on, so the trainer traces the model like one on the
    mesh. ``Parameter`` itself keeps its rule: a context it was not
    initialized on raises, in an eager call and swapped in for a trace
    alike."""
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.parameter import swapped_in
    data = mx.nd.array(np.random.default_rng(0).normal(size=(4, 8)))
    label = mx.nd.array(np.zeros((4,), np.float32))
    losses = {}
    for ctx in (mx.cpu(0), mx.cpu(1)):
        mx.random.seed(5)
        net = gluon.nn.HybridSequential(prefix="off_mesh_")
        net.add(gluon.nn.Dense(5, in_units=8), gluon.nn.Dense(3, in_units=5))
        net.initialize(mx.init.Xavier(), ctx=ctx)
        trainer = parallel.ShardedTrainer(
            net, lambda out, _label: (out * out).sum(), "sgd",
            {"learning_rate": 1e-2},
            mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]))
        losses[ctx] = [float(trainer.step(data, label).asnumpy())
                       for _ in range(2)]
        assert net.collect_params().list_ctx() == [ctx]
    assert losses[mx.cpu(1)] == losses[mx.cpu(0)]
    weight = net[1].weight      # a child's: traced arrays carry no label
    with pytest.raises(RuntimeError, match="not initialized on context"):
        weight.data(mx.cpu(0))
    with pytest.raises(RuntimeError, match="not initialized on context"):
        net(data)                   # eager, inputs on cpu(0), copy on cpu(1)

    def doubled(v):
        with swapped_in([weight], [v]):
            return weight.data(mx.cpu(0))._data * 2

    with pytest.raises(RuntimeError, match="not initialized on context"):
        jax.jit(doubled)(jnp.ones((3, 5)))
