"""The chunked loss head (``ops/nn.py::_chunked_ce``) in one pass: the loop
that computes the loss forms the head's gradient from the logits it holds.
Against a plain two-pass rule written out here (a forward loop that keeps
the log-sum-exp, a backward loop that computes each chunk's logits again),
to the bit; the lowered text's products and loops; the primal alone; no
target at all."""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mxnet_tpu.ops import nn as nn_ops

T, D, V = 96, 32, 200


def _logits(h, weight):
    return lax.dot_general(h, weight, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def two_pass(hidden, weight, labels, chunk):
    return _two_pass_fwd(hidden, weight, labels, chunk)[0]


def _two_pass_fwd(hidden, weight, labels, chunk):
    valid = labels >= 0
    count = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)

    def one(_, args):
        h, lab = args
        logits = _logits(h, weight)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lab, 0)[:, None], axis=-1)[:, 0]
        return None, (lse, jnp.where(lab >= 0, lse - picked, 0.0))

    _, (lse, nll) = lax.scan(one, None, (
        hidden.reshape(-1, chunk, hidden.shape[-1]),
        labels.reshape(-1, chunk)))
    return jnp.sum(nll) / count, (hidden, weight, labels, lse.reshape(-1),
                                  count)


def _two_pass_bwd(chunk, res, g):
    hidden, weight, labels, lse, count = res
    ids = jnp.arange(weight.shape[0], dtype=labels.dtype)

    def one(dw, args):
        h, lab, l = args
        probs = jnp.exp(_logits(h, weight) - l[:, None])
        d = jnp.where((lab >= 0)[:, None],
                      probs - (ids[None, :] == lab[:, None]), 0.0)
        d = (d * (g / count)).astype(hidden.dtype)
        dw = dw + lax.dot_general(d, h, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, jnp.matmul(d, weight)

    dw, dh = lax.scan(one, jnp.zeros(weight.shape, jnp.float32), (
        hidden.reshape(-1, chunk, hidden.shape[-1]),
        labels.reshape(-1, chunk), lse.reshape(-1, chunk)))
    return dh.reshape(hidden.shape), dw.astype(weight.dtype), None


two_pass.defvjp(_two_pass_fwd, _two_pass_bwd)


def _operands(dtype, heads=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    hidden = jax.random.normal(ks[0], (T, D), jnp.float32).astype(dtype)
    weight = jax.random.normal(ks[1], (heads * V, D),
                               jnp.float32).astype(dtype)
    labels = jax.random.randint(ks[2], (T, heads) if heads > 1 else (T,),
                                -V // 8, V)         # a ninth negative
    return hidden, weight, labels


def _head(labels, chunk):
    return lambda h, w: nn_ops.chunked_softmax_cross_entropy.fn(
        h, w, labels, chunk=chunk)


@pytest.mark.parametrize("cotangent", [1.0, 0.5])
@pytest.mark.parametrize("chunk", [T, 16, 36], ids=[
    "one_chunk", "several", "not_a_divisor"])
def test_one_pass_equals_the_two_pass_rule_to_the_bit(chunk, cotangent):
    hidden, weight, labels = _operands(jnp.bfloat16)
    assert int(jnp.sum(labels < 0)) > 0
    size = nn_ops._ce_chunks(T, chunk)
    assert size == (chunk if T % chunk == 0 else T)
    head = _head(labels, chunk)
    got = jax.jit(jax.value_and_grad(
        lambda h, w: cotangent * head(h, w), (0, 1)))(hidden, weight)
    want = jax.jit(jax.value_and_grad(
        lambda h, w: cotangent * two_pass(h, w, labels.astype(jnp.int32),
                                          size), (0, 1)))(hidden, weight)
    assert got[1][0].dtype == got[1][1].dtype == jnp.bfloat16
    assert float(jnp.linalg.norm(want[1][0].astype(jnp.float32))) > 0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("heads", [1, 3])
def test_any_cotangent_scales_the_gradient_of_the_whole_softmax(heads):
    hidden, weight, labels = _operands(jnp.float32, heads)
    lab = labels.reshape(T, heads)

    def whole(h, w):
        logp = jax.nn.log_softmax((h @ w.T).reshape(T, heads, V))
        ll = jnp.take_along_axis(logp, jnp.maximum(lab, 0)[..., None],
                                 -1)[..., 0]
        valid = lab >= 0
        return 3.0 * jnp.mean(-(ll * valid).sum(0) / valid.sum(0))

    head = _head(labels, 16)
    got = jax.value_and_grad(lambda h, w: 3.0 * head(h, w), (0, 1))(
        hidden, weight)
    want = jax.value_and_grad(whole, (0, 1))(hidden, weight)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=3e-7)


def _lowered(fn, *args, **how):
    return jax.jit(fn).lower(*args).as_text(**how)


def test_differentiated_it_lowers_to_three_products_and_one_loop():
    hidden, weight, labels = _operands(jnp.bfloat16)
    text = _lowered(jax.value_and_grad(_head(labels, 16), (0, 1)),
                    hidden, weight, debug_info=True)
    assert text.count("stablehlo.dot_general") == 3
    assert text.count("stablehlo.while") == 1
    assert "(loss_head)/" in text       # the scope the trace reader finds


def test_several_targets_a_position_share_the_loop_and_the_products():
    """Chunks outside, heads inside: ``hidden`` is cut into chunks by one
    loop, a chunk's ``d(hidden)`` is one product over the heads' rows, and
    nothing of the size (heads, positions, d) exists."""
    hidden, weight, labels = _operands(jnp.bfloat16, heads=3)
    text = _lowered(jax.value_and_grad(_head(labels, 16), (0, 1)),
                    hidden, weight)
    assert text.count("stablehlo.dot_general") == 3
    assert text.count("stablehlo.while") == 1
    assert not re.search(r"tensor<3x%dx%dx" % (T, D), text)
    assert not re.search(r"tensor<3x%dx16x%dx" % (T // 16, D), text)
    # one (chunks, chunk, d) array of hidden's, the loop's operand
    assert len(set(re.findall(r"tensor<%dx16x%dx\w+>" % (T // 16, D),
                              text))) == 1


def test_outside_a_gradient_it_is_the_logits_product_alone():
    hidden, weight, labels = _operands(jnp.bfloat16)
    text = _lowered(_head(labels, 16), hidden, weight)
    assert text.count("stablehlo.dot_general") == 1
    assert text.count("stablehlo.while") == 1
    assert "tensor<%dx%dxf32>" % (V, D) not in text
    value = jax.jit(_head(labels, 16))(hidden, weight)
    with_gradient, _ = jax.jit(jax.value_and_grad(_head(labels, 16)))(
        hidden, weight)
    assert float(value) == float(with_gradient)


def test_recomputed_it_pays_the_primal_and_the_three():
    """Under ``jax.checkpoint`` around a head the forward pass is the
    loss-only loop and the backward pass runs the differentiated loop:
    1 + 3 products, as the two-pass rule's four."""
    hidden, weight, labels = _operands(jnp.bfloat16)
    text = _lowered(jax.value_and_grad(jax.checkpoint(_head(labels, 16)),
                                       (0, 1)), hidden, weight)
    assert text.count("stablehlo.dot_general") == 4
    assert text.count("stablehlo.while") == 2


@pytest.mark.parametrize("heads", [1, 3])
def test_no_target_at_all_is_a_loss_of_zero_and_no_gradient(heads):
    hidden, weight, labels = _operands(jnp.bfloat16, heads)
    value, grads = jax.jit(jax.value_and_grad(
        _head(jnp.full_like(labels, -1), 16), (0, 1)))(hidden, weight)
    assert float(value) == 0.0
    for g in grads:
        assert not np.any(np.asarray(g, np.float32))    # zeros, no NaN


def test_a_head_without_targets_leaves_the_others_their_means():
    hidden, weight, labels = _operands(jnp.float32, heads=3)
    labels = labels.at[:, 1].set(-1)
    value, (dh, dw) = jax.value_and_grad(_head(labels, 16), (0, 1))(
        hidden, weight)
    alone = [float(_head(labels[:, j], 16)(hidden, weight[j * V:(j + 1) * V]))
             for j in range(3)]
    assert alone[1] == 0.0
    assert float(value) == pytest.approx(sum(alone) / 3, rel=1e-6)
    assert not np.any(np.asarray(dw[V:2 * V]))
    assert np.any(np.asarray(dw[:V])) and np.any(np.asarray(dw[2 * V:]))
    assert np.all(np.isfinite(np.asarray(dh)))
