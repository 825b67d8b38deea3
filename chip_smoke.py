#!/usr/bin/env python3
"""Chip smoke: the two paths users depend on, run once on the TPU.

    python chip_smoke.py              # one chip: three phases
    python chip_smoke.py --chips 4    # four chips: dp=4 vs dp=1, nothing else

One process, one chip by default. It refuses to start unless
``jax.devices()[0].platform == "tpu"`` — it never sets ``JAX_PLATFORMS``
and never falls back. Every phase raises on failure, so any failure is a
non-zero exit and no result line. Phases (full width, random weights from
``--seed``, host-made batches):

- ``train_resnet`` — ResNet-50 v1, 224x224, batch 32, bf16, through
  ``parallel.ShardedTrainer`` on a one-device mesh: three ``step`` calls,
  then one ``step_many`` span of 8.
- ``train_bert``   — BERT-base pretraining step, s512 b16 bf16, padding
  mask, dropout as shipped; the compiled step must contain the Pallas
  attention kernels. Then ``dot_product_attention`` at (16, 512, 12, 64)
  with a padding mask: the flash kernels against the op's XLA form
  (``ops.nn.xla_attention``, called directly), on the chip, and the packed
  entry (value and packed gradient) against the separate-operand kernels,
  bit for bit.
- ``serve_lm``     — ``transformer_lm_base`` behind ``DecodeEngine`` ->
  ``GenerationScheduler`` -> ``ModelServer`` in this process: four
  concurrent HTTP ``POST /generate`` streams + ``GET /healthz``; served
  tokens are checked against greedy decoding by full re-forward of the
  same model, the decode program must have compiled once.

Earlier lines report progress, seconds per phase (compile and run apart),
losses, the compile-cache directory and its hit/miss counts, and whether
64-bit types reached the compiled programs. The LAST line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as jax reports
the device.
"""
import argparse
import json
import os
import re
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np


# what each phase runs at: full width of models the repo ships
RESNET = dict(batch=32, image=224, span=8, classes=1000)
BERT = dict(batch=16, seq=512)
ATTENTION = dict(shape=(16, 512, 12, 64))            # (B, S, H, D)
LM = dict(slots=8, max_seq=1024, ladder=(32, 128, 512), new_tokens=32,
          vocab=32000, prompt_lengths=(32, 512))     # + two from the seed
DP4 = dict(batch=64, seq=128)
BERT_VOCAB = 30522


def log(msg, **fields):
    """One progress line on stdout: ``msg`` or ``msg {json fields}``."""
    print(msg + (" " + json.dumps(fields) if fields else ""), flush=True)


def clock():
    return time.perf_counter()


def host(x):
    """Device value -> numpy, waiting for the device."""
    return np.asarray(x.asnumpy() if hasattr(x, "asnumpy") else x)


def on_devices(arr):
    return {s.device for s in arr.addressable_shards}


def has_64bit(text):
    return {"f64": "f64[" in text, "s64": "s64[" in text}


def first_and_steady(fn, n):
    """Call ``fn`` ``n`` times, each to completion. Returns ``(results,
    first_s, steady_s)``: the first call pays trace + compile, the median
    of the rest is the steady run time; compile ~= first - steady."""
    out, secs = [], []
    for _ in range(n):
        t0 = clock()
        out.append(host(fn()))
        secs.append(clock() - t0)
    return out, secs[0], float(np.median(secs[1:])) if n > 1 else None


# --------------------------------------------------------------- train_resnet

def phase_train_resnet(dev, seed):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    batch, image, span = RESNET["batch"], RESNET["image"], RESNET["span"]
    mx.random.seed(seed)
    rng = np.random.default_rng(seed)
    t0 = clock()
    net = vision.resnet50_v1()
    # deferred shapes resolve on one eager forward; on the host CPU, so
    # the chip compiles the training programs and not ~200 one-op ones
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net(mx.nd.zeros((1, 3, image, image), ctx=mx.cpu()))
    net.cast("bfloat16")
    net.collect_params().reset_ctx(mx.current_context())
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh(dp=1, devices=[dev]))
    build_s = clock() - t0
    name, before = next((k, host(v).astype(np.float32))
                        for k, v in trainer.param_values.items()
                        if k.endswith("conv0_weight"))

    def batch_of(*lead):
        x = rng.standard_normal(lead + (batch, 3, image, image),
                                dtype=np.float32)
        y = rng.integers(0, RESNET["classes"],
                         lead + (batch,)).astype(np.float32)
        return mx.nd.array(x, dtype="bfloat16"), mx.nd.array(y)

    batches = [batch_of() for _ in range(3)]
    spans = [batch_of(span) for _ in range(2)]
    feed = iter(batches)
    losses, first_s, step_s = first_and_steady(
        lambda: trainer.step(*next(feed)), 3)
    feed = iter(spans)
    (span_losses, span_losses2), span_first_s, span_s = first_and_steady(
        lambda: trainer.step_many(*next(feed)), 2)

    all_losses = [float(l) for l in losses] + span_losses.tolist() \
        + span_losses2.tolist()
    assert np.isfinite(all_losses).all(), all_losses
    assert len(set(all_losses)) > 1, all_losses
    after = trainer.param_values[name]
    assert on_devices(after) == {dev}, on_devices(after)
    assert all(on_devices(v) == {dev}
               for v in trainer.param_values.values())
    assert not np.array_equal(before, host(after).astype(np.float32))
    log("phase train_resnet", build_s=round(build_s, 2),
        step_compile_s=round(first_s - step_s, 2), step_s=round(step_s, 4),
        span8_compile_s=round(span_first_s - span_s, 2),
        span8_s=round(span_s, 4), losses=[round(l, 4) for l in all_losses])
    return trainer, batches[0]


# ----------------------------------------------------------------- train_bert

def make_bert(seq, dropout=None):
    """BERT-base; ``dropout=None`` keeps the rate the model ships."""
    from mxnet_tpu.models.bert import bert_base
    kw = {} if dropout is None else {"dropout": dropout}
    return bert_base(max_length=seq, **kw)


def bert_trainer(seed, mesh, seq, dropout=None):
    """BERT-base + pretraining loss behind ShardedTrainer (adam, bf16),
    with the padding mask real pretraining batches carry."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models.bert import BERTPretrainingLoss

    class PretrainStep(HybridBlock):
        """Whole pretraining loss inside the block: the trainer sees a
        scalar. Gather-first decode on the masked slots."""

        def __init__(self, bert, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.bert = bert
            self.loss = BERTPretrainingLoss(picked=True)

        def hybrid_forward(self, F, tokens, segments, valid_len, positions,
                           labels, weights, nsp_labels):
            _, _, mlm_logits, nsp_logits = self.bert(
                tokens, segments, valid_len, positions)
            return self.loss(mlm_logits, nsp_logits, labels, positions,
                             weights, nsp_labels)

    mx.random.seed(seed)
    net = make_bert(seq, dropout)
    net.initialize(mx.init.Xavier())
    return parallel.ShardedTrainer(
        PretrainStep(net), lambda out, _label: out, "adam",
        {"learning_rate": 1e-4}, mesh=mesh, dtype="bfloat16")


def bert_batch(rng, batch, seq, n_masks=20):
    vocab = BERT_VOCAB
    import mxnet_tpu as mx
    tokens = rng.integers(4, vocab, (batch, seq)).astype(np.float32)
    segments = np.zeros((batch, seq), np.float32)
    segments[:, seq // 2:] = 1.0
    valid_len = rng.integers(seq // 2, seq + 1, (batch,)).astype(np.float32)
    positions = np.stack([rng.choice(seq // 2, n_masks, replace=False)
                          for _ in range(batch)]).astype(np.float32)
    labels = rng.integers(4, vocab, (batch, n_masks)).astype(np.float32)
    weights = np.ones((batch, n_masks), np.float32)
    nsp = rng.integers(0, 2, (batch,)).astype(np.float32)
    data = tuple(mx.nd.array(a) for a in (
        tokens, segments, valid_len, positions, labels, weights, nsp))
    return data, mx.nd.zeros((batch,))


def assert_flash_in(text, what):
    n = text.count("tpu_custom_call")
    assert n >= 2, ("%s: %d Pallas custom calls in the compiled step — "
                    "want the forward and the backward kernel"
                    % (what, n))
    return n


def phase_train_bert(dev, seed):
    from mxnet_tpu import parallel

    batch, seq = BERT["batch"], BERT["seq"]
    rng = np.random.default_rng(seed)
    t0 = clock()
    trainer = bert_trainer(seed, parallel.make_mesh(dp=1, devices=[dev]),
                           seq)
    build_s = clock() - t0
    b0 = bert_batch(rng, batch, seq)
    t0 = clock()
    text = trainer.lower_step(*b0).compile().as_text()
    compile_s = clock() - t0
    kernels = assert_flash_in(text, "BERT-base s512 b16 step")
    batches = iter([b0, bert_batch(rng, batch, seq),
                    bert_batch(rng, batch, seq)])
    losses, first_s, step_s = first_and_steady(
        lambda: trainer.step(*next(batches)), 3)
    losses = [float(l) for l in losses]
    assert np.isfinite(losses).all() and len(set(losses)) > 1, losses
    log("phase train_bert", build_s=round(build_s, 2),
        compile_s=round(compile_s, 2), first_step_s=round(first_s, 2),
        step_s=round(step_s, 4), pallas_custom_calls=kernels,
        losses=[round(l, 4) for l in losses])
    return trainer, b0


def check_flash_against_xla(seed):
    """The first on-device correctness check of the kernels: the op's
    flash path (bf16, as the models call it) against its XLA form on the
    same values in f32."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import nn as nn_ops

    B, S, H, D = ATTENTION["shape"]
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D),
                                               dtype=np.float32),
                           jnp.bfloat16) for _ in range(3))
    valid = rng.integers(S // 2, S + 1, (B,))
    mask = jnp.asarray(np.arange(S)[None, :] < valid[:, None], jnp.int32)

    def attend(q, k, v, mask):
        return nn_ops.dot_product_attention.fn(q, k, v, mask=mask,
                                               layout="BSHD")

    flash = jax.jit(attend)
    text = flash.lower(q, k, v, mask).compile().as_text()
    assert "tpu_custom_call" in text, "dispatcher did not take the kernel"
    t0 = clock()
    out = host(flash(q, k, v, mask)).astype(np.float32)
    flash_s = clock() - t0

    def heads_first(a):
        return jnp.transpose(a, (0, 2, 1, 3))

    xla = jax.jit(lambda q, k, v, mask: heads_first(nn_ops.xla_attention(
        heads_first(q), heads_first(k), heads_first(v), mask)))
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        assert "tpu_custom_call" not in \
            xla.lower(*f32, mask).compile().as_text()
        ref = host(xla(*f32, mask))
    assert out.shape == ref.shape == (B, S, H, D)
    assert np.isfinite(out).all()
    err = float(np.abs(out - ref).max())
    # bf16 tolerance, fixed beforehand: the output is rounded to bf16
    # (2^-8 relative) after a bf16 P.V matmul accumulated in f32
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
    log("check flash_vs_xla", shape=[B, S, H, D], max_abs_err=err,
        ref_abs_max=float(np.abs(ref).max()), flash_s=round(flash_s, 3))

    # the packed entry (what the models call): the same kernels reading q,
    # k, v as column blocks of one (B, S, 3*H*D) array and writing ONE
    # packed gradient in place, against the separate-operand call above
    def loss(attend_fn):
        return lambda *a: jnp.sum(attend_fn(*a).astype(jnp.float32) ** 2)

    qkv = jnp.concatenate([a.reshape(B, S, H * D) for a in (q, k, v)], -1)
    packed = jax.jit(jax.value_and_grad(loss(
        lambda a: nn_ops.packed_self_attention.fn(a, mask=mask,
                                                  num_heads=H))))
    text = packed.lower(qkv).compile().as_text()
    # forward and the one fused backward (the sequence is one key block)
    assert text.count("tpu_custom_call") == 2, "packed entry fell back"
    value, d_qkv = packed(qkv)
    want, grads = jax.jit(jax.value_and_grad(
        loss(lambda q, k, v: attend(q, k, v, mask)), argnums=(0, 1, 2)))(
            q, k, v)
    want_d = np.concatenate([host(g).reshape(B, S, H * D) for g in grads],
                            -1).astype(np.float32)
    d_qkv = host(d_qkv).astype(np.float32)
    # the two sums run over differently shaped arrays: same value up to
    # the order of the additions
    np.testing.assert_allclose(float(value), float(want), rtol=1e-5)
    np.testing.assert_array_equal(d_qkv, want_d)
    assert np.isfinite(d_qkv).all() and np.abs(d_qkv).max() > 0
    log("check packed_vs_split", loss=float(value),
        grad_abs_max=float(np.abs(d_qkv).max()), equal=True)


# ------------------------------------------------------------------- serve_lm

def post_generate(url, prompt, max_new_tokens):
    """One streamed ``POST /generate``; returns ``(tokens, last_line)``."""
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens,
                       "temperature": 0.0}).encode()
    with urllib.request.urlopen(urllib.request.Request(
            url + "/generate", data=body), timeout=900) as resp:
        assert resp.status == 200, resp.status
        lines = [json.loads(l) for l in resp if l.strip()]
    return [l["token"] for l in lines if "token" in l], lines[-1]


def greedy_deficits(forward, prompt, got):
    """Greedy decoding by full re-forward, the pattern of
    tests/test_generation.py: token i must be ``argmax logits(prompt +
    got[:i])[-1]``, and a causal model yields all those logits in ONE
    forward over ``prompt + got[:-1]``. Returns ``(exact, deficits)``:
    how many served tokens ARE the reference argmax, and per token how
    far its reference logit sits below the row's max, in units of the
    row's standard deviation (0 for an exact match)."""
    import mxnet_tpu as mx
    seq = np.asarray(list(prompt) + got[:-1], np.int32)[None]
    logits = host(forward(mx.nd.array(seq)))[0].astype(np.float64)
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(got)]
    deficits = [(row.max() - row[t]) / row.std() for row, t in
                zip(rows, got)]
    return sum(d == 0 for d in deficits), deficits


def make_lm():
    from mxnet_tpu import models
    return models.transformer_lm_base(vocab_size=LM["vocab"])


def phase_serve_lm(dev, seed):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.cached_op import CachedOp
    from mxnet_tpu.serving import ModelServer
    from mxnet_tpu.serving.generation import (DecodeEngine,
                                              GenerationScheduler)

    new_tokens, vocab = LM["new_tokens"], LM["vocab"]
    mx.random.seed(seed)
    rng = np.random.default_rng(seed)
    # float32 weights and arenas as shipped. f32 matmuls at full precision
    # for this phase: greedy argmax over 32,000 near-flat logits of a
    # random model is decided in the 4th digit, which the default
    # one-pass bf16 matmul does not keep — process-wide, because the
    # scheduler's worker thread traces the programs
    prev_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    lm = make_lm()
    lm.initialize(mx.init.Xavier())
    # the upper rungs prefill through the causal flash kernel (seq % 128
    # == 0), the lowest through the XLA path
    eng = DecodeEngine(lm, num_slots=LM["slots"], max_seq=LM["max_seq"],
                       ladder=LM["ladder"])
    sched = GenerationScheduler(eng)
    srv = ModelServer(None, port=0, generator=sched).start()
    try:
        lo, hi = LM["prompt_lengths"]
        lengths = [lo, hi] + rng.integers(lo + 1, hi, 2).tolist()

        def serve_all():
            """Four concurrent streams of fresh prompts; returns
            ``[(prompt, tokens, last_line)]``."""
            prompts = [rng.integers(0, vocab, n).tolist() for n in lengths]
            with ThreadPoolExecutor(len(prompts)) as pool:
                results = list(pool.map(
                    lambda p: post_generate(srv.url, p, new_tokens),
                    prompts))
            return [(p,) + r for p, r in zip(prompts, results)]

        t0 = clock()
        cold = serve_all()
        cold_s = clock() - t0
        t0 = clock()
        warm = serve_all()      # same lengths: every program resident
        warm_s = clock() - t0
        with urllib.request.urlopen(srv.url + "/healthz", timeout=30) as r:
            assert r.status == 200
            health = json.loads(r.read())
        assert health["status"] == "ok", health

        for _prompt, toks, done in cold + warm:
            assert done.get("done") is True, done
            assert len(toks) == new_tokens
            assert all(0 <= t < vocab for t in toks)
        stats = eng.compile_stats()
        assert stats["decode"]["misses"] == 1, stats
        assert stats["prefill"]["misses"] <= len(eng.ladder), stats
        for arena in (eng.cache.k_arena, eng.cache.v_arena):
            assert on_devices(arena._data) == {dev}

        # reference: the model's own full forward as one jitted program
        # (weights as arguments, like the engine's programs)
        def reforward(tokens, *pvals):
            with eng.bound_params(pvals):
                return lm(tokens)

        reforward_op = CachedOp(reforward, name="smoke.reforward")

        def forward(tokens):
            return reforward_op(tokens, *eng.param_args())

        report = {}
        for label, i, tol in (("xla_prefill_shortest", 0, 1e-3),
                              ("flash_prefill_longest", 1, 5e-2)):
            prompt, toks, _done = cold[i]
            exact, deficits = greedy_deficits(forward, prompt, toks)
            report[label] = {"prompt": len(prompt),
                             "exact": "%d/%d" % (exact, new_tokens),
                             "max_deficit_sigma": float(max(deficits))}
            # tolerances fixed beforehand (PERF.md, Bring-up): a served
            # token may differ from the reference argmax only at a tie
            # within `tol` row-sigma; a wrong cache is off by O(1) sigma
            assert max(deficits) <= tol, (label, deficits)
        text = eng.lower_decode().compile().as_text()
    finally:
        srv.stop()
        jax.config.update("jax_default_matmul_precision", prev_precision)
    log("phase serve_lm", cold_s=round(cold_s, 2), warm_s=round(warm_s, 2),
        compile_s=round(cold_s - warm_s, 2), prompt_lengths=lengths,
        new_tokens=new_tokens, greedy_vs_reforward=report,
        compile_stats={k: v["misses"] for k, v in stats.items()})
    return text


# ------------------------------------------------------------------ four chips

def phase_dp4_vs_dp1(devs, seed):
    """BERT-base s128, global batch 64: three steps on dp=4 against the
    same three steps on dp=1 (dropout 0, so the two see the same math),
    then one dp=4 step with dropout as shipped."""
    from mxnet_tpu import parallel

    batch, seq = DP4["batch"], DP4["seq"]

    def run(mesh, dropout, steps):
        rng = np.random.default_rng(seed)       # same batches on both
        trainer = bert_trainer(seed, mesh, seq, dropout)
        b0 = bert_batch(rng, batch, seq)
        compiled = trainer.lower_step(*b0).compile()
        batches = iter([b0] + [bert_batch(rng, batch, seq)
                               for _ in range(steps - 1)])
        losses, first_s, step_s = first_and_steady(
            lambda: trainer.step(*next(batches)), steps)
        return trainer, compiled, [float(l) for l in losses], first_s, \
            step_s

    mesh4 = parallel.make_mesh(dp=4, devices=devs[:4])
    mesh1 = parallel.make_mesh(dp=1, devices=devs[:1])
    t4, compiled4, loss4, first4, step4 = run(mesh4, 0.0, 3)
    _, _, loss1, first1, step1 = run(mesh1, 0.0, 3)
    assert np.isfinite(loss4 + loss1).all(), (loss4, loss1)
    # bf16 tolerance, fixed beforehand: the step-1 losses differ only in
    # reduction order; later ones also by bf16 rounding of the updates
    np.testing.assert_allclose(loss4, loss1, rtol=2e-2)

    text = compiled4.as_text()
    kernels = assert_flash_in(text, "BERT-base s128 dp=4 step")
    assert "all-reduce" in text, "no gradient all-reduce in the dp=4 step"
    # q/k/v reach the kernels batch-sharded: an all-gather producing a
    # tensor with the GLOBAL batch in front would be the partitioner
    # undoing the sharding around them
    gathered = [line for line in text.splitlines() if "all-gather" in line
                and re.search(r"\[%d,%d[,\]]" % (batch, seq), line)]
    assert not gathered, gathered[:3]
    for v in t4.param_values.values():
        assert on_devices(v) == set(devs[:4]), on_devices(v)
    # the batch: the step's last 8 arguments (None = pruned as unused)
    batch_shardings = [s for s in compiled4.input_shardings[0][-8:]
                       if s is not None]
    assert len(batch_shardings) >= 7, compiled4.input_shardings[0][-8:]
    for s in batch_shardings:
        assert len(s.device_set) == 4 and not s.is_fully_replicated, s

    _, compiled_drop, loss_drop, _, _ = run(mesh4, None, 1)
    assert np.isfinite(loss_drop).all(), loss_drop
    assert_flash_in(compiled_drop.as_text(), "dp=4 step with dropout")
    log("phase dp4_vs_dp1", dp4_losses=loss4, dp1_losses=loss1,
        max_rel_diff=float(np.max(np.abs(np.subtract(loss4, loss1))
                                  / np.abs(loss1))),
        dp4_first_step_s=round(first4, 2), dp4_step_s=round(step4, 4),
        dp1_first_step_s=round(first1, 2), dp1_step_s=round(step1, 4),
        pallas_custom_calls=kernels, dp4_dropout_loss=loss_drop[0])


# ----------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the dp=4 against dp=1 comparison and no "
                         "other phase (default 1: the three phases)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("chip_smoke.py runs on the chip: jax came up on %r (%s)"
                 % (devs[0].platform, devs))
    if len(devs) < args.chips:
        sys.exit("--chips %d: jax reports %d device(s)"
                 % (args.chips, len(devs)))
    from mxnet_tpu import pcache

    t_start = clock()
    log("devices", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__)
    log("compile cache", dir=pcache.cache_dir(),
        placed_by="JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "pcache default")

    if args.chips == 4:
        phase_dp4_vs_dp1(devs, args.seed)
    else:
        resnet, resnet_batch = phase_train_resnet(devs[0], args.seed)
        bert, bert_b0 = phase_train_bert(devs[0], args.seed)
        check_flash_against_xla(args.seed)
        decode_text = phase_serve_lm(devs[0], args.seed)
        # jax_enable_x64 is on process-wide (mxnet_tpu/__init__.py) and
        # 64-bit types are emulated on this chip: report what got through
        log("x64 in compiled programs",
            resnet_step=has_64bit(
                resnet.lower_step(*resnet_batch).compile().as_text()),
            bert_step=has_64bit(
                bert.lower_step(*bert_b0).compile().as_text()),
            decode_step=has_64bit(decode_text))

    st = pcache.stats()
    log("compile cache stats", hits=st["disk_hits"], misses=st["disk_misses"],
        requests=st["requests"], dir=st["dir"])
    log("total", seconds=round(clock() - t_start, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
