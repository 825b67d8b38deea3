"""Language-model training benchmarks: BERT-base pretraining, Transformer
LM, LSTM LM — the BASELINE.md north-star configs beyond ResNet ("LSTM LM +
Transformer, BERT-base pretraining").

Unlike the CNN benchmark (bench.py), these are matmul-bound workloads where
the chip's measured 148.7 TFLOP/s bf16 matmul ceiling (PERF.md) is
reachable — this is the framework's MFU proof point.

Per model: runs a fused training span (lax.scan over fwd+bwd+update, bf16,
in-graph synthetic batches via ShardedTrainer.bench_span_fn), then reports
img-equiv throughput, model FLOP/s, and MFU. FLOPs are counted from the
model's actual dense weights (6*N per token for fwd+bwd+param-grad) plus
the analytic attention term; embedding gathers are excluded.

Usage:  python benchmark/bench_lm.py [bert|translm|lstm|all|bertdelta]

``bertdelta`` runs BERT pretraining twice — flash attention on and off
(the ``MXNET_FLASH_ATTENTION`` knob) — and records both runs plus a
``bert_base_pretrain_flash_delta_*`` record with the speedup, so the
flash-vs-XLA-softmax MFU gap (ROADMAP item 1b) lives in the artifact
instead of README prose. On CPU both runs take the XLA path (flash
dispatch requires a chip) and the delta record says so.

Env: LM_STEPS (span length, 64), LM_REPEAT (2), LM_BATCH (overrides per-
model default batch).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

def log(*a):
    print(*a, file=sys.stderr, flush=True)


def peak_tflops():
    """Published bf16 peak of the device the spans run on (telemetry's
    table, keyed by ``device_kind``); an unknown kind is an error."""
    import jax
    from mxnet_tpu.observability import telemetry
    dev = jax.devices()[0]
    peak = telemetry.device_peaks(dev)[0]
    if peak is None:
        raise RuntimeError("no published peak for device kind %r"
                           % (dev.device_kind,))
    return peak / 1e12


class LMLoss:
    """Next-token softmax cross-entropy over (..., V) logits vs (...)
    integer targets; f32 log-softmax regardless of model dtype."""

    def __call__(self, out, y):
        import jax
        import jax.numpy as jnp
        from mxnet_tpu.ndarray.ndarray import NDArray
        o = out._data if isinstance(out, NDArray) else out
        t = y._data if isinstance(y, NDArray) else y
        logp = jax.nn.log_softmax(o.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(
            logp.reshape(-1, logp.shape[-1]),
            t.reshape(-1).astype(jnp.int32)[:, None], axis=-1)
        return NDArray(-jnp.mean(ll))


def dense_param_elems(trainer, exclude=("embed", "embedding")):
    """Matmul-participating weight elements (grad-bearing, ndim>=2,
    non-embedding) — the N of the 6*N*token FLOP estimate."""
    n = 0
    for p in trainer._params:
        if getattr(p, "grad_req", "write") == "null":
            continue
        name = p.name.lower()
        if any(e in name for e in exclude):
            continue
        v = p.data()
        if len(v.shape) >= 2:
            n += int(np.prod(v.shape))
    return n


def run_span(trainer, make_batch, tag, steps, repeat, tokens_per_step,
             flops_per_step):
    log("compiling %s span (%d steps)..." % (tag, steps))
    t0 = time.time()
    l = trainer.bench_span_fn(steps, make_batch, tag=tag)
    lv = l.asnumpy()
    log("  warmup %.1fs, loss[0]=%.3f loss[-1]=%.3f"
        % (time.time() - t0, lv[0], lv[-1]))
    t0 = time.time()
    for _ in range(repeat):
        l = trainer.bench_span_fn(steps, make_batch, tag=tag)
    l.asnumpy()
    dt = time.time() - t0
    tok_s = steps * repeat * tokens_per_step / dt
    tflops = steps * repeat * flops_per_step / dt / 1e12
    return tok_s, tflops


def bench_bert(steps, repeat, batch=None, flash=None):
    """One BERT pretrain measurement. ``flash=False`` forces the XLA
    softmax path via the ``MXNET_FLASH_ATTENTION`` knob (restored after
    the run) and suffixes the metric ``_noflash``; ``None`` leaves the
    ambient knob alone."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.models.bert import bert_base
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "example", "bert"))
    from pretrain_bert import PretrainStep, PretrainLoss

    batch = batch or 64
    seq = int(os.environ.get("LM_SEQ", "128"))  # 512 = phase-2 pretraining
    vocab, n_masks = 30522, 20
    prev_flash = os.environ.get("MXNET_FLASH_ATTENTION")
    if flash is not None:
        # the override must cover model build AND the measured span (the
        # dispatch decision is taken at trace time); restored in the
        # finally below even when setup raises
        os.environ["MXNET_FLASH_ATTENTION"] = "1" if flash else "0"
    try:
        mx.random.seed(0)
        net = bert_base(vocab_size=vocab, max_length=seq)
        net.initialize(mx.init.Xavier())
        step = PretrainStep(net)
        mesh = parallel.make_mesh(dp=1)
        trainer = parallel.ShardedTrainer(step, PretrainLoss(), "adam",
                                          {"learning_rate": 1e-4},
                                          mesh=mesh, dtype="bfloat16")

        def make_batch(key):
            k1, k2, k3, k4 = jax.random.split(key, 4)
            tokens = jax.random.randint(k1, (batch, seq), 4, vocab
                                        ).astype(jnp.float32)
            segments = jnp.concatenate(
                [jnp.zeros((batch, seq // 2)),
                 jnp.ones((batch, seq // 2))],
                axis=1).astype(jnp.float32)
            positions = jax.random.randint(k2, (batch, n_masks), 0, seq
                                           ).astype(jnp.float32)
            labels = jax.random.randint(k3, (batch, n_masks), 4, vocab
                                        ).astype(jnp.float32)
            weights = jnp.ones((batch, n_masks), jnp.float32)
            nsp = jax.random.randint(k4, (batch,), 0, 2
                                     ).astype(jnp.float32)
            y = jnp.zeros((batch,), jnp.float32)  # unused dummy
            return (tokens, segments, positions, labels, weights, nsp), y

        # 6*N per token (fwd 2N + bwd 4N) + attention 12*s^2*d per seq
        # per layer for fwd, x3 for training. The MLM head (transform +
        # vocab decoder) runs gather-first on the M masked slots only, so
        # its params are billed at B*M tokens, not B*T (round-5 change;
        # reference GluonNLP decode semantics).
        n_dense = dense_param_elems(trainer, exclude=("embed", "embedding",
                                                      "mlm"))
        n_mlm = dense_param_elems(trainer) - n_dense
        tokens_per_step = batch * seq
        units, n_layers = 768, 12
        attn = 3 * n_layers * 4 * seq * seq * units * batch
        flops_per_step = (6 * n_dense * tokens_per_step
                          + 6 * n_mlm * batch * n_masks + attn)
        log("BERT-base: %.1fM body + %.1fM mlm-head dense params, "
            "%.1f GFLOP/step (b%d s%d m%d)"
            % (n_dense / 1e6, n_mlm / 1e6, flops_per_step / 1e9, batch,
               seq, n_masks))
        tok_s, tflops = run_span(trainer, make_batch, "bert", steps,
                                 repeat, tokens_per_step, flops_per_step)
    finally:
        if flash is not None:
            if prev_flash is None:
                os.environ.pop("MXNET_FLASH_ATTENTION", None)
            else:
                os.environ["MXNET_FLASH_ATTENTION"] = prev_flash
    # provenance from the ACTUAL dispatch conditions, not just the env
    import jax as _jax
    from mxnet_tpu.ops.nn import _flash_enabled
    from mxnet_tpu.ops.pallas_kernels import flash_attention_bshd_usable
    on_tpu = any(d.platform != "cpu" for d in _jax.devices())
    head_dim = units // 12
    usable = flash_attention_bshd_usable((batch, seq, 12, head_dim),
                                         head_dim)
    enabled = _flash_enabled() if flash is None else flash
    kern = ("bshd_flash" if on_tpu and usable and enabled
            else "xla_softmax")
    suffix = "_noflash" if flash is False else ""
    return dict(metric="bert_base_pretrain_tokens_per_sec_b%d_s%d%s"
                       % (batch, seq, suffix),
                kernel=kern,
                value=round(tok_s, 1), unit="tokens/s",
                seq_per_sec=round(tok_s / seq, 1),
                tflops=round(tflops, 1),
                mfu_peak=round(tflops / peak_tflops(), 3))


def bench_bert_flash_delta(steps, repeat, batch=None):
    """BERT with flash attention on vs off, plus the delta record —
    ROADMAP item 1b's with/without proof in one run. Returns THREE
    records (all three are appended to BENCH_LM.json)."""
    import jax
    with_flash = bench_bert(steps, repeat, batch, flash=True)
    without = bench_bert(steps, repeat, batch, flash=False)
    on_cpu = all(d.platform == "cpu" for d in jax.devices())
    delta = dict(
        metric=with_flash["metric"].replace(
            "_tokens_per_sec", "_flash_delta"),
        flash_kernel=with_flash["kernel"],
        flash_tokens_s=with_flash["value"],
        noflash_tokens_s=without["value"],
        flash_mfu_peak=with_flash["mfu_peak"],
        noflash_mfu_peak=without["mfu_peak"],
        speedup=round(with_flash["value"] /
                      max(without["value"], 1e-9), 3),
    )
    if on_cpu:
        delta["note"] = ("flash dispatch requires a TPU: both runs took "
                         "the XLA softmax path; rerun on chip for the "
                         "real delta")
    return [with_flash, without, delta]


def bench_translm(steps, repeat, batch=None):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models.transformer import TransformerLM

    batch = batch or 32
    seq, vocab = 512, 32000
    units, n_layers, heads, hidden = 768, 12, 12, 3072  # GPT-2-small class
    mx.random.seed(0)
    net = TransformerLM(vocab_size=vocab, units=units, num_layers=n_layers,
                        num_heads=heads, hidden_size=hidden,
                        max_len=seq, dropout=0.0)
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=1)

    trainer = parallel.ShardedTrainer(net, LMLoss(), "adam",
                                      {"learning_rate": 1e-4}, mesh=mesh,
                                      dtype="bfloat16")

    def make_batch(key):
        k1, k2 = jax.random.split(key)
        x = jax.random.randint(k1, (batch, seq), 0, vocab
                               ).astype(jnp.float32)
        y = jax.random.randint(k2, (batch, seq), 0, vocab
                               ).astype(jnp.float32)
        return (x,), y

    n_dense = dense_param_elems(trainer)
    tokens_per_step = batch * seq
    attn = 3 * n_layers * 4 * seq * seq * units * batch
    flops_per_step = 6 * n_dense * tokens_per_step + attn
    log("TransformerLM: %.1fM dense params, %.1f GFLOP/step (b%d s%d)"
        % (n_dense / 1e6, flops_per_step / 1e9, batch, seq))
    tok_s, tflops = run_span(trainer, make_batch, "translm", steps, repeat,
                             tokens_per_step, flops_per_step)
    return dict(metric="transformer_lm_tokens_per_sec_b%d_s%d"
                % (batch, seq),
                value=round(tok_s, 1), unit="tokens/s",
                tflops=round(tflops, 1),
                mfu_peak=round(tflops / peak_tflops(), 3))


def bench_lstm(steps, repeat, batch=None):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.models.lstm_lm import RNNModel

    batch = batch or 128
    seq, vocab, hidden, layers = 35, 33278, 1500, 2  # reference wikitext-2
    mx.random.seed(0)
    net = RNNModel(mode="lstm", vocab_size=vocab, num_embed=hidden,
                   num_hidden=hidden, num_layers=layers, dropout=0.0)
    net.initialize(mx.init.Xavier())
    mesh = parallel.make_mesh(dp=1)

    trainer = parallel.ShardedTrainer(net, LMLoss(), "sgd",
                                      {"learning_rate": 1.0}, mesh=mesh,
                                      dtype="bfloat16")

    def make_batch(key):
        k1, k2 = jax.random.split(key)
        x = jax.random.randint(k1, (seq, batch), 0, vocab
                               ).astype(jnp.float32)
        y = jax.random.randint(k2, (seq, batch), 0, vocab
                               ).astype(jnp.float32)
        return (x,), y

    n_dense = dense_param_elems(trainer)
    tokens_per_step = batch * seq
    flops_per_step = 6 * n_dense * tokens_per_step
    log("LSTM-LM: %.1fM dense params, %.1f GFLOP/step (b%d s%d)"
        % (n_dense / 1e6, flops_per_step / 1e9, batch, seq))
    tok_s, tflops = run_span(trainer, make_batch, "lstm", steps, repeat,
                             tokens_per_step, flops_per_step)
    return dict(metric="lstm_lm_tokens_per_sec_b%d" % batch,
                value=round(tok_s, 1), unit="tokens/s",
                tflops=round(tflops, 1),
                mfu_peak=round(tflops / peak_tflops(), 3))


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    steps = int(os.environ.get("LM_STEPS", "64"))
    repeat = int(os.environ.get("LM_REPEAT", "2"))
    batch = os.environ.get("LM_BATCH")
    batch = int(batch) if batch else None
    import jax
    log("devices:", jax.devices())
    runners = dict(bert=bench_bert, translm=bench_translm, lstm=bench_lstm,
                   bertdelta=bench_bert_flash_delta)
    names = ["bert", "translm", "lstm"] if which == "all" else [which]
    from benchmark._artifact import stamp
    results = []
    for name in names:
        res = runners[name](steps, repeat, batch)
        # provenance per record: this artifact is a LIST accumulated
        # across runs, so each entry must carry its own backend
        # (bertdelta returns a list of records)
        for rec in (res if isinstance(res, list) else [res]):
            stamp(rec)
            print(json.dumps(rec), flush=True)
            results.append(rec)
    # persist machine-readable results (VERDICT r3: LM numbers must be an
    # artifact, not README prose — reference pattern opperf.py output)
    out_path = os.path.join(os.path.dirname(__file__), "BENCH_LM.json")
    existing = []
    if os.path.exists(out_path):
        with open(out_path) as fh:
            existing = json.load(fh)
    keep = [e for e in existing
            if e["metric"] not in {r["metric"] for r in results}]
    with open(out_path, "w") as fh:
        json.dump(keep + results, fh, indent=1)
    log("wrote", out_path)


if __name__ == "__main__":
    main()
