"""What the two serving traffic kinds share: the client that consumes a
stream on the benchmark's own clock, the warm-up of the cell's shapes, the
end-to-end arithmetic and the output check."""
import gc
import importlib
import threading
import time

import numpy as np

from chipbench.stats import percentile, pooled_gaps

DRAIN_S = 60.0      # wait this long past the close for streams still open


class Record:
    """One request as the client saw it, on ``time.monotonic``."""

    def __init__(self, prompt, new_tokens, due):
        self.prompt, self.new_tokens, self.due = prompt, new_tokens, due
        self.token_times, self.tokens = [], []
        self.error = None
        self.request = None
        self.done = threading.Event()

    @property
    def ok(self):
        return self.error is None and self.done.is_set()


def send(system, record, inline=False):
    """Submit ``record`` now and consume its stream, on a thread of its own
    or (``inline``) on the caller's; a refusal is recorded, not raised."""
    try:
        record.request = system.submit(record.prompt, record.new_tokens)
    except Exception as exc:  # noqa: BLE001 — a refused request is a result
        record.error = exc
        record.done.set()
        return None
    if inline:
        return _consume(record)
    thread = threading.Thread(target=_consume, args=(record,), daemon=True)
    thread.start()
    return thread


def _consume(record):
    try:
        for tok in record.request.tokens(timeout=DRAIN_S):
            record.token_times.append(time.monotonic())
            record.tokens.append(tok)
    except Exception as exc:  # noqa: BLE001 — a failed stream is a result
        record.error = exc
    finally:
        record.done.set()


def random_prompt(rng, vocab, n):
    return rng.integers(0, vocab, n).tolist()


def build_and_warm(ctx):
    """Build the system and run one request per prefill rung the cell's
    traffic reaches (and so the decode, sampling and prefix-store programs):
    set-up compiles here, the window compiles nothing."""
    cfg, cell = ctx.cfg, ctx.cell
    builder = importlib.import_module(cfg["builder"])
    system = builder.build(cfg, cell, ctx.seed, ctx.devices)
    ctx.log("built", seconds=round(ctx.since_start(), 2))
    rng = np.random.default_rng(ctx.seed + 1)
    # the first rung again at the end: its first call took the zeroed arenas
    # of a new engine, and jit keeps a second program for arenas that a
    # program has made (0.9-1.3 s inside the first window otherwise)
    for n in cell["warm_prompt_lengths"] + cell["warm_prompt_lengths"][:1]:
        rec = Record(random_prompt(rng, cfg["vocab_size"], n), 4,
                     time.monotonic())
        thread = send(system, rec)
        rec.done.wait(1200.0)
        if not rec.ok or len(rec.tokens) != 4:
            raise RuntimeError("warm-up request of %d tokens failed: %r"
                               % (n, rec.error))
        thread.join()
    system.engine.prefix_flush()
    ctx.log("warm", seconds=round(ctx.since_start(), 2),
            memory={k: v for k, v in (ctx.devices[0].memory_stats()
                                      or {}).items() if "bytes" in k})
    return builder, system


def start_trace_timer(ctx, t0):
    """Trace ``trace_seconds`` of the window from ``trace_after_s`` on, from
    a timer thread; returns a function that waits for it to end."""
    if not ctx.trace:
        return lambda: None
    cell = ctx.cell

    def body():
        time.sleep(max(0.0, t0 + cell["trace_after_s"] - time.monotonic()))
        ctx.tracer.start()
        time.sleep(cell["trace_seconds"])
        ctx.tracer.stop()

    thread = threading.Thread(target=body, daemon=True)
    thread.start()
    return thread.join


def finish(ctx, builder, system, records, t0):
    """Wait for the open streams, reduce the records to the end-to-end
    metrics and hand back the check that runs after memory is read."""
    cfg, cell = ctx.cfg, ctx.cell
    t1 = t0 + ctx.seconds
    deadline = time.monotonic() + DRAIN_S
    for rec in records:
        rec.done.wait(max(0.0, deadline - time.monotonic()))
    failed = [r for r in records if not r.ok]
    good = [r for r in records if r.ok]
    if failed:
        ctx.log("failures", count=len(failed), of=len(records), first=sorted(
            {repr(r.error)[:300] for r in failed})[:3])
    if not good:
        raise RuntimeError("no request of %d finished" % len(records))
    worst = 1e3 * (time.monotonic() - t0)
    # a failed or refused request counts as the worst
    ttft = [1e3 * (r.token_times[0] - r.due) for r in good] + \
        [worst] * len(failed)
    gaps = [1e3 * g for g in pooled_gaps([r.token_times for r in good])]
    in_window = sum(1 for r in good for t in r.token_times if t0 <= t < t1)
    end_to_end = {"serve_tokens_per_s": in_window / ctx.seconds,
                  "ttft_ms_p95": percentile(ttft, 95),
                  "itl_ms_p95": percentile(gaps, 95)}
    obs = {"kind": "serve", "window_s": ctx.seconds, "t0": t0, "t1": t1,
           "chips": 1, "counters_end": system.counters(),
           "requests": [{
               "prompt_len": len(r.prompt), "new_tokens": r.new_tokens,
               "due": r.due, "token_times": r.token_times, "ok": r.ok,
               "enqueue_t": getattr(r.request, "enqueue_t", None),
               "admitted_t": getattr(r.request, "admitted_t", None),
           } for r in records]}
    ctx.window = [t0, t1]
    ctx.log("drained", requests=len(records), failed=len(failed),
            late_streams=sum(1 for r in good if r.token_times[-1] >= t1))

    def verify():
        system.close()
        gc.collect()
        return serving_numbers(cfg, cell, ctx.seed, builder, good)

    return {"attempted": len(records), "failed": len(failed),
            "end_to_end": end_to_end, "observations": obs, "verify": verify,
            "finished": good, "builder": builder}


def sample_of(records, seed, count):
    """``count`` finished requests drawn from the seed, the longest (prompt
    plus served tokens) always among them."""
    if not records:
        return []
    order = sorted(range(len(records)),
                   key=lambda i: -(len(records[i].prompt) + len(records[i].tokens)))
    rest = order[1:]
    np.random.default_rng(seed).shuffle(rest)
    return [records[i] for i in [order[0]] + rest[:count - 1]]


def deficits_sigma(rows, tokens):
    """Per served token, how far its reference logit lies below the row's
    best, in units of the row's standard deviation."""
    rows = np.asarray(rows, np.float64)
    picked = rows[np.arange(len(tokens)), np.asarray(tokens)]
    return (rows.max(axis=1) - picked) / rows.std(axis=1)


def serving_numbers(cfg, cell, seed, builder, good, control=None,
                    detail=None, **reference):
    """``{name: value}`` for a serving cell: streams that are not what was
    asked for (length, token range), and the widest gap by which a served
    token's logit lies below the reference's best over a seeded sample of
    the finished requests. ``control`` (keywords of ``served_logits`` that
    lower its precision): read instead the gap of the token that lower
    precision puts first. ``detail`` (a dict) also receives the mean deficit
    and the share of tokens that are not the reference's first;
    ``reference`` goes to ``served_logits``."""
    vocab = cfg["vocab_size"]
    bad = sum(1 for r in good if len(r.tokens) != r.new_tokens
              or not all(0 <= t < vocab for t in r.tokens))
    params = builder.reference_weights(cfg, seed)
    worst, count, every = 0.0, 0, []
    for rec in sample_of(good, seed, cell["check_requests"]):
        rows = builder.served_logits(params, cfg, rec.prompt, rec.tokens,
                                     **reference)
        tokens = rec.tokens
        if control:
            low = builder.served_logits(params, cfg, rec.prompt, rec.tokens,
                                        **control)
            tokens = np.asarray(low).argmax(axis=1)
        d = deficits_sigma(rows, tokens)
        worst = max(worst, float(d.max()) if np.isfinite(d).all() else np.inf)
        count += len(tokens)
        every.extend(d.tolist())
    if detail is not None and every:
        detail.update(tokens=count, mean_deficit_sigma=float(np.mean(every)),
                      flipped_share=float(np.mean(np.asarray(every) > 0)),
                      p99_deficit_sigma=float(np.percentile(every, 99)))
    return {"bad_streams": float(bad), "unchecked": float(count == 0),
            "max_deficit_sigma": worst}
