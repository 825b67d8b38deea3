"""Continuous batching: iteration-level scheduling over the slot arena.

The Orca scheduling model, on top of ``DecodeEngine``: a single worker
thread runs an endless loop of *iterations*; at each iteration boundary it

1. expires queued requests whose deadline passed (``DeadlineExceeded``,
   matching ``DynamicBatcher``'s queue-wait semantics),
2. **admits** waiting requests into free KV-cache slots (one compiled
   prefill each, streaming the request's first token — the TTFT moment),
3. runs **one fused decode step** for every live slot, and
4. **retires** finished sequences (EOS / token budget / ``max_seq``)
   immediately, handing their slots to the next queued request —

so a short request never waits for a long one to finish, and the device
never idles while work is queued. Tokens stream to consumers through each
:class:`GenerationRequest` as they are produced.

Robustness mirrors ``DynamicBatcher``: bounded queue (``ServerBusy``),
drain-on-close (``close(drain=True)`` finishes the entire backlog —
bounded by each request's token budget — while ``drain=False`` fails it),
a worker that can never die silently, and a ``generation.step`` chaos
point *inside* the retried step callable so the resilience stack
(retry → breaker → /healthz) applies to generation unchanged.
"""
from __future__ import annotations

import queue as _queue
import threading
import time
from collections import deque

import numpy as _np

from ...observability import tracer as _trace
from ...resilience import chaos as _chaos
from ...resilience import retry as _retry
from ...resilience._stats import Registry
from ..batcher import (DeadlineExceeded, ServerBusy, ServerClosed,
                       ServingError)

__all__ = ["GenerationScheduler", "GenerationRequest"]

_registry = Registry()


class GenerationRequest:
    """One streaming generation: consumers iterate :meth:`tokens` (or call
    :meth:`result`) while the scheduler produces into it."""

    def __init__(self, prompt, max_new_tokens, temperature, eos_id,
                 timeout_ms, request_id=None):
        self.prompt = _np.asarray(prompt, dtype=_np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.request_id = request_id
        self.enqueue_t = time.monotonic()
        self.deadline = (self.enqueue_t + timeout_ms / 1e3
                         if timeout_ms else None)
        self.ctx = _trace.current()
        self.tokens_out = []
        self.finish_reason = None
        self.slot = None
        self.admitted_t = None
        self.first_token_t = None
        self.token_times = []         # scheduler's clock at each emit
        self.done_t = None
        self.prefix_skipped = 0       # prompt tokens served from the cache
        self._pending = None          # last sampled, not yet cache-written
        self._prefill_pos = 0         # chunked-prefill progress (tokens)
        self._prefill_t0 = None
        self._q = _queue.Queue()
        self._done = threading.Event()
        self._error = None
        self._cancelled = False

    # ---- consumer side ----------------------------------------------------
    def tokens(self, timeout=None):
        """Yield generated token ids as they are produced; returns on
        normal completion, raises the failure (``DeadlineExceeded``,
        ``ServerClosed``, a model fault...) otherwise. ``timeout`` bounds
        the wait for EACH token."""
        while True:
            kind, val = self._q.get(timeout=timeout)
            if kind == "token":
                yield val
            elif kind == "done":
                return
            else:
                raise val

    def next_event(self, timeout=None):
        """Block for the next stream event: ``("token", id)``,
        ``("done", reason)`` or ``("error", exc)`` — the primitive under
        :meth:`tokens` for consumers (the HTTP layer) that must see the
        FIRST outcome before committing to a transport framing."""
        return self._q.get(timeout=timeout)

    def result(self, timeout=None):
        """Block until the request finishes; returns the full token list
        (raises on failure). ``timeout`` is end-to-end."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still running")
        if self._error is not None:
            raise self._error
        return list(self.tokens_out)

    @property
    def done(self):
        return self._done.is_set()

    def cancel(self):
        """Consumer gone (client disconnect): ask the scheduler to retire
        this sequence at the next iteration boundary and hand its slot to
        the queue, instead of decoding to budget for nobody. Idempotent;
        safe from any thread."""
        self._cancelled = True

    # ---- scheduler side ---------------------------------------------------
    def _emit(self, tok):
        if self._done.is_set():
            return  # failed externally (close timeout): consumer is gone
        self.tokens_out.append(int(tok))
        self.token_times.append(time.monotonic())
        if self.first_token_t is None:
            self.first_token_t = self.token_times[0]
        self._pending = int(tok)
        self._q.put(("token", int(tok)))

    def _finish(self, reason):
        """Mark clean completion. Returns False (and does nothing) when
        the request already finished — e.g. failed by a close() timeout
        while the worker was still stepping it — so the caller skips the
        success accounting instead of double-counting."""
        if self._done.is_set():
            return False
        self.finish_reason = reason
        self.done_t = time.monotonic()
        self._q.put(("done", reason))
        self._done.set()
        return True

    def _fail(self, exc):
        if self._done.is_set():
            return
        self.finish_reason = "error"
        self.done_t = time.monotonic()
        self._error = exc
        self._q.put(("error", exc))
        self._done.set()


class GenerationScheduler:
    """Admit / step / retire loop over a :class:`DecodeEngine`.

    Parameters
    ----------
    engine : DecodeEngine
    max_queue_size : int, optional
        Bound on *waiting* requests (live slots are bounded by the arena);
        beyond it :meth:`submit` raises :class:`ServerBusy`. Defaults to
        ``MXNET_GEN_QUEUE_SIZE``.
    default_timeout_ms : float, optional
        Queue-wait deadline applied when ``submit`` doesn't pass one
        (``None`` = wait forever). Like the batcher, the deadline covers
        time *in queue* — an admitted sequence always runs to completion.
    default_max_new_tokens : int, optional
        Token budget when a request doesn't specify one
        (``MXNET_GEN_MAX_NEW_TOKENS``).
    metrics : GenerationMetrics | False | None
        TTFT / tokens-per-slot percentile recording (see
        ``serving/metrics.py``). ``None`` (default) builds one — the
        documented ``/metrics`` generation section must not silently
        vanish under the quickstart wiring; pass ``False`` to disable.
    retry_policy : RetryPolicy | False | None
        Wrapped around every decode step (``None`` = env-configured
        ``retry.generation`` policy; ``False`` disables). The
        ``generation.step`` chaos point fires inside the retried callable,
        so armed transient faults are absorbed per attempt.
    speculative : SpeculativeDecoder, optional
        Attach a draft-then-verify fast path (``speculative.py``). When
        every live slot is greedy and the arena has headroom, iterations
        run draft + fused verify and emit up to ``k+1`` tokens per
        sequence per step — token-exact vs the plain path. Alternatively
        pass ``draft_model=`` and the decoder is built (and owned) here.
    lane_policy : str, optional
        ``"mixed"`` (default, ``MXNET_GEN_LANE``) serves prefill and
        decode interleaved. ``"prefill"`` declares a prefill-only lane:
        requests retire after their first token with reason
        ``"prefill"`` and their prompt K/V is published to the prefix
        cache — the disaggregation handoff a decode lane admits from.
        ``"decode"`` expects admits to be covered by the prefix cache and
        counts ``decode_lane_misses`` when they are not (advisory:
        correctness is preserved by prefilling the remainder locally).
    """

    def __init__(self, engine, max_queue_size=None, default_timeout_ms=None,
                 default_max_new_tokens=None, metrics=None,
                 retry_policy=None, speculative=None, draft_model=None,
                 lane_policy=None, name="generation"):
        from ... import config as _config
        self.engine = engine
        self.name = name
        if retry_policy is None:
            retry_policy = _retry.named_policy("retry.generation")
        self._retry = retry_policy or None
        self._owns_spec = False
        if speculative is None and draft_model is not None:
            from .speculative import SpeculativeDecoder
            speculative = SpeculativeDecoder(engine, draft_model)
            self._owns_spec = True
        self._spec = speculative or None
        lane = str(lane_policy if lane_policy is not None
                   else _config.get("MXNET_GEN_LANE")).lower()
        if lane not in ("mixed", "prefill", "decode"):
            raise ServingError("lane_policy must be mixed|prefill|decode, "
                               "got %r" % lane)
        self._lane = lane
        self._max_queue = int(max_queue_size or
                              _config.get("MXNET_GEN_QUEUE_SIZE"))
        self._default_timeout_ms = default_timeout_ms
        self._default_max_new = int(default_max_new_tokens or
                                    _config.get("MXNET_GEN_MAX_NEW_TOKENS"))
        if metrics is None:
            from ..metrics import GenerationMetrics
            metrics = GenerationMetrics(name=name)
        self.metrics = metrics or None
        if self.metrics is not None:
            self.metrics.set_engine(engine)
            self.metrics.set_queue_depth_fn(lambda: self.queue_depth)
        self._queue = deque()
        self._live = {}               # slot -> GenerationRequest (decoding)
        self._prefilling = {}         # slot -> GenerationRequest (chunking)
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._closing = False
        self._drain = True
        self._c = {"submitted": 0, "completed": 0, "failed": 0,
                   "cancelled": 0, "prefix_hits": 0,
                   "prefix_tokens_saved": 0, "decode_lane_misses": 0}
        _registry.add(self)
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name=name + "-scheduler")
        self._worker.start()

    # ---- client side ------------------------------------------------------
    @property
    def queue_depth(self):
        with self._lock:
            return len(self._queue)

    @property
    def live_count(self):
        with self._lock:
            return len(self._live)

    def submit(self, prompt, max_new_tokens=None, temperature=0.0,
               eos_id=None, timeout_ms=None, request_id=None):
        """Enqueue one generation; returns a :class:`GenerationRequest`
        immediately (tokens stream into it). Raises synchronously:
        :class:`ServerBusy` (queue full), :class:`ServerClosed`,
        :class:`~.decode.PromptTooLong` / :class:`ServingError` (bad
        prompt)."""
        prompt = _np.asarray(prompt, dtype=_np.int64)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ServingError("prompt must be a non-empty 1-D token list")
        self.engine.validate_prompt(int(prompt.size))
        if max_new_tokens is None:
            max_new_tokens = self._default_max_new
        if int(max_new_tokens) < 1:
            raise ServingError("max_new_tokens must be >= 1")
        if timeout_ms is None:
            timeout_ms = self._default_timeout_ms
        req = GenerationRequest(prompt, max_new_tokens, temperature, eos_id,
                                timeout_ms, request_id=request_id)
        with self._lock:
            if self._closing:
                raise ServerClosed("generation scheduler is shut down")
            if len(self._queue) >= self._max_queue:
                if self.metrics is not None:
                    self.metrics.record_rejected()
                raise ServerBusy("generation queue full (%d waiting)"
                                 % len(self._queue))
            self._queue.append(req)
            self._c["submitted"] += 1
            self._not_empty.notify()
        return req

    def generate(self, prompt, **kwargs):
        """Blocking convenience: submit + ``result()``."""
        return self.submit(prompt, **kwargs).result()

    def close(self, drain=True, timeout=None):
        """Stop intake. ``drain=True`` finishes the whole backlog — live
        sequences run out their token budgets and queued requests are
        admitted as slots free (matching ``DynamicBatcher``'s
        drain-the-backlog contract; bounded because every request has a
        budget). ``drain=False`` fails queued AND live requests with
        :class:`ServerClosed`. ``timeout`` bounds the drain; stragglers
        are failed rather than stranded. Idempotent."""
        with self._lock:
            self._closing = True
            self._drain = drain
            self._not_empty.notify_all()
        self._worker.join(timeout)
        _registry.discard(self)
        if self._spec is not None and self._owns_spec:
            self._spec.close()
            self._owns_spec = False
        if self._worker.is_alive():
            with self._lock:
                stranded = (list(self._queue) + list(self._live.values())
                            + list(self._prefilling.values()))
                self._queue.clear()
            for req in stranded:
                req._fail(ServerClosed(
                    "drain timed out with generation unfinished"))
            return False
        return True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---- worker side ------------------------------------------------------
    def _run(self):
        # Same never-die contract as DynamicBatcher._run: this thread is
        # the only producer for every open GenerationRequest stream.
        try:
            while True:
                if not self._iterate():
                    return
        except BaseException as exc:
            self._abort(exc)

    def _iterate(self):
        """One scheduling iteration. Returns False when the worker should
        exit (closed and nothing left to do). An iteration that does work
        is one ``generation.iteration`` span whose children are the
        device calls (``generation.prefill``, ``generation.step``) and
        ``generation.emit``: its self time is what the host did between
        them. The idle wait records none."""
        with _trace.span("generation.iteration") as span:
            return self._iterate_in(span)

    def _iterate_in(self, span):
        admits, expired, cancelled = [], [], []
        with self._not_empty:
            self._drop_expired_locked(expired, cancelled)
            if self._closing and not self._drain:
                to_fail = (list(self._queue) + list(self._live.values())
                           + list(self._prefilling.values()))
                self._queue.clear()
                self._live.clear()
                self._prefilling.clear()
            else:
                to_fail = []
                free = self.engine.cache.free_slots
                admits = self._select_admits_locked(free)
            idle = (not admits and not expired and not self._live
                    and not self._prefilling and not to_fail
                    and not cancelled)
            if idle:
                span.cancel()
                if self._closing:
                    return False
                self._not_empty.wait(0.05)
                return True
            span.set(admits=len(admits), live=len(self._live))
        for req in expired:
            if self.metrics is not None:
                self.metrics.record_expired()
            req._fail(DeadlineExceeded(
                "generation request expired after queueing %.1f ms"
                % ((time.monotonic() - req.enqueue_t) * 1e3)))
        for req in cancelled:
            with self._lock:
                self._c["cancelled"] += 1
            if self.metrics is not None:
                self.metrics.record_error()
            req._fail(ServerClosed("cancelled by consumer while queued"))
        for req in to_fail:
            if req.slot is not None:
                self.engine.cache.release(req.slot)
            self._count_done(ok=False)
            req._fail(ServerClosed("scheduler shut down before completion"))
        for req in admits:
            self._admit(req)
        self._advance_prefills()
        with self._lock:
            has_live = bool(self._live)
        if has_live:
            self._step()
        return True

    # effective deadline assigned to deadline-less requests for admission
    # ordering: far enough out that any real (seconds-scale) deadline
    # beats them, near enough that they AGE past fresh deadline-bearing
    # arrivals and cannot be starved forever (pure sort-them-last would
    # invert the starvation this ordering exists to fix)
    _NO_DEADLINE_HORIZON_S = 600.0

    def _select_admits_locked(self, free):
        """Deadline-aware admission order (the starvation fix): take up
        to ``free`` queued requests by earliest *effective* deadline —
        the real deadline, or enqueue time + ``_NO_DEADLINE_HORIZON_S``
        for deadline-less requests (FIFO among themselves, and with a
        bounded wait even under a sustained deadline-bearing stream).
        Plain FIFO let a burst of long prompts occupy every slot for
        their full budgets while short deadline-bearing chat requests
        expired in queue."""
        if not self._queue or free <= 0:
            return []

        def eff(req):
            if req.deadline is not None:
                return req.deadline
            return req.enqueue_t + self._NO_DEADLINE_HORIZON_S

        order = sorted(range(len(self._queue)),
                       key=lambda i: (eff(self._queue[i]),
                                      self._queue[i].enqueue_t, i))
        take = set(order[:free])
        admits = [self._queue[i] for i in order[:free]]
        self._queue = deque(req for i, req in enumerate(self._queue)
                            if i not in take)
        return admits

    def _drop_expired_locked(self, expired, cancelled):
        """Prune the wait queue: deadline-passed entries -> ``expired``,
        consumer-cancelled entries -> ``cancelled`` (a dead entry must
        neither occupy bounded queue capacity nor win a slot and a full
        prefill for a consumer known to be gone)."""
        now = time.monotonic()
        kept = deque()
        while self._queue:
            req = self._queue.popleft()
            if req._cancelled:
                cancelled.append(req)
            elif req.deadline is not None and now > req.deadline:
                expired.append(req)
            else:
                kept.append(req)
        self._queue.extend(kept)

    def _count_done(self, ok):
        with self._lock:
            self._c["completed" if ok else "failed"] += 1
        if not ok and self.metrics is not None:
            self.metrics.record_error()

    def _admit(self, req):
        """Prefill one request into a free slot and stream its first
        token. Prefill failures fail only THIS request."""
        if req._cancelled:  # cancelled between queue-prune and admission
            with self._lock:
                self._c["cancelled"] += 1
            if self.metrics is not None:
                self.metrics.record_error()
            req._fail(ServerClosed("cancelled by consumer while queued"))
            return
        try:
            slot = self.engine.cache.acquire()
        except ServingError:  # free_slots went stale: requeue, retry later
            with self._lock:
                self._queue.appendleft(req)
            return
        req.slot = slot
        req.admitted_t = time.monotonic()
        # the prefill spans belong to the iteration; this instant keeps the
        # request's own trace linked to them, by slot
        _trace.instant("generation.admit", parent=req.ctx,
                       request_id=req.request_id, slot=int(slot))
        try:
            req._prefill_t0 = time.monotonic()
            n = int(req.prompt.size)
            skipped = self.engine.prefix_admit(slot, req.prompt)
            if skipped:
                req.prefix_skipped = skipped
                with self._lock:
                    self._c["prefix_hits"] += 1
                    self._c["prefix_tokens_saved"] += skipped
            elif self._lane == "decode" and self.engine.prefix \
                    is not None and n > self.engine.prefix.block:
                # a decode lane expects its prefill to have been done
                # by a prefill lane; a miss is a routing signal, not
                # an error — the remainder prefills locally
                with self._lock:
                    self._c["decode_lane_misses"] += 1
            chunk = self.engine.chunk
            remaining = n - skipped
            if chunk and remaining > chunk:
                # long prompt: rung-sized chunks interleave with the
                # decode iterations (_advance_prefills)
                req._prefill_pos = skipped
                with self._lock:
                    self._prefilling[slot] = req
                return
            if skipped or chunk:
                _, tok = self.engine.prefill_chunks(
                    slot, req.prompt, skipped,
                    temperature=req.temperature)
            else:
                tok = self.engine.prefill(slot, req.prompt,
                                          temperature=req.temperature)
        except Exception as exc:  # noqa: BLE001 — this request only
            self.engine.cache.release(slot)
            req.slot = None
            self._count_done(ok=False)
            req._fail(exc)
            return
        self._finish_prefill(req, tok)

    def _advance_prefills(self):
        """One chunk-program call per prefilling slot per iteration: a
        4k-token prompt becomes ~32 rung-sized slices *between* decode
        steps instead of one monolithic stall in front of every live
        stream's next token."""
        with self._lock:
            prefilling = dict(self._prefilling)
        for slot, req in prefilling.items():
            if req._cancelled or req.done:
                with self._lock:
                    self._prefilling.pop(slot, None)
                self._retire_cancelled(req, slot)
                continue
            try:
                pos, tok = self.engine.prefill_chunks(
                    slot, req.prompt, req._prefill_pos,
                    temperature=req.temperature, max_chunks=1)
                req._prefill_pos = pos
                if self.metrics is not None:
                    self.metrics.record_prefill_chunk()
            except Exception as exc:  # noqa: BLE001 — this request only
                with self._lock:
                    self._prefilling.pop(slot, None)
                self.engine.cache.release(slot)
                req.slot = None
                self._count_done(ok=False)
                req._fail(exc)
                continue
            if tok is not None:
                with self._lock:
                    self._prefilling.pop(slot, None)
                self._finish_prefill(req, tok)

    def _finish_prefill(self, req, tok):
        """Prompt fully in the arena: stream the first token (the TTFT
        moment), THEN publish its K/V to the prefix cache (the extract +
        device->host copy must not sit in front of the first token), and
        either join the decode batch or — on a prefill-only lane —
        retire immediately (the disaggregation handoff: the K/V now
        lives in the prefix cache for a decode lane to admit from)."""
        if self.metrics is not None:
            self.metrics.record_prefill(time.monotonic() - req._prefill_t0)
        req._emit(tok)
        if self.metrics is not None:
            self.metrics.record_ttft(req.first_token_t - req.enqueue_t)
        try:
            # async: the extract + device->host slab copy runs on the
            # publisher thread, never between two decode iterations
            self.engine.prefix_store_async(req.slot, req.prompt)
        except Exception:  # noqa: BLE001 — publishing is best-effort
            pass
        if self._lane == "prefill":
            self.engine.cache.release(req.slot)
            req.slot = None
            if not req._finish("prefill"):
                return
            if self.metrics is not None:
                self.metrics.record_done(1, "prefill", 1e-9)
            self._count_done(ok=True)
            _trace.instant("generation.retire", request_id=req.request_id,
                           reason="prefill", tokens=1)
            return
        with self._lock:
            self._live[req.slot] = req
        self._retire_if_finished(req)

    def _retire_cancelled(self, req, slot):
        """Release + fail one consumer-cancelled (or externally-failed)
        sequence — shared by the live sweep and the prefilling advance.
        Already-done requests (failed by a close() timeout) were counted
        by whoever failed them; only the release happens here."""
        self.engine.cache.release(slot)
        req.slot = None
        if req.done:
            return
        with self._lock:
            self._c["cancelled"] += 1
        if self.metrics is not None:
            self.metrics.record_error()
        _trace.instant("generation.retire", request_id=req.request_id,
                       reason="cancelled", tokens=len(req.tokens_out))
        req._fail(ServerClosed("cancelled by consumer"))

    def _fail_iteration(self, live, exc):
        """One fused iteration faulted: fail every live sequence (the
        plain and speculative step paths share these semantics)."""
        if self.metrics is not None:
            self.metrics.record_step_failure()
        with self._lock:
            for slot in live:
                self._live.pop(slot, None)
        for slot, req in live.items():
            self.engine.cache.release(slot)
            self._count_done(ok=False)
            req._fail(exc)

    def _sweep_abandoned(self, live):
        """Drop cancelled/externally-failed sequences BEFORE spending a
        decode step on them: release the slot, drain the request, and
        count it — a disconnected client must not hold arena capacity to
        budget exhaustion."""
        for slot, req in list(live.items()):
            if not (req._cancelled or req.done):
                continue
            with self._lock:
                self._live.pop(slot, None)
            live.pop(slot)
            self._retire_cancelled(req, slot)

    def _step(self):
        """One fused decode step for all live slots; emit + retire."""
        with self._lock:
            live = dict(self._live)
        self._sweep_abandoned(live)
        if not live:
            return
        if (self._spec is not None
                and all(r.temperature == 0.0 for r in live.values())
                and self._spec.can_step(list(live))):
            # speculative fast path: all-greedy batch with arena headroom
            # for k+1 writes — token-exact, so engaging it per-iteration
            # is invisible to consumers
            self._step_spec(live)
            return
        n_slots = self.engine.num_slots
        tokens = _np.zeros(n_slots, dtype=_np.int32)
        temps = _np.zeros(n_slots, dtype=_np.float32)
        for slot, req in live.items():
            tokens[slot] = req._pending
            temps[slot] = req.temperature

        def run_step():
            # chaos point INSIDE the retried callable: every retry attempt
            # re-rolls the injection, mirroring serving.execute
            _chaos.point("generation.step")
            return self.engine.decode_step(tokens, temps)

        t0 = time.monotonic()
        try:
            if self._retry is not None:
                next_toks = self._retry.call(run_step)
            else:
                next_toks = run_step()
        except Exception as exc:  # noqa: BLE001 — fail the whole iteration
            self._fail_iteration(live, exc)
            return
        self.engine.cache.advance(list(live.keys()))
        if self.metrics is not None:
            self.metrics.record_step(len(live), time.monotonic() - t0)
        with _trace.span("generation.emit", slots=len(live)):
            for slot, req in live.items():
                req._emit(int(next_toks[slot]))
                self._retire_if_finished(req)

    def _step_spec(self, live):
        """One draft-then-verify iteration: up to ``k+1`` tokens per live
        sequence from one fused verify step. Failure semantics, retry
        wrapping, and the ``generation.step`` chaos point mirror the
        plain path exactly."""
        slots = list(live)
        pending = {s: live[s]._pending for s in slots}

        def history(slot):
            req = live[slot]
            return _np.concatenate([
                req.prompt.astype(_np.int32),
                _np.asarray(req.tokens_out[:-1], dtype=_np.int32)])

        def run_step():
            _chaos.point("generation.step")
            return self._spec.round(slots, pending, history)

        t0 = time.monotonic()
        try:
            if self._retry is not None:
                result = self._retry.call(run_step)
            else:
                result = run_step()
        except Exception as exc:  # noqa: BLE001 — fail the whole iteration
            self._fail_iteration(live, exc)
            return
        elapsed = time.monotonic() - t0
        emitted = 0
        with _trace.span("generation.emit", slots=len(live)):
            for slot, req in live.items():
                toks = result[slot]
                # trim to budget, then to (and including) the first EOS:
                # only the kept tokens' cache writes are committed
                n_allow = min(len(toks),
                              req.max_new_tokens - len(req.tokens_out))
                if req.eos_id is not None:
                    for j in range(n_allow):
                        if toks[j] == req.eos_id:
                            n_allow = j + 1
                            break
                self._spec.commit(slot, n_allow)
                emitted += n_allow
                for tok in toks[:n_allow]:
                    req._emit(tok)
                self._retire_if_finished(req)
        if self.metrics is not None:
            self.metrics.record_spec_round(
                len(live), self._spec.k * len(live), emitted, elapsed)

    def _retire_if_finished(self, req):
        """EOS / token budget / arena edge -> finish and free the slot NOW
        (the next iteration can hand it to a queued request)."""
        reason = None
        if req.eos_id is not None and req._pending == req.eos_id:
            reason = "eos"
        elif len(req.tokens_out) >= req.max_new_tokens:
            reason = "length"
        elif int(self.engine.cache.lengths[req.slot]) >= self.engine.max_seq:
            reason = "max_seq"
        if reason is None:
            return
        with self._lock:
            self._live.pop(req.slot, None)
        self.engine.cache.release(req.slot)
        if not req._finish(reason):
            return  # already failed externally: no success accounting
        if self.metrics is not None:
            gen_s = req.done_t - req.first_token_t
            self.metrics.record_done(len(req.tokens_out), reason,
                                     max(gen_s, 1e-9))
        self._count_done(ok=True)
        _trace.instant("generation.retire", request_id=req.request_id,
                       reason=reason, tokens=len(req.tokens_out))

    def _abort(self, exc):
        """Unexpected worker failure: close intake, fail every reachable
        request — no consumer is ever left blocked on a dead worker."""
        with self._lock:
            self._closing = True
            stranded = (list(self._queue) + list(self._live.values())
                        + list(self._prefilling.values()))
            self._queue.clear()
            self._live.clear()
            self._prefilling.clear()
        err = ServerClosed("generation scheduler worker died: %s: %s"
                           % (type(exc).__name__, exc))
        err.__cause__ = exc
        for req in stranded:
            if req.slot is not None:
                try:
                    self.engine.cache.release(req.slot)
                except ValueError:
                    pass
            self._count_done(ok=False)
            req._fail(err)

    # ---- lane policy ------------------------------------------------------
    @property
    def lane_policy(self):
        return self._lane

    def set_lane_policy(self, lane):
        """Declare this scheduler a ``prefill``/``decode``/``mixed`` lane
        (what ``fleet.ModelRegistry.load(gen_lane=...)`` calls — a
        ModelVersion bulkhead becomes a disaggregation lane)."""
        lane = str(lane).lower()
        if lane not in ("mixed", "prefill", "decode"):
            raise ServingError("lane_policy must be mixed|prefill|decode, "
                               "got %r" % lane)
        self._lane = lane
        return self

    def program_bound(self):
        """Compiled programs this scheduler's lane can hold — the target
        engine's families plus, when speculative decoding is attached,
        the draft engine's and the one verify program. What the fleet
        compile-budget admission charges a generation lane."""
        n = self.engine.program_bound()
        if self._spec is not None:
            n += self._spec.draft.program_bound() + 1
        return n

    # ---- stats ------------------------------------------------------------
    def stats(self):
        with self._lock:
            out = dict(self._c)
            out["queue_depth"] = len(self._queue)
            out["live_slots"] = len(self._live)
            out["prefilling_slots"] = len(self._prefilling)
            out["closing"] = self._closing
        out["lane"] = self._lane
        out["compile"] = self.engine.compile_stats()
        if self.engine.prefix is not None:
            out["prefix"] = self.engine.prefix.stats()
        if self._spec is not None:
            # the decoder's ledger is the one source of truth for round
            # accounting; spec_rounds here is a derived convenience view
            out["speculative"] = self._spec.stats()
            out["spec_rounds"] = out["speculative"]["rounds"]
        else:
            out["spec_rounds"] = 0
        return out


def scheduler_stats():
    """``{name: stats}`` over all live schedulers (the ``/metrics``
    ``generation.schedulers`` view)."""
    return _registry.map(lambda s: s.stats())
