"""Speculative decoding: draft-k-then-verify (Leviathan et al. 2023).

Decode emits one token per target-model step because step N+1's input is
step N's output — the sequential bottleneck HBM bandwidth can't fix. A
small *draft* model breaks it: the draft proposes ``k`` tokens
autoregressively (cheap), then ONE fused fixed-signature verify step on
the target scores all ``k+1`` positions at once and accepts the longest
run where the target's own greedy choice agrees with the draft. Greedy
acceptance is *token-exact*: every emitted token is the target argmax
given its exact committed prefix, so a speculative stream is bitwise the
non-speculative stream — speculation changes the schedule, never the
output.

Mechanics on the slot arena:

- The draft model gets its own :class:`DecodeEngine` over a mirror arena
  (same slots/max_seq). Drafting is ``k+1`` fused draft decode steps for
  the whole live batch (the extra step writes the last proposal's K/V so
  full acceptance leaves no draft-cache hole).
- The verify step is one CachedOp with fixed signature
  ``(num_slots, k+1)`` tokens + lengths + arenas — the target model's
  ``prefill_chunk`` over the arena rows. Membership churn still compiles
  NOTHING (one verify program, ever).
- Rollback is free: verify writes K/V for all ``k+1`` positions, and
  rejecting a suffix just means *not advancing the committed length* —
  the same stale-data-is-unreachable invariant pad tails already rely
  on.
- The draft cache is self-healing: before every round, any slot whose
  draft length disagrees with the target's committed length is rebuilt
  by chunk-prefilling the request's committed tokens through the draft —
  so mixed greedy/sampling batches, retries mid-round, and admissions
  all converge without lockstep bookkeeping.
"""
from __future__ import annotations

import threading

import numpy as _np

from ... import config as _config
from ...cached_op import CachedOp
from ...observability import tracer as _trace
from .decode import DecodeEngine

__all__ = ["SpeculativeDecoder"]


class SpeculativeDecoder:
    """Draft-then-verify fast path over a target :class:`DecodeEngine`.

    Parameters
    ----------
    engine : DecodeEngine
        The target engine (owns the authoritative arena + sampling).
    draft_model : TransformerLM-like
        The small proposer. Must expose the same incremental-decode
        contract (``prefill_chunk``/``step`` + geometry properties).
    k : int, optional
        Proposals per verify step (``MXNET_GEN_SPEC_K``).
    """

    def __init__(self, engine, draft_model, k=None, name=None):
        self.engine = engine
        self.k = int(k if k is not None else _config.get("MXNET_GEN_SPEC_K"))
        if self.k < 1:
            raise ValueError("speculative k must be >= 1")
        name = name or (engine._name + ".spec")
        self.name = name
        draft_max = getattr(draft_model, "max_len", None)
        if draft_max is not None and int(draft_max) < engine.max_seq:
            # SlotKVCache.for_model would silently clamp the mirror
            # arena to the draft's max_len and the mismatch would
            # surface as a mid-flight advance()/set_length() crash
            # failing every live request — fail at construction instead
            raise ValueError(
                "draft model max_len %d < target max_seq %d: the draft "
                "must cover the full arena depth (use a shallower/"
                "narrower draft, not a shorter one)"
                % (int(draft_max), engine.max_seq))
        # the draft mirrors the target geometry; its prefix cache is
        # pointless (draft prefill only happens on sync) and its chunk
        # width must be positive so any history length can be rebuilt
        self.draft = DecodeEngine(
            draft_model, num_slots=engine.num_slots, max_seq=engine.max_seq,
            ladder=engine.ladder, top_k=0,
            chunk=engine.chunk or engine.ladder[-1],
            prefix_cache=False, name=name + ".draft")
        # hold every draft slot permanently: draft slot i mirrors target
        # slot i, and lengths are driven by sync/commit, not acquire
        for _ in range(self.draft.num_slots):
            self.draft.cache.acquire()
        self._verify_op = CachedOp(self._verify_fn, name=name + ".verify")
        self._base = {}
        self._lock = threading.Lock()
        self._c = {"rounds": 0, "drafted": 0, "accepted": 0, "syncs": 0}

    # ---- traced verify program --------------------------------------------
    def _verify_fn(self, tokens, lengths, k_arena, v_arena, *pvals):
        """ONE fused verify: append ``(num_slots, k+1)`` tokens to every
        slot at its committed length and return the target's greedy
        choice at each position (plus the updated arenas — rejected
        positions stay written but unreachable)."""
        from ... import ndarray as nd
        cache = [(k_arena[layer], v_arena[layer])
                 for layer in range(self.engine.cache.num_layers)]
        with self.engine.bound_params(pvals):
            logits, new_cache = self.engine._model.prefill_chunk(
                tokens, cache, lengths)
        k_arena = nd.stack(*[k for k, _ in new_cache], axis=0)
        v_arena = nd.stack(*[v for _, v in new_cache], axis=0)
        return nd.sample_greedy(logits), k_arena, v_arena

    # ---- host side --------------------------------------------------------
    def can_step(self, slots):
        """Whether a speculative round fits: EVERY slot — live, free
        (length 0), or mid-chunked-prefill — needs room for ``k+1``
        writes before the arena edge. The verify program writes all
        ``num_slots`` rows at their lengths; a slot whose committed
        length sits past ``max_seq - (k+1)`` would force a clamped
        (shifted) write that overwrites committed K/V — so the round is
        skipped instead (``slots`` is accepted for interface symmetry
        but the check is arena-wide)."""
        del slots
        lengths = self.engine.cache.lengths
        return bool((lengths + self.k + 1 <= self.engine.max_seq).all())

    def _sync_draft(self, slot, history):
        """Rebuild one draft slot from the request's committed tokens
        (prompt + emitted-but-last) — called whenever draft and target
        lengths disagree (first round after admit, after non-speculative
        iterations, after a retried round)."""
        self.draft.cache.set_length(slot, 0)
        self.draft.prefill_chunks(slot, history, 0, sample=False)
        with self._lock:
            self._c["syncs"] += 1

    def round(self, slots, pending, history_fn):
        """One speculative iteration for the live ``slots``.

        ``pending[slot]`` is each sequence's last sampled-but-unwritten
        token (the scheduler's ``_pending`` convention); ``history_fn(slot)``
        lazily yields the committed token run for draft resync. Returns
        ``{slot: [tokens]}`` — 1 to ``k+1`` target-greedy tokens per
        slot, *untrimmed* (the scheduler applies budget/EOS cuts and then
        :meth:`commit`\\ s the count it kept)."""
        from ... import ndarray as nd
        eng = self.engine
        t_len = eng.cache.lengths
        for s in slots:
            if int(self.draft.cache.lengths[s]) != int(t_len[s]):
                self._sync_draft(s, history_fn(s))
        with self._lock:
            self._base = {s: int(t_len[s]) for s in slots}
        n_slots = eng.num_slots
        x = _np.zeros(n_slots, dtype=_np.int32)
        for s in slots:
            x[s] = pending[s]
        zeros_t = _np.zeros(n_slots, dtype=_np.float32)
        consumed = [x.copy()]                      # x_0 = pending
        with _trace.span("generation.spec_draft", slots=len(slots),
                         k=self.k):
            for i in range(self.k + 1):
                toks = self.draft.decode_step(x, zeros_t)
                self.draft.cache.advance(slots)
                if i < self.k:
                    x = x.copy()
                    for s in slots:
                        x[s] = toks[s]
                    consumed.append(x)             # x_{i+1} = draft_{i+1}
        tokens_mat = _np.stack(consumed, axis=1)   # (num_slots, k+1)
        # can_step guaranteed every slot's write window fits (no
        # dynamic_update_slice start-clamp, so no committed row is ever
        # shifted over); the minimum is pure belt-and-braces
        lengths = _np.minimum(eng.cache.lengths,
                              eng.max_seq - (self.k + 1)).astype(_np.int32)
        with _trace.span("generation.spec_verify", slots=len(slots),
                         k=self.k):
            greedy, k_arena, v_arena = self._verify_op(
                nd.array(tokens_mat), nd.array(lengths),
                eng.cache.k_arena, eng.cache.v_arena, *eng.param_args())
            eng.cache.commit(k_arena, v_arena)
            g = greedy.asnumpy()
        out = {}
        accepted = 0
        for s in slots:
            y = g[s]
            d = tokens_mat[s]
            a = 0
            while a < self.k and int(d[a + 1]) == int(y[a]):
                a += 1
            accepted += a
            out[s] = [int(t) for t in y[:a + 1]]
        with self._lock:
            self._c["rounds"] += 1
            self._c["drafted"] += self.k * len(slots)
            self._c["accepted"] += accepted
        return out

    def commit(self, slot, n):
        """Advance both arenas' committed length for ``slot`` by the
        ``n`` tokens the scheduler actually kept (budget/EOS may trim the
        accepted run). Everything past the new length — rejected drafts,
        trimmed acceptances, the draft's own speculative writes — is
        unreachable stale data."""
        base = self._base[slot]
        self.engine.cache.set_length(slot, base + n)
        self.draft.cache.set_length(slot, base + n)

    # ---- stats ------------------------------------------------------------
    def stats(self):
        with self._lock:
            out = dict(self._c)
        out["k"] = self.k
        out["acceptance_rate"] = (out["accepted"] / float(out["drafted"])
                                  if out["drafted"] else 0.0)
        out["verify"] = self._verify_op.cache_stats()
        out["draft_compile"] = self.draft.compile_stats()
        return out

    def close(self):
        self.draft.close()
