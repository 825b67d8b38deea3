"""Transformer language model (flagship model for the TPU build).

The reference ships LSTM/attention examples built from ops
(`example/gluon/word_language_model`, `example/nmt`); this provides the
modern equivalent as a first-class Gluon model, designed mesh-first:
parameter names carry `qkv`/`proj`/`ffn_up`/`ffn_down` markers so
tensor-parallel PartitionSpec rules (mxnet_tpu.parallel.shard_params) apply
by regex — the Megatron split: qkv/ffn_up column-sharded on 'tp', proj/
ffn_down row-sharded — and attention routes through the
`_contrib_packed_self_attention` op (full-sequence self-attention on the
unsplit QKV projection) and `_contrib_dot_product_attention` (the decode
steps, q_len != kv_len): pallas flash kernels where they run, XLA
elsewhere.
"""
from __future__ import annotations

import math

import numpy as np

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["MultiHeadAttention", "TransformerEncoderLayer", "TransformerLM",
           "transformer_lm_tiny", "transformer_lm_small", "transformer_lm_base"]


class MultiHeadAttention(HybridBlock):
    def __init__(self, units, num_heads, dropout=0.0, causal=True, **kwargs):
        super().__init__(**kwargs)
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._dropout = dropout
        self._causal = causal
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, flatten=False, use_bias=False,
                                in_units=units, prefix="qkv_")
            self.proj = nn.Dense(units, flatten=False, use_bias=False,
                                 in_units=units, prefix="proj_")

    def hybrid_forward(self, F, x):
        # x: (B, T, C). The packed (B, T, 3C) projection goes to the
        # attention op unsplit and its (B, T, C) result to the output
        # projection: where the flash kernels run they read q, k, v as
        # column blocks of the one array (no 4-D view, no relayout copy);
        # elsewhere the op splits it into (B, T, H, D) views itself
        out = F._contrib_packed_self_attention(
            self.qkv(x), num_heads=self._num_heads, dropout=self._dropout,
            causal=self._causal)
        return self.proj(out)

    def _split_qkv(self, x):
        return self._heads(self.qkv(x))

    def _heads(self, qkv):
        """The packed (B, T, 3C) projection as q, k, v (B, T, H, D)."""
        B, T, C3 = qkv.shape
        H = self._num_heads
        qkv = qkv.reshape((B, T, 3, H, C3 // (3 * H)))
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    # ---- incremental decode (KV-cache) path -------------------------------
    def forward_kv(self, x, kv_mask=None):
        """Full-prefix forward that also returns this layer's K/V.

        ``x (B, T, C)``; ``kv_mask (B, T)`` keep-mask for padded prompt
        tails (``None`` = every position valid). Returns
        ``(out (B, T, C), k (B, T, H, D), v (B, T, H, D))`` — the K/V the
        generation prefill copies into its cache arena."""
        from .. import ndarray as nd
        qkv = self.qkv(x)  # (B, T, 3C)
        out = nd._contrib_packed_self_attention(
            qkv, mask=kv_mask, num_heads=self._num_heads,
            dropout=self._dropout, causal=self._causal)
        # k and v are sliced for the arena only; the attention read them
        # in place
        _, k, v = self._heads(qkv)
        return self.proj(out), k, v

    def step(self, x, k_cache, v_cache, positions):
        """One incremental-decode step against cached K/V.

        ``x (B, 1, C)`` is the new token's hidden state; ``k_cache`` /
        ``v_cache (B, S, H, D)`` hold the first ``positions[b]`` keys and
        values per row. Writes the new K/V at ``positions`` (per-row
        ``dynamic_update_slice``), attends the 1-token query against all
        cached positions ``<= positions[b]``, and returns
        ``(out (B, 1, C), new_k_cache, new_v_cache)``."""
        from .. import ndarray as nd
        B, T, C = x.shape
        q, k, v = self._split_qkv(x)
        k_cache = nd.kv_cache_update(k_cache, k, positions)
        v_cache = nd.kv_cache_update(v_cache, v, positions)
        S = k_cache.shape[1]
        span = nd.arange(0, S, dtype="int32").reshape((1, S))
        kv_mask = span < (positions.reshape((B, 1)) + 1)
        # single-token query: validity lives entirely in kv_mask, so the
        # causal flag is off (q's position IS the last unmasked key)
        out = nd._contrib_dot_product_attention(
            q, k_cache, v_cache, mask=kv_mask, dropout=0.0, causal=False,
            layout="BSHD")
        return self.proj(out.reshape((B, 1, C))), k_cache, v_cache

    def step_chunk(self, x, k_cache, v_cache, start):
        """A multi-token incremental step: append a whole *chunk* of new
        hidden states against cached K/V.

        ``x (B, C, units)`` is a chunk of C consecutive positions starting
        at absolute position ``start[b]`` per row; ``k_cache`` /
        ``v_cache (B, S, H, D)`` hold the first ``start[b]`` committed
        keys/values. Writes the chunk's K/V at ``start`` (per-row
        ``dynamic_update_slice``) and attends each chunk query at absolute
        position ``start[b] + i`` to every cached position ``<= start[b]
        + i`` — causal *within* the chunk, full over the prefix. This is
        the one program shape behind chunked prefill, prefix-cache suffix
        fill, and the speculative verify step: ``step`` is the ``C == 1``
        special case, a full prefill is the ``start == 0`` special case.
        Chunk rows past the caller's valid count produce garbage outputs
        AND garbage cache writes — both unreachable, because committed
        lengths gate every later attention mask and the next chunk's
        write overlays the pad tail before reading it."""
        from .. import ndarray as nd
        B, C, _ = x.shape
        q, k, v = self._split_qkv(x)
        k_cache = nd.kv_cache_update(k_cache, k, start)
        v_cache = nd.kv_cache_update(v_cache, v, start)
        S = k_cache.shape[1]
        span = nd.arange(0, S, dtype="int32").reshape((1, 1, S))
        qpos = start.reshape((B, 1, 1)) + \
            nd.arange(0, C, dtype="int32").reshape((1, C, 1))
        kv_mask = (span < qpos + 1).reshape((B, 1, C, S))
        out = nd._contrib_dot_product_attention(
            q, k_cache, v_cache, mask=kv_mask, dropout=0.0, causal=False,
            layout="BSHD")
        return self.proj(out.reshape((B, C, self._units))), k_cache, v_cache


class TransformerEncoderLayer(HybridBlock):
    """Pre-norm block (attention + MLP)."""

    def __init__(self, units, num_heads, hidden_size, dropout=0.0,
                 causal=True, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.attn = MultiHeadAttention(units, num_heads, dropout, causal)
            self.ln2 = nn.LayerNorm(in_channels=units)
            self.ffn_up = nn.Dense(hidden_size, flatten=False,
                                   in_units=units, prefix="ffn_up_")
            self.ffn_down = nn.Dense(units, flatten=False,
                                     in_units=hidden_size,
                                     prefix="ffn_down_")
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x):
        x = x + self.dropout(self.attn(self.ln1(x)))
        h = F.LeakyReLU(self.ffn_up(self.ln2(x)), act_type="gelu")
        x = x + self.dropout(self.ffn_down(h))
        return x

    def _ffn(self, x):
        from .. import ndarray as nd
        h = nd.LeakyReLU(self.ffn_up(self.ln2(x)), act_type="gelu")
        return x + self.dropout(self.ffn_down(h))

    def forward_kv(self, x, kv_mask=None):
        """Full-prefix forward returning ``(out, k, v)`` (see
        :meth:`MultiHeadAttention.forward_kv`)."""
        a, k, v = self.attn.forward_kv(self.ln1(x), kv_mask)
        return self._ffn(x + self.dropout(a)), k, v

    def step(self, x, k_cache, v_cache, positions):
        """Incremental-decode step (see :meth:`MultiHeadAttention.step`)."""
        a, k_cache, v_cache = self.attn.step(self.ln1(x), k_cache, v_cache,
                                             positions)
        return self._ffn(x + self.dropout(a)), k_cache, v_cache

    def step_chunk(self, x, k_cache, v_cache, start):
        """Chunk-append step (see :meth:`MultiHeadAttention.step_chunk`)."""
        a, k_cache, v_cache = self.attn.step_chunk(self.ln1(x), k_cache,
                                                   v_cache, start)
        return self._ffn(x + self.dropout(a)), k_cache, v_cache


class TransformerLM(HybridBlock):
    """Decoder-only LM: embed → N blocks → norm → logits."""

    def __init__(self, vocab_size, units=256, num_layers=4, num_heads=8,
                 hidden_size=None, max_len=2048, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        hidden_size = hidden_size or 4 * units
        self._units = units
        self._max_len = max_len
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.pos_embed = nn.Embedding(max_len, units, prefix="pos_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            with self.blocks.name_scope():
                for _ in range(num_layers):
                    self.blocks.add(TransformerEncoderLayer(
                        units, num_heads, hidden_size, dropout))
            self.ln_f = nn.LayerNorm(in_channels=units)
            self.head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                 in_units=units, prefix="head_")

    def hybrid_forward(self, F, tokens):
        # tokens: (B, T) int
        B, T = tokens.shape
        from .. import ndarray as nd
        pos = nd.arange(0, T, dtype="int32")
        x = self.embed(tokens) + self.pos_embed(pos)
        x = self.blocks(x)
        x = self.ln_f(x)
        return self.head(x)

    # ---- incremental decode (KV-cache) path -------------------------------
    @property
    def num_heads(self):
        return next(iter(self.blocks)).attn._num_heads

    @property
    def head_dim(self):
        return self._units // self.num_heads

    @property
    def num_layers(self):
        return len(self.blocks)

    @property
    def units(self):
        return self._units

    @property
    def max_len(self):
        return self._max_len

    def init_cache(self, batch_size, max_len=None, dtype="float32"):
        """Zeroed per-layer KV caches: ``[(k, v), ...]`` with each buffer
        ``(batch_size, max_len, heads, head_dim)``."""
        from .. import ndarray as nd
        S = int(max_len or self._max_len)
        shape = (int(batch_size), S, self.num_heads, self.head_dim)
        return [(nd.zeros(shape, dtype=dtype), nd.zeros(shape, dtype=dtype))
                for _ in range(self.num_layers)]

    def prefill(self, tokens, lengths=None):
        """Fill a KV cache from a (padded) prompt in ONE forward pass.

        ``tokens (B, T)`` int; ``lengths (B,)`` int32 valid lengths
        (``None`` = all ``T``). Returns ``(logits, cache)`` where
        ``logits (B, vocab)`` belongs to each row's LAST VALID position
        and ``cache`` is ``[(k, v), ...]`` with ``(B, T, H, D)`` buffers —
        positions past ``lengths[b]`` contain garbage that downstream
        attention must keep masked (``TransformerLM.step`` does)."""
        from .. import ndarray as nd
        B, T = tokens.shape
        pos = nd.arange(0, T, dtype="int32")
        x = self.embed(tokens) + self.pos_embed(pos)
        if lengths is None:
            lengths = nd.full((B,), T, dtype="int32")
        kv_mask = pos.reshape((1, T)) < lengths.reshape((B, 1))
        cache = []
        for blk in self.blocks:
            x, k, v = blk.forward_kv(x, kv_mask)
            cache.append((k, v))
        x = self.ln_f(x)
        # gather each row's last valid hidden state (one-hot contraction:
        # stays one fused program under jit, no host round-trip)
        last = nd.one_hot(lengths - 1, depth=T)              # (B, T)
        h_last = nd.sum(x * last.reshape((B, T, 1)), axis=1)  # (B, C)
        return self.head(h_last), cache

    def prefill_chunk(self, tokens, cache, start):
        """Append a chunk of ``C`` tokens per row at per-row offsets.

        ``tokens (B, C)`` int — consecutive prompt/draft tokens whose
        first element sits at absolute position ``start[b]`` (int32
        ``(B,)``); ``cache`` as returned by :meth:`init_cache` /
        :meth:`prefill`, holding ``start[b]`` committed positions per
        row. Returns ``(logits (B, C, vocab), new_cache)`` where
        ``logits[b, i]`` is the next-token distribution after consuming
        ``tokens[b, :i+1]`` — exactly what the speculative verify step
        scores and what chunked prefill samples its first token from
        (row ``valid - 1`` of the final chunk). Purely functional like
        :meth:`step`; pad rows write garbage K/V past the caller's valid
        count, unreachable through committed lengths (see
        ``MultiHeadAttention.step_chunk``)."""
        from .. import ndarray as nd
        B, C = tokens.shape
        pos = start.reshape((B, 1)) + \
            nd.arange(0, C, dtype="int32").reshape((1, C))
        # clamp for the position-embedding gather only: pad-tail positions
        # of the final chunk can run past max_len; their rows are garbage
        # by contract either way
        pos = nd.minimum(pos, self._max_len - 1)
        x = self.embed(tokens) + self.pos_embed(pos)
        new_cache = []
        for (k_c, v_c), blk in zip(cache, self.blocks):
            x, k_c, v_c = blk.step_chunk(x, k_c, v_c, start)
            new_cache.append((k_c, v_c))
        x = self.ln_f(x)
        return self.head(x), new_cache

    def step(self, tokens, cache, lengths):
        """One fused decode step for a whole batch of sequences.

        ``tokens (B, 1)`` int — the token to append per row; ``cache`` as
        returned by :meth:`init_cache`/:meth:`prefill`; ``lengths (B,)``
        int32 — how many positions are already cached per row (== the
        position the new token is written at). Returns
        ``(logits (B, vocab), new_cache)``. Purely functional: the caller
        owns cache replacement and length bookkeeping."""
        B = tokens.shape[0]
        x = self.embed(tokens) + self.pos_embed(lengths.reshape((B, 1)))
        new_cache = []
        for (k_c, v_c), blk in zip(cache, self.blocks):
            x, k_c, v_c = blk.step(x, k_c, v_c, lengths)
            new_cache.append((k_c, v_c))
        x = self.ln_f(x)
        return self.head(x.reshape((B, self._units))), new_cache


def tp_rules(spec_cls=None):
    """Megatron-style tensor-parallel rules for TransformerLM params."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"qkv_weight$", P("tp", None)),       # column parallel (out, in)
        (r"ffn_up_weight$", P("tp", None)),
        (r"proj_weight$", P(None, "tp")),      # row parallel
        (r"ffn_down_weight$", P(None, "tp")),
        (r"embed_weight$", P(None, "tp")),
        (r"head_weight$", P("tp", None)),
    ]


def transformer_lm_tiny(vocab_size=1024, **kwargs):
    return TransformerLM(vocab_size, units=64, num_layers=2, num_heads=4,
                         max_len=256, **kwargs)


def transformer_lm_small(vocab_size=32000, **kwargs):
    return TransformerLM(vocab_size, units=512, num_layers=8, num_heads=8,
                         **kwargs)


def transformer_lm_base(vocab_size=32000, **kwargs):
    """BERT-base scale (~110M) decoder."""
    return TransformerLM(vocab_size, units=768, num_layers=12, num_heads=12,
                         **kwargs)
