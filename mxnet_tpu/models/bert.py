"""BERT for masked-LM + next-sentence pretraining (BASELINE.md reference
config "BERT-base pretraining"; the reference ecosystem ships BERT via
GluonNLP on the same Gluon substrate).

Mesh-first like models/transformer.py: parameter names carry qkv/proj/
ffn_up/ffn_down markers so the Megatron tensor-parallel rules
(`mxnet_tpu.parallel` + `models.transformer.tp_rules`) apply unchanged;
attention routes through `_contrib_packed_self_attention` (the flash
kernels on the unsplit QKV projection where they run, the XLA softmax
elsewhere). Padding is handled with a boolean keep-mask broadcast to
(B, 1, 1, T) — reduced to a per-key mask for the kernels, fused into the
softmax by XLA otherwise."""
from __future__ import annotations

import math

import numpy as np

from ..gluon.block import HybridBlock
from ..gluon import nn
from .transformer import MultiHeadAttention, tp_rules  # noqa: F401

__all__ = ["BERTModel", "BERTEncoder", "bert_tiny", "bert_base",
           "BERTPretrainingLoss"]


def _gather_positions(F, x, positions):
    """Gather (B, M, ...) rows of ``x`` (B, T, ...) at integer ``positions``
    (B, M) — shared by the gather-first decode and the loss fallback."""
    B, M = positions.shape
    rows = F.arange(0, B).reshape((B, 1))
    rows = F.broadcast_mul(rows, F.ones_like(positions))
    idx = F.stack(rows.reshape((-1,)), positions.reshape((-1,)), axis=0)
    return F.gather_nd(x, idx)                         # (B*M, ...)


class _MaskedAttention(MultiHeadAttention):
    """MultiHeadAttention with a padding keep-mask (bidirectional)."""

    def __init__(self, units, num_heads, dropout=0.0, **kwargs):
        super().__init__(units, num_heads, dropout=dropout, causal=False,
                         **kwargs)

    def hybrid_forward(self, F, x, mask=None):
        # packed projection in, (B, T, C) out (see MultiHeadAttention)
        out = F._contrib_packed_self_attention(
            self.qkv(x), mask=mask, num_heads=self._num_heads,
            dropout=self._dropout, causal=False)
        return self.proj(out)


class _BERTLayer(HybridBlock):
    """Post-norm encoder block (BERT convention: residual -> LayerNorm)."""

    def __init__(self, units, num_heads, hidden_size, dropout=0.0, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn = _MaskedAttention(units, num_heads, dropout)
            self.ln1 = nn.LayerNorm(in_channels=units)
            self.ffn_up = nn.Dense(hidden_size, flatten=False,
                                   in_units=units, prefix="ffn_up_")
            self.ffn_down = nn.Dense(units, flatten=False,
                                     in_units=hidden_size,
                                     prefix="ffn_down_")
            self.ln2 = nn.LayerNorm(in_channels=units)
            self.dropout = nn.Dropout(dropout)

    def hybrid_forward(self, F, x, mask=None):
        x = self.ln1(x + self.dropout(self.attn(x, mask)))
        h = F.LeakyReLU(self.ffn_up(x), act_type="gelu")
        x = self.ln2(x + self.dropout(self.ffn_down(h)))
        return x


class BERTEncoder(HybridBlock):
    """Token + segment + learned-position embeddings -> N encoder blocks."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 hidden_size, max_length=512, num_segments=2, dropout=0.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._max_length = max_length
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units,
                                           prefix="word_embed_")
            self.segment_embed = nn.Embedding(num_segments, units,
                                              prefix="segment_embed_")
            self.pos_embed = nn.Embedding(max_length, units,
                                          prefix="pos_embed_")
            self.ln = nn.LayerNorm(in_channels=units)
            self.dropout = nn.Dropout(dropout)
            self.layers = []
            for i in range(num_layers):
                layer = _BERTLayer(units, num_heads, hidden_size, dropout,
                                   prefix="layer%d_" % i)
                self.layers.append(layer)
                self.register_child(layer)

    def hybrid_forward(self, F, tokens, segments, valid_len=None):
        B, T = tokens.shape
        pos = F.arange(0, T).reshape((1, T))
        x = self.word_embed(tokens) + self.segment_embed(segments) \
            + self.pos_embed(pos)
        x = self.dropout(self.ln(x))
        mask = None
        if valid_len is not None:
            # keep-mask (B, 1, 1, T): every query may attend to keys < len
            ar = F.arange(0, T).reshape((1, 1, 1, T))
            mask = F.broadcast_lesser(
                ar, valid_len.reshape((B, 1, 1, 1)))
        for layer in self.layers:
            x = layer(x, mask)
        return x


class BERTModel(HybridBlock):
    """Encoder + pooler + MLM decoder + NSP classifier (pretraining heads).

    Forward returns ``(sequence_output, pooled, mlm_logits, nsp_logits)``.
    """

    def __init__(self, vocab_size=30522, units=768, num_layers=12,
                 num_heads=12, hidden_size=3072, max_length=512,
                 num_segments=2, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = vocab_size
        with self.name_scope():
            self.encoder = BERTEncoder(vocab_size, units, num_layers,
                                       num_heads, hidden_size, max_length,
                                       num_segments, dropout,
                                       prefix="encoder_")
            self.pooler = nn.Dense(units, flatten=False, in_units=units,
                                   prefix="pooler_")
            self.mlm_transform = nn.Dense(units, flatten=False,
                                          in_units=units,
                                          prefix="mlm_transform_")
            self.mlm_ln = nn.LayerNorm(in_channels=units)
            self.mlm_decoder = nn.Dense(vocab_size, flatten=False,
                                        in_units=units,
                                        prefix="mlm_decoder_")
            self.nsp = nn.Dense(2, flatten=False, in_units=units,
                                prefix="nsp_")

    def hybrid_forward(self, F, tokens, segments, valid_len=None,
                       masked_positions=None):
        seq = self.encoder(tokens, segments, valid_len)
        cls = F.slice_axis(seq, axis=1, begin=0, end=1).reshape(
            (seq.shape[0], -1))
        pooled = F.tanh(self.pooler(cls))
        if masked_positions is not None:
            # gather-FIRST (reference GluonNLP BERTModel._decode: the MLM
            # transform + vocab decoder run only on the M masked slots, not
            # all T positions — at s128/M20 that is 6.4x less vocab-head
            # work; the round-5 XPlane study measured full-seq decoding at
            # ~18% of the training step)
            B, M = masked_positions.shape
            picked = _gather_positions(F, seq, masked_positions).reshape(
                (B, M, -1))
            h = F.LeakyReLU(self.mlm_transform(picked), act_type="gelu")
            mlm_logits = self.mlm_decoder(self.mlm_ln(h))  # (B, M, V)
        else:
            h = F.LeakyReLU(self.mlm_transform(seq), act_type="gelu")
            mlm_logits = self.mlm_decoder(self.mlm_ln(h))  # (B, T, V)
        nsp_logits = self.nsp(pooled)
        return seq, pooled, mlm_logits, nsp_logits


class BERTPretrainingLoss(HybridBlock):
    """Masked-LM + next-sentence loss. ``mlm_positions`` selects the masked
    slots (B, M); ``mlm_weights`` zeroes padding in M.

    ``picked=True`` declares that ``mlm_logits`` is already (B, M, V) from
    the model's gather-first decode (``masked_positions`` passed to
    ``BERTModel``) — explicit, because shape inference alone cannot
    distinguish full-sequence logits when T == M."""

    def __init__(self, picked=False, **kwargs):
        super().__init__(**kwargs)
        self._picked = picked

    def hybrid_forward(self, F, mlm_logits, nsp_logits, mlm_labels,
                       mlm_positions, mlm_weights, nsp_labels):
        B, M = mlm_positions.shape
        V = mlm_logits.shape[-1]
        if self._picked:
            assert mlm_logits.shape[1] == M, \
                "picked=True expects (B, M, V) logits"
            picked = mlm_logits.reshape((B * M, V))
        else:
            picked = _gather_positions(F, mlm_logits, mlm_positions)
        logp = F.log_softmax(picked, axis=-1)
        ll = F.pick(logp, mlm_labels.reshape((-1,)), axis=-1)
        w = mlm_weights.reshape((-1,))
        mlm_loss = -F.sum(ll * w) / (F.sum(w) + 1e-6)
        nsp_logp = F.log_softmax(nsp_logits, axis=-1)
        nsp_loss = -F.mean(F.pick(nsp_logp, nsp_labels, axis=-1))
        return mlm_loss + nsp_loss


def bert_tiny(vocab_size=1000, max_length=128, **kwargs):
    """2-layer/128-unit config for tests and the multichip dryrun."""
    return BERTModel(vocab_size=vocab_size, units=128, num_layers=2,
                     num_heads=2, hidden_size=512, max_length=max_length,
                     dropout=0.0, **kwargs)


def bert_base(vocab_size=30522, **kwargs):
    """BERT-base: 12 layers x 768 units x 12 heads (the BASELINE.md
    pretraining reference config)."""
    return BERTModel(vocab_size=vocab_size, units=768, num_layers=12,
                     num_heads=12, hidden_size=3072, **kwargs)
