"""Byte-level decoder with EVA attention and a multi-byte prediction head.

The block of EvaByte (the published configuration the benchmark runs):
RMSNorm whose gain is stored as an offset from one, rotary positions over
the whole head, EVA attention (``ops.nn.eva_attention``: exact causal
softmax inside the query's own window, learned chunk summaries of every
earlier window, one normaliser), a gated (SiLU) feed-forward, and a head
that predicts the next ``pred_heads`` bytes of every position.

A sequence of 32,768 bytes keeps more for the backward pass than a chip
holds, so the decoder can run each layer's forward again in the backward
(``recompute``; ``gluon.block.recomputed``): what is kept of a layer is its
input and the attention's output and log-sum-exp, which spares the flash
kernel its second forward.

Every block is a Gluon block (so each enters a scope of its own name in a
traced program); the attention op writes ``attention`` and, inside it,
``eva_pool``.
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock, recomputed
from .mla_moe import GatedFFN, RMSNorm, _dense

__all__ = ["EvaAttention", "EvaDecoderLayer", "EvaDecoder", "eva_lm_tiny"]

# what a recomputed layer keeps besides its input: the names
# ``ops.pallas_kernels`` tags the EVA forward kernel's results with
KEPT_OF_ATTENTION = ("eva_attention_out", "eva_attention_lse")


class EvaAttention(HybridBlock):
    """``x (B, S, units)`` -> ``(B, S, units)``, causal. ``mu`` pools a
    chunk's keys, ``phi`` weighs its values (both ``(heads, head width)``)."""

    def __init__(self, units, num_heads, window, chunk, rope_theta=10000.0,
                 **kwargs):
        super().__init__(**kwargs)
        self._heads, self._window, self._chunk = num_heads, window, chunk
        self._theta = rope_theta
        with self.name_scope():
            self.q = _dense(units, units, "q_")
            self.k = _dense(units, units, "k_")
            self.v = _dense(units, units, "v_")
            self.mu = self.params.get("mu", shape=(num_heads,
                                                   units // num_heads))
            self.phi = self.params.get("phi", shape=(num_heads,
                                                     units // num_heads))
            self.proj = _dense(units, units, "proj_")

    def hybrid_forward(self, F, x, mu, phi):
        B, S, units = x.shape
        H = self._heads

        def rotated(a):
            return F._contrib_rotary_embedding(
                a.reshape((B, S, H, units // H)),
                theta=self._theta).reshape((B, S, units))

        out = F._contrib_eva_attention(
            rotated(self.q(x)), rotated(self.k(x)), self.v(x), mu, phi,
            num_heads=H, window=self._window, chunk=self._chunk)
        return self.proj(out)


class EvaDecoderLayer(HybridBlock):
    def __init__(self, units, num_heads, hidden_size, window, chunk,
                 rope_theta, eps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, unit_offset=True,
                                     prefix="attn_norm_")
            self.attn = EvaAttention(units, num_heads, window, chunk,
                                     rope_theta, prefix="attn_")
            self.ffn_norm = RMSNorm(units, eps, unit_offset=True,
                                    prefix="ffn_norm_")
            self.ffn = GatedFFN(units, hidden_size, prefix="ffn_")

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.attn_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class EvaDecoder(HybridBlock):
    """Embedding -> ``num_layers`` layers -> final RMSNorm -> untied head of
    ``pred_heads * vocab_size`` rows. ``net(tokens)`` gives the logits
    ``(B, S, pred_heads, vocab_size)``, head j of position t predicting the
    byte at t + 1 + j; ``net(tokens, labels)`` with ``labels (B, S,
    pred_heads)`` (negative: no such byte) the mean over the heads of each
    head's mean cross-entropy, the head applied a chunk of positions at a
    time. ``recompute``: each layer's forward runs again in the backward
    pass, and what is kept of a layer is its input and the attention's
    output and log-sum-exp, not every intermediate."""

    def __init__(self, vocab_size, units, num_layers, num_heads, hidden_size,
                 window, chunk, pred_heads=1, rope_theta=10000.0, eps=1e-5,
                 loss_chunk=2048, recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._pred_heads, self._loss_chunk = pred_heads, loss_chunk
        self._recompute = bool(recompute)
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = EvaDecoderLayer(units, num_heads, hidden_size, window,
                                        chunk, rope_theta, eps,
                                        prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.norm = RMSNorm(units, eps, unit_offset=True, prefix="norm_")
            self.head_weight = self.params.get(
                "head_weight", shape=(pred_heads * vocab_size, units))

    def hybrid_forward(self, F, tokens, labels=None, head_weight=None):
        x = self.embed(tokens)
        for layer in self.layers:
            x = recomputed(layer, x, KEPT_OF_ATTENTION) if self._recompute \
                else layer(x)
        x = self.norm(x)
        if labels is None:
            logits = F.FullyConnected(x, head_weight, no_bias=True,
                                      flatten=False,
                                      num_hidden=head_weight.shape[0])
            return logits.reshape(tuple(x.shape[:-1])
                                  + (self._pred_heads, -1))
        return F._contrib_chunked_softmax_cross_entropy(
            x, head_weight, labels, chunk=self._loss_chunk)


def eva_lm_tiny(**kwargs):
    """The CPU test configuration: hidden 64, 4 heads of 16, window 32,
    chunks of 4, 2 layers, 2 prediction heads over 32 byte values."""
    cfg = dict(vocab_size=32, units=64, num_layers=2, num_heads=4,
               hidden_size=160, window=32, chunk=4, pred_heads=2,
               rope_theta=100000.0, loss_chunk=16)
    cfg.update(kwargs)
    return EvaDecoder(**cfg)
