"""Hybrid decoder: Mamba-2 state-space mixers with a softmax-attention mixer
now and then.

The block of Granite 4.0-H (the published configuration the benchmark runs;
the mixer is Dao and Gu, "Transformers are SSMs", ICML 2024): every layer is
``x += r * mixer(RMSNorm(x))`` then ``x += r * ffn(RMSNorm(x))`` with a
residual multiplier ``r`` and a gated (SiLU) feed-forward; ``layer_types``
says which mixer each layer has. ``mamba``: an input projection to ``[z |
xBC | dt]``, a causal depthwise convolution, the selective scan over heads
that share one group of B and C, a gated RMSNorm and an output projection
(``ops.nn.ssm_mixer``: everything between the two projections). ``attention``:
grouped-query causal attention without positions
(``ops.nn.grouped_attention``), its score multiplier folded into q. The
embedding is multiplied on the way in and IS the head on the way out
(logits divided by ``logits_scaling``).

A long sequence keeps more for the backward pass than a chip holds, so the
decoder can run each layer's forward again in the backward (``recompute``;
``gluon.block.recomputed``): what is kept of a layer is its input and, of an
attention layer, the kernel's output and log-sum-exp.

Every block is a Gluon block (so each enters a scope of its own name in a
traced program); the mixers' ops write ``ssm`` (``ssm_conv`` and ``ssm_scan``
inside it) and ``attention``.
"""
from __future__ import annotations

import math

from ..gluon.block import HybridBlock, recomputed
from .mla_moe import GatedFFN, RMSNorm, _dense

__all__ = ["MambaMixer", "GroupedAttention", "HybridDecoderLayer",
           "HybridDecoder", "hybrid_ssm_tiny"]

# what a recomputed layer keeps besides its input: the names
# ``ops.pallas_kernels`` tags the grouped forward kernel's results with
KEPT_OF_ATTENTION = ("grouped_attention_out", "grouped_attention_lse")


class MambaMixer(HybridBlock):
    """``x (B, S, units)`` -> ``(B, S, units)``: ``heads`` scan heads of
    ``head_dim`` over a state ``state`` wide, one group of B and C."""

    def __init__(self, units, heads, head_dim, state, conv_taps=4, chunk=256,
                 eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._head_dim, self._state = heads, head_dim, state
        self._chunk, self._eps = chunk, eps
        inner, conv = heads * head_dim, heads * head_dim + 2 * state
        with self.name_scope():
            self.inp = _dense(inner + conv + heads, units, "in_")
            self.conv_weight = self.params.get("conv_weight",
                                               shape=(conv, conv_taps))
            self.conv_bias = self.params.get("conv_bias", shape=(conv,),
                                             init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(heads,),
                                           init="zeros")
            self.A_log = self.params.get("A_log", shape=(heads,),
                                         init="zeros")
            self.D = self.params.get("D", shape=(heads,), init="ones")
            self.gate_gamma = self.params.get("gate_gamma", shape=(inner,),
                                              init="ones")
            self.out = _dense(units, inner, "out_")

    def hybrid_forward(self, F, x, conv_weight, conv_bias, dt_bias, A_log, D,
                       gate_gamma):
        y = F._contrib_ssm_mixer(
            self.inp(x), conv_weight, conv_bias, dt_bias, A_log, D,
            gate_gamma, num_heads=self._heads, head_dim=self._head_dim,
            state=self._state, chunk=self._chunk, eps=self._eps)
        return self.out(y)


class GroupedAttention(HybridBlock):
    """``x (B, S, units)`` -> ``(B, S, units)``, causal, no positions:
    ``num_heads`` query heads of ``units / num_heads`` over ``num_kv_heads``
    key-value heads, scores times ``multiplier``."""

    def __init__(self, units, num_heads, num_kv_heads, multiplier=None,
                 **kwargs):
        super().__init__(**kwargs)
        d = units // num_heads
        self._heads, self._kv_heads = num_heads, num_kv_heads
        # the op scales by 1/sqrt(d): q carries the rest
        self._q_scale = 1.0 if multiplier is None \
            else float(multiplier) * math.sqrt(d)
        with self.name_scope():
            self.q = _dense(units, units, "q_")
            self.k = _dense(num_kv_heads * d, units, "k_")
            self.v = _dense(num_kv_heads * d, units, "v_")
            self.proj = _dense(units, units, "proj_")

    def hybrid_forward(self, F, x):
        q = self.q(x)
        if self._q_scale != 1.0:
            q = q * self._q_scale
        out = F._contrib_grouped_attention(
            q, self.k(x), self.v(x), num_heads=self._heads,
            num_kv_heads=self._kv_heads)
        return self.proj(out)


class HybridDecoderLayer(HybridBlock):
    """One layer of type ``kind`` (``"mamba"`` or ``"attention"``)."""

    def __init__(self, kind, units, hidden_size, mamba, attention,
                 residual=1.0, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._residual = float(residual)
        with self.name_scope():
            self.mixer_norm = RMSNorm(units, eps, prefix="mixer_norm_")
            if kind == "mamba":
                self.mixer = MambaMixer(units, eps=eps, prefix="mixer_",
                                        **mamba)
            elif kind == "attention":
                self.mixer = GroupedAttention(units, prefix="mixer_",
                                              **attention)
            else:
                raise ValueError("unknown layer type %r" % (kind,))
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            self.ffn = GatedFFN(units, hidden_size, prefix="ffn_")

    def hybrid_forward(self, F, x):
        x = x + self._residual * self.mixer(self.mixer_norm(x))
        return x + self._residual * self.ffn(self.ffn_norm(x))


class HybridDecoder(HybridBlock):
    """Embedding (times ``embedding_multiplier``) -> one layer for each
    entry of ``layer_types`` -> final RMSNorm -> the embedding as the head
    (logits over ``logits_scaling``). ``net(tokens)`` gives the logits
    ``(B, S, vocab_size)``; ``net(tokens, labels)`` with ``labels (B, S)``
    (negative: no target) the mean cross-entropy, the head applied a chunk
    of positions at a time; the embedding's gradient is the sum of the
    lookup's and the head's. ``recompute``: each layer's forward runs again
    in the backward pass."""

    def __init__(self, vocab_size, units, layer_types, hidden_size,
                 mamba_heads, mamba_head_dim, mamba_state, num_heads,
                 num_kv_heads, conv_taps=4, chunk=256,
                 attention_multiplier=None, residual_multiplier=1.0,
                 embedding_multiplier=1.0, logits_scaling=1.0, eps=1e-5,
                 loss_chunk=2048, recompute=False, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._units = vocab_size, units
        self._embedding_multiplier = float(embedding_multiplier)
        self._logits_scaling = float(logits_scaling)
        self._loss_chunk, self._recompute = loss_chunk, bool(recompute)
        mamba = dict(heads=mamba_heads, head_dim=mamba_head_dim,
                     state=mamba_state, conv_taps=conv_taps, chunk=chunk)
        attention = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                         multiplier=attention_multiplier)
        with self.name_scope():
            self.embed_weight = self.params.get("embed_weight",
                                                shape=(vocab_size, units))
            self.layers = []
            for i, kind in enumerate(layer_types):
                layer = HybridDecoderLayer(
                    kind, units, hidden_size, mamba, attention,
                    residual_multiplier, eps, prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.norm = RMSNorm(units, eps, prefix="norm_")

    def hybrid_forward(self, F, tokens, labels=None, embed_weight=None):
        x = F.Embedding(tokens, embed_weight, input_dim=self._vocab,
                        output_dim=self._units) * self._embedding_multiplier
        for layer in self.layers:
            x = recomputed(layer, x, KEPT_OF_ATTENTION) if self._recompute \
                else layer(x)
        x = self.norm(x) * (1.0 / self._logits_scaling)
        if labels is None:
            return F.FullyConnected(x, embed_weight, no_bias=True,
                                    flatten=False, num_hidden=self._vocab)
        return F._contrib_chunked_softmax_cross_entropy(
            x, embed_weight, labels, chunk=self._loss_chunk)


def hybrid_ssm_tiny(**kwargs):
    """The CPU test configuration: hidden 64, three layers (mamba,
    attention, mamba), 4 scan heads of 8 over a state of 16 in chunks of 8,
    4 query heads over 2 key-value heads, 48 ids."""
    cfg = dict(vocab_size=48, units=64,
               layer_types=("mamba", "attention", "mamba"), hidden_size=160,
               mamba_heads=4, mamba_head_dim=8, mamba_state=16, num_heads=4,
               num_kv_heads=2, chunk=8, attention_multiplier=0.0625,
               residual_multiplier=0.22, embedding_multiplier=12.0,
               logits_scaling=8.0, loss_chunk=16)
    cfg.update(kwargs)
    return HybridDecoder(**cfg)
