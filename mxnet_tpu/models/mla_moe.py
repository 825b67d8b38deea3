"""Decoder with multi-head latent attention and held sparse experts.

The block of DeepSeek-V2/V3 and its descendants (Kimi-VL-A3B's language
decoder is the published configuration the benchmark runs): RMSNorm, rotary
positions, latent attention (a low-rank joint key/value compression; keys
of ``nope + rope`` against values of ``v``; one rotary key a position shared
by the heads), a gated (SiLU) feed-forward in the leading dense layers, and
in the rest a dropless expert layer with sigmoid top-k routing, a correction
bias that chooses and does not weigh, and shared experts.

Built for ONE chip's share of an expert- and vocabulary-parallel
deployment: :class:`HeldExpertsFFN` is told which experts it holds
(``first_expert`` and ``experts_held``) and routes over all
``router_width`` of them; the vocabulary is whatever slice the caller
gives. With ``experts_held == router_width`` it is the whole model.
Training form only (keys and values materialised per head from the latent);
the absorbed decode form and a cache arena are not here.

Every block is a Gluon block (so each enters a scope of its own name in a
traced program); the attention op writes ``attention``, the expert layer
``moe_router`` / ``moe_experts`` / ``moe_shared``.
"""
from __future__ import annotations

import itertools
import weakref

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["RMSNorm", "LatentAttention", "GatedFFN", "HeldExpertsFFN",
           "LatentMoEDecoderLayer", "LatentMoEDecoder", "latent_moe_tiny",
           "routed_rows_snapshot"]

# the expert layers alive in this process, for the counter's readers
_EXPERT_LAYERS = weakref.WeakValueDictionary()
_BUILT = itertools.count()


def routed_rows_snapshot():
    """``[[rows routed to each held expert] per live expert layer]`` of each
    layer's last call, in the order the layers were built, fetched from the
    device now (the one host sync of the counter)."""
    return [[float(r) for r in layer.routed_rows.asnumpy()]
            for _, layer in sorted(_EXPERT_LAYERS.items())]


def _dense(units, in_units, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    prefix=prefix)


class RMSNorm(HybridBlock):
    """``unit_offset``: the leaf (then named ``offset``) stores the gain's
    offset from one, zero at the start, and the gain is one plus it."""

    def __init__(self, units, eps=1e-5, unit_offset=False, **kwargs):
        super().__init__(**kwargs)
        self._eps, self._offset = eps, unit_offset
        with self.name_scope():
            # an initializer fills whatever is named ``gamma`` with ones
            self.gamma = self.params.get("offset", shape=(units,),
                                         init="zeros") if unit_offset \
                else self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._eps, unit_offset=self._offset)


class LatentAttention(HybridBlock):
    """``x (B, S, units)`` -> ``(B, S, units)``, causal."""

    def __init__(self, units, num_heads, nope_dim, rope_dim, v_dim, kv_rank,
                 rope_theta=10000.0, eps=1e-5, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._dn, self._dr = num_heads, nope_dim, rope_dim
        self._rank, self._theta = kv_rank, rope_theta
        with self.name_scope():
            self.q = _dense(num_heads * (nope_dim + rope_dim), units, "q_")
            self.kva = _dense(kv_rank + rope_dim, units, "kva_")
            self.kv_norm = RMSNorm(kv_rank, eps, prefix="kv_norm_")
            self.kvb = _dense(num_heads * (nope_dim + v_dim), kv_rank, "kvb_")
            self.proj = _dense(units, num_heads * v_dim, "proj_")

    def hybrid_forward(self, F, x):
        B, S, _ = x.shape
        H, dn, dr, rank = self._heads, self._dn, self._dr, self._rank
        q = self.q(x).reshape((B, S, H, dn + dr))
        q_nope = F.slice_axis(q, axis=-1, begin=0, end=dn).reshape(
            (B, S, H * dn))
        q_rope = F._contrib_rotary_embedding(
            F.slice_axis(q, axis=-1, begin=dn, end=dn + dr),
            theta=self._theta)
        kva = self.kva(x)
        latent = F.slice_axis(kva, axis=-1, begin=0, end=rank)
        k_rope = F._contrib_rotary_embedding(
            F.slice_axis(kva, axis=-1, begin=rank, end=rank + dr),
            theta=self._theta)
        kv = self.kvb(self.kv_norm(latent))
        out = F._contrib_latent_attention(q_nope, q_rope, kv, k_rope,
                                          num_heads=H, causal=True)
        return self.proj(out)


class GatedFFN(HybridBlock):
    """``W_down(silu(W_gate x) * W_up x)``."""

    def __init__(self, units, hidden_size, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(hidden_size, units))
            self.up_weight = self.params.get(
                "up_weight", shape=(hidden_size, units))
            self.down_weight = self.params.get(
                "down_weight", shape=(units, hidden_size))

    def hybrid_forward(self, F, x, gate_weight, up_weight, down_weight):
        return F._contrib_gated_ffn(x, gate_weight, up_weight, down_weight)


class HeldExpertsFFN(HybridBlock):
    """The expert layer as the holder of experts ``first_expert ..
    first_expert + experts_held`` computes it: the routed part of the held
    experts (``parallel.moe.held_experts_ffn``: dropless, sorted, grouped)
    plus the shared experts, which every chip computes alike. The rows
    routed to each held expert in the last call stay on the device in
    :attr:`routed_rows` (an auxiliary output of the traced step; reading it
    is the only host sync)."""

    def __init__(self, units, expert_hidden, router_width, experts_held,
                 first_expert=0, top_k=1, shared_experts=0, scale=1.0,
                 normalize=True, **kwargs):
        super().__init__(**kwargs)
        from .. import ndarray as nd
        self._first, self._top_k = first_expert, top_k
        self._scale, self._normalize = scale, normalize
        self._shared = shared_experts * expert_hidden
        self.routed_rows = nd.zeros((experts_held,))
        _EXPERT_LAYERS[next(_BUILT)] = self
        get = self.params.get
        with self.name_scope():
            self.router_weight = get("router_weight",
                                     shape=(router_width, units))
            # chooses and does not weigh: no gradient, never trained
            self.router_bias = get("router_bias", shape=(router_width,),
                                   init="zeros", grad_req="null")
            self.expert_gate_weight = get(
                "expert_gate_weight", shape=(experts_held, units, expert_hidden))
            self.expert_up_weight = get(
                "expert_up_weight", shape=(experts_held, units, expert_hidden))
            self.expert_down_weight = get(
                "expert_down_weight", shape=(experts_held, expert_hidden, units))
            if self._shared:
                self.shared_gate_weight = get(
                    "shared_gate_weight", shape=(self._shared, units))
                self.shared_up_weight = get(
                    "shared_up_weight", shape=(self._shared, units))
                self.shared_down_weight = get(
                    "shared_down_weight", shape=(units, self._shared))

    def hybrid_forward(self, F, x, router_weight, router_bias,
                       expert_gate_weight, expert_up_weight,
                       expert_down_weight, shared_gate_weight=None,
                       shared_up_weight=None, shared_down_weight=None):
        from .. import _tape
        y, rows = F._contrib_held_experts_ffn(
            x, router_weight, router_bias, expert_gate_weight,
            expert_up_weight, expert_down_weight, first=self._first,
            top_k=self._top_k, scale=self._scale, normalize=self._normalize)
        _tape.aux_write(self.routed_rows, rows._data)
        if self._shared:
            y = y + F._contrib_gated_ffn(
                x, shared_gate_weight, shared_up_weight, shared_down_weight,
                scope="moe_shared")
        return y


class LatentMoEDecoderLayer(HybridBlock):
    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        units, eps = cfg["units"], cfg["eps"]
        with self.name_scope():
            self.attn_norm = RMSNorm(units, eps, prefix="attn_norm_")
            self.attn = LatentAttention(
                units, cfg["num_heads"], cfg["nope_dim"], cfg["rope_dim"],
                cfg["v_dim"], cfg["kv_rank"], cfg["rope_theta"], eps,
                prefix="attn_")
            self.ffn_norm = RMSNorm(units, eps, prefix="ffn_norm_")
            if dense:
                self.ffn = GatedFFN(units, cfg["hidden_size"], prefix="ffn_")
            else:
                self.ffn = HeldExpertsFFN(
                    units, cfg["expert_hidden"], cfg["router_width"],
                    cfg["experts_held"], cfg["first_expert"], cfg["top_k"],
                    cfg["shared_experts"], cfg["routed_scale"],
                    cfg["normalize"], prefix="moe_")

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.attn_norm(x))
        return x + self.ffn(self.ffn_norm(x))


class LatentMoEDecoder(HybridBlock):
    """Embedding -> ``dense_layers`` dense + the rest expert layers -> final
    RMSNorm -> untied head. ``net(tokens)`` gives the logits ``(B, S, V)``;
    ``net(tokens, labels)`` the mean next-token cross-entropy over the
    positions whose label is not negative, the head applied a chunk of
    positions at a time (no whole logits array)."""

    def __init__(self, vocab_size, units, num_layers, num_heads, nope_dim,
                 rope_dim, v_dim, kv_rank, hidden_size, expert_hidden,
                 router_width, experts_held=None, first_expert=0, top_k=1,
                 shared_experts=0, dense_layers=1, routed_scale=1.0,
                 normalize=True, rope_theta=10000.0, eps=1e-5,
                 loss_chunk=2048, **kwargs):
        super().__init__(**kwargs)
        cfg = dict(units=units, num_heads=num_heads, nope_dim=nope_dim,
                   rope_dim=rope_dim, v_dim=v_dim, kv_rank=kv_rank,
                   hidden_size=hidden_size, expert_hidden=expert_hidden,
                   router_width=router_width,
                   experts_held=experts_held or router_width,
                   first_expert=first_expert, top_k=top_k,
                   shared_experts=shared_experts, routed_scale=routed_scale,
                   normalize=normalize, rope_theta=rope_theta, eps=eps)
        self._loss_chunk = loss_chunk
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = LatentMoEDecoderLayer(cfg, i < dense_layers,
                                              prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.norm = RMSNorm(units, eps, prefix="norm_")
            self.head_weight = self.params.get("head_weight",
                                               shape=(vocab_size, units))

    @property
    def expert_layers(self):
        """The :class:`HeldExpertsFFN` blocks, in order."""
        return [l.ffn for l in self.layers if isinstance(l.ffn, HeldExpertsFFN)]

    def hybrid_forward(self, F, tokens, labels=None, head_weight=None):
        x = self.embed(tokens)
        for layer in self.layers:
            x = layer(x)
        x = self.norm(x)
        if labels is None:
            return F.FullyConnected(x, head_weight, no_bias=True,
                                    flatten=False,
                                    num_hidden=head_weight.shape[0])
        return F._contrib_chunked_softmax_cross_entropy(
            x, head_weight, labels, chunk=self._loss_chunk)


def latent_moe_tiny(**kwargs):
    """The CPU test configuration: hidden 64, 4 heads of 24 + 8 / 16, latent
    32, 1 dense + 2 expert layers, 16 experts of which 4 are held, 4 a
    token, 2 shared, vocabulary 64."""
    cfg = dict(vocab_size=64, units=64, num_layers=3, num_heads=4,
               nope_dim=24, rope_dim=8, v_dim=16, kv_rank=32, hidden_size=160,
               expert_hidden=48, router_width=16, experts_held=4,
               first_expert=0, top_k=4, shared_experts=2, dense_layers=1,
               routed_scale=2.446, rope_theta=800000.0, loss_chunk=16)
    cfg.update(kwargs)
    return LatentMoEDecoder(**cfg)
