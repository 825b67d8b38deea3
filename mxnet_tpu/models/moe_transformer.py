"""Stage-stacked Mixture-of-Experts transformer LM — the planner's
flagship workload (ROADMAP item 2: a model that does not fit one chip).

What this is now: the Switch top-1 oracle the planner's and the elastic
tests place and re-place over dp x pp x ep meshes (LayerNorm, learned
positions, a capacity, over-capacity tokens dropped). It is not the path
of any published expert model: a decoder with latent attention and a
dropless layer over the experts a chip holds is ``models/mla_moe.py`` on
``parallel.moe.held_experts_ffn``.

Design is mesh-first for the :mod:`~mxnet_tpu.parallel.planner` naming
convention: every per-layer parameter is ONE tensor with a leading
``n_stages`` axis (``stack_*`` -> ``PartitionSpec('pp')``), and the
expert FFN weights carry ``(n_stages, n_experts, ...)`` leading axes
(``stack_expert_*`` -> ``PartitionSpec('pp', 'ep')``) so a
:class:`~mxnet_tpu.parallel.planner.ShardingPlan` places the whole model
by regex — dp x pp x ep on one mesh, XLA's SPMD partitioner inserting
the all_to_alls/collective-permutes the placement implies. The MoE FFN
is :func:`~mxnet_tpu.parallel.moe.moe_ffn` (Switch top-1 routing, static
capacity, over-capacity tokens dropped) on the full token pool; its
load-balancing aux loss is returned by :meth:`MoETransformerLM.aux_loss`
after a forward for callers that want to add it.

Unlike :class:`~mxnet_tpu.models.transformer.TransformerLM` (generation-
serving oriented, per-layer sub-blocks), this model trades block
modularity for stacked parameters: a python loop over stages indexes
each stage's slab out of the pp-sharded stack, which keeps one parameter
per logical tensor — exactly what elastic reshard-on-restore needs
(checkpoints re-place the SAME full tensors under a different plan,
bitwise).
"""
from __future__ import annotations

import numpy as np

from ..gluon.block import HybridBlock
from ..gluon import nn

__all__ = ["MoETransformerLM", "moe_lm_tiny"]


def _ln(x, gamma, beta, eps=1e-5):
    import jax.numpy as jnp
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gamma + beta


class MoETransformerLM(HybridBlock):
    """Decoder-only LM: embed -> n_stages x [attn + MoE FFN] -> logits.

    All per-stage parameters are stacked on a leading ``n_stages`` axis
    (planner convention); attention is causal, dropout-free (the
    elastic-resume contract wants bitwise-deterministic replay)."""

    def __init__(self, vocab_size=64, units=32, num_heads=2, num_layers=2,
                 hidden_size=None, n_experts=4, max_len=64,
                 capacity_factor=2.0, **kwargs):
        super().__init__(**kwargs)
        hidden_size = hidden_size or 2 * units
        assert units % num_heads == 0
        self._units = units
        self._num_heads = num_heads
        self._num_layers = num_layers
        self._n_experts = n_experts
        self._capacity_factor = capacity_factor
        self._max_len = max_len
        self._aux = None
        L, D, H, E = num_layers, units, hidden_size, n_experts
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.pos_embed = nn.Embedding(max_len, units, prefix="pos_")
            self.head = nn.Dense(vocab_size, flatten=False, use_bias=False,
                                 in_units=units, prefix="head_")
            get = self.params.get
            self.stack_ln1_gamma = get("stack_ln1_gamma", shape=(L, D),
                                       init="ones")
            self.stack_ln1_beta = get("stack_ln1_beta", shape=(L, D),
                                      init="zeros")
            self.stack_ln2_gamma = get("stack_ln2_gamma", shape=(L, D),
                                       init="ones")
            self.stack_ln2_beta = get("stack_ln2_beta", shape=(L, D),
                                      init="zeros")
            self.stack_qkv_weight = get("stack_qkv_weight",
                                        shape=(L, D, 3 * D))
            self.stack_proj_weight = get("stack_proj_weight",
                                         shape=(L, D, D))
            self.stack_gate_weight = get("stack_gate_weight",
                                         shape=(L, D, E))
            self.stack_expert_w1 = get("stack_expert_w1",
                                       shape=(L, E, D, H))
            self.stack_expert_w2 = get("stack_expert_w2",
                                       shape=(L, E, H, D))

    @property
    def n_experts(self):
        return self._n_experts

    @property
    def num_layers(self):
        return self._num_layers

    def profile(self, batch, seq, **kwargs):
        """The planner's :class:`~mxnet_tpu.parallel.planner.ModelProfile`
        for this model at one batch geometry."""
        from ..parallel.planner import ModelProfile
        return ModelProfile.from_block(self, batch, seq=seq,
                                       d_model=self._units, **kwargs)

    def aux_loss(self):
        """Switch load-balancing aux loss summed over stages from the
        most recent forward (traced value; add it to the objective if
        desired — the default objective leaves it out so routing drift
        never breaks bitwise replay comparisons across PRs)."""
        return self._aux

    def _attn(self, x, qkv_w, proj_w):
        import jax.numpy as jnp
        B, T, D = x.shape
        Hn = self._num_heads
        hd = D // Hn
        qkv = x @ qkv_w                                   # (B, T, 3D)
        qkv = qkv.reshape(B, T, 3, Hn, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        q = q.transpose(0, 2, 1, 3)                       # (B, H, T, hd)
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax_softmax(s)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
        return out @ proj_w

    def hybrid_forward(self, F, tokens, stack_ln1_gamma, stack_ln1_beta,
                       stack_ln2_gamma, stack_ln2_beta, stack_qkv_weight,
                       stack_proj_weight, stack_gate_weight,
                       stack_expert_w1, stack_expert_w2):
        from .. import ndarray as nd
        from ..ndarray.ndarray import NDArray
        from ..parallel.moe import moe_ffn

        B, T = tokens.shape
        pos = nd.arange(0, T, dtype="int32")
        x = (self.embed(tokens) + self.pos_embed(pos))._data
        g1, b1 = stack_ln1_gamma._data, stack_ln1_beta._data
        g2, b2 = stack_ln2_gamma._data, stack_ln2_beta._data
        qkv_w, proj_w = stack_qkv_weight._data, stack_proj_weight._data
        gate_w = stack_gate_weight._data
        w1, w2 = stack_expert_w1._data, stack_expert_w2._data
        aux_total = 0.0
        for i in range(self._num_layers):
            x = x + self._attn(_ln(x, g1[i], b1[i]), qkv_w[i], proj_w[i])
            y, aux = moe_ffn(_ln(x, g2[i], b2[i]), gate_w[i], w1[i], w2[i],
                             capacity_factor=self._capacity_factor)
            x = x + y
            aux_total = aux_total + aux
        self._aux = aux_total
        return self.head(NDArray(x))


    # ---- incremental decode (KV-cache) path -------------------------------
    # Same contract as TransformerLM (what DecodeEngine compiles its
    # fused fixed-signature programs against): properties + init_cache /
    # prefill / prefill_chunk / step. The MoE FFN stays moe_ffn — under
    # jit with the expert stacks committed onto an 'ep' mesh axis, the
    # SPMD partitioner shards the expert einsums, so the SAME contract
    # serves expert-parallel with zero decode-path changes.

    @property
    def num_heads(self):
        return self._num_heads

    @property
    def head_dim(self):
        return self._units // self._num_heads

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
                for _ in range(self._num_layers)]

    def _slabs(self):
        """The stacked parameter tensors as raw jax values."""
        return (self.stack_ln1_gamma.data()._data,
                self.stack_ln1_beta.data()._data,
                self.stack_ln2_gamma.data()._data,
                self.stack_ln2_beta.data()._data,
                self.stack_qkv_weight.data()._data,
                self.stack_proj_weight.data()._data,
                self.stack_gate_weight.data()._data,
                self.stack_expert_w1.data()._data,
                self.stack_expert_w2.data()._data)

    def _split_qkv(self, xv, qkv_w):
        """(B, T, D) hidden -> q/k/v in BSHD layout, one slab's weights."""
        B, T, D = xv.shape
        Hn = self._num_heads
        hd = D // Hn
        qkv = (xv @ qkv_w).reshape(B, T, 3, Hn, hd)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def _moe(self, xv, gate_w, w1, w2):
        from ..parallel.moe import moe_ffn
        y, _aux = moe_ffn(xv, gate_w, w1, w2,
                          capacity_factor=self._capacity_factor)
        return y

    def prefill(self, tokens, lengths=None):
        """Fill a KV cache from a (padded) prompt in ONE forward pass.
        Same contract as :meth:`TransformerLM.prefill`: returns
        ``(logits (B, vocab) at each row's last valid position,
        cache [(k, v), ...])``."""
        from .. import ndarray as nd
        from ..ndarray.ndarray import NDArray
        B, T = tokens.shape
        pos = nd.arange(0, T, dtype="int32")
        x = self.embed(tokens) + self.pos_embed(pos)
        if lengths is None:
            lengths = nd.full((B,), T, dtype="int32")
        kv_mask = pos.reshape((1, T)) < lengths.reshape((B, 1))
        g1, b1, g2, b2, qkv_w, proj_w, gate_w, w1, w2 = self._slabs()
        xv = x._data
        cache = []
        for i in range(self._num_layers):
            h = _ln(xv, g1[i], b1[i])
            q, k, v = self._split_qkv(h, qkv_w[i])
            out = nd._contrib_dot_product_attention(
                NDArray(q), NDArray(k), NDArray(v), mask=kv_mask,
                causal=True, layout="BSHD")
            xv = xv + out._data.reshape(B, T, self._units) @ proj_w[i]
            xv = xv + self._moe(_ln(xv, g2[i], b2[i]), gate_w[i],
                                w1[i], w2[i])
            cache.append((NDArray(k), NDArray(v)))
        last = nd.one_hot(lengths - 1, depth=T)              # (B, T)
        h_last = nd.sum(NDArray(xv) * last.reshape((B, T, 1)), axis=1)
        return self.head(h_last), cache

    def _incremental(self, tokens, cache, start, chunk):
        """Shared body of :meth:`step` (chunk=False, C==1) and
        :meth:`prefill_chunk` (chunk=True): append C tokens per row at
        per-row offsets ``start`` against cached K/V, purely
        functional. Returns ``(hidden (B, C, D), new_cache)``."""
        from .. import ndarray as nd
        from ..ndarray.ndarray import NDArray
        B, C = tokens.shape
        if chunk:
            pos = start.reshape((B, 1)) + \
                nd.arange(0, C, dtype="int32").reshape((1, C))
            # clamp for the position-embedding gather only (pad tails of
            # the final chunk may run past max_len; garbage by contract)
            pos = nd.minimum(pos, self._max_len - 1)
        else:
            pos = start.reshape((B, 1))
        x = self.embed(tokens) + self.pos_embed(pos)
        g1, b1, g2, b2, qkv_w, proj_w, gate_w, w1, w2 = self._slabs()
        xv = x._data
        new_cache = []
        for i, (k_c, v_c) in enumerate(cache):
            h = _ln(xv, g1[i], b1[i])
            q, k, v = self._split_qkv(h, qkv_w[i])
            k_c = nd.kv_cache_update(k_c, NDArray(k), start)
            v_c = nd.kv_cache_update(v_c, NDArray(v), start)
            S = k_c.shape[1]
            if chunk:
                span = nd.arange(0, S, dtype="int32").reshape((1, 1, S))
                qpos = start.reshape((B, 1, 1)) + \
                    nd.arange(0, C, dtype="int32").reshape((1, C, 1))
                kv_mask = (span < qpos + 1).reshape((B, 1, C, S))
            else:
                span = nd.arange(0, S, dtype="int32").reshape((1, S))
                kv_mask = span < (start.reshape((B, 1)) + 1)
            out = nd._contrib_dot_product_attention(
                NDArray(q), k_c, v_c, mask=kv_mask, dropout=0.0,
                causal=False, layout="BSHD")
            xv = xv + out._data.reshape(B, C, self._units) @ proj_w[i]
            xv = xv + self._moe(_ln(xv, g2[i], b2[i]), gate_w[i],
                                w1[i], w2[i])
            new_cache.append((k_c, v_c))
        return xv, new_cache

    def prefill_chunk(self, tokens, cache, start):
        """Append a chunk of ``C`` tokens per row at per-row offsets;
        same contract as :meth:`TransformerLM.prefill_chunk`. Returns
        ``(logits (B, C, vocab), new_cache)``."""
        from ..ndarray.ndarray import NDArray
        xv, new_cache = self._incremental(tokens, cache, start, chunk=True)
        return self.head(NDArray(xv)), new_cache

    def step(self, tokens, cache, lengths):
        """One fused decode step; same contract as
        :meth:`TransformerLM.step`. Returns ``(logits (B, vocab),
        new_cache)``."""
        from ..ndarray.ndarray import NDArray
        B = tokens.shape[0]
        xv, new_cache = self._incremental(tokens, cache, lengths,
                                          chunk=False)
        return self.head(NDArray(xv.reshape(B, self._units))), new_cache


def jax_softmax(s):
    import jax
    return jax.nn.softmax(s, axis=-1)


def moe_lm_tiny(vocab_size=64, n_experts=4, num_layers=2, **kwargs):
    """The CPU-oracle test/bench configuration: 2 stages x 4 experts —
    factorable as dp·pp2·ep{1,2,4} on an 8-device pool."""
    return MoETransformerLM(vocab_size, units=32, num_heads=2,
                            num_layers=num_layers, n_experts=n_experts,
                            max_len=64, **kwargs)
