"""Pallas TPU kernels for the hot ops.

Role parity: the reference hand-writes CUDA kernels for its hot paths
(`src/operator/nn/` .cu files, fusion RTC `src/operator/fusion/`); here the
few ops XLA doesn't already fuse optimally get Pallas kernels. First
citizen: flash attention — O(S) memory blockwise attention with online
softmax, the kernel that sets the ceiling for long-context transformer
throughput. This is exactly the fusion the reference could never do:
its attention was composed from ops (`src/operator/contrib/
transformer.cc`), materialising the (S, S) score matrix in HBM.

Forward AND backward are Pallas (MXU matmuls over VMEM-resident tiles,
fp32 accumulators; backward recomputes score tiles from the saved
logsumexp — the flash-attention-2 dq/dkdv split, or ONE kernel: head-fused
over one key block, latent where a (row, head)'s float32 dq fits VMEM).

Supports the full training configuration of the transformer model zoo:
  - key padding mask (B, S): BERT-style bidirectional masking;
  - causal masking with block-level skipping;
  - attention dropout via a counter-based in-kernel PRNG (lowbias32 hash
    over global (head, q, k) element coordinates + a per-call seed), so
    forward and both backward kernels regenerate identical keep bits with
    no O(S^2) mask materialisation and no pltpu PRNG dependency (which
    has no CPU interpret path).

Layout: (batch, heads, seq, head_dim), blocks of 128 on seq to match the
MXU/VPU tiling constraints (pallas_guide.md).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_attention_bshd",
           "flash_attention_packed", "flash_attention_latent",
           "flash_attention_eva", "flash_attention_grouped", "ssm_scan",
           "causal_conv1d", "causal_conv_usable",
           "flash_attention_usable", "flash_attention_bshd_usable",
           "flash_attention_latent_usable", "flash_attention_eva_usable",
           "flash_attention_grouped_usable", "ssm_scan_usable"]

# 128 is the alignment unit (MXU/VPU tiling); actual blocks are chosen
# per call by _pick_blocks: the largest 128-multiple divisor of S up to
# the preferred size. Bigger k-blocks amortize the streaming loop's
# per-iteration overhead — measured on-chip (BERT-base s512): 128/128 =
# 51 TFLOP/s, 256/512 = 74 TFLOP/s end-to-end.
BLOCK_Q = 128
BLOCK_K = 128
_PREF_BLOCK_Q = 256
_PREF_BLOCK_K = 512


def _pick_blocks(S, causal):
    """(blk_q, blk_k) for a length-S problem: largest 128-multiple
    divisors of S up to the preferred sizes. Causal block-skipping
    assumes blk_k <= blk_q, so clamp there. Dropout keep-bits are keyed
    on GLOBAL (head, q, k) coordinates, so block choice never changes
    the sampled mask."""
    def pick(pref):
        b = max(128, min(pref, S))
        while b > 128 and S % b:
            b -= 128
        return b
    bq = pick(_PREF_BLOCK_Q)
    bk = pick(_PREF_BLOCK_K)
    if causal and (bk > bq or bq % bk):
        # block-skip arithmetic needs blk_k to DIVIDE blk_q
        bk = bq
    return bq, bk


# VMEM a head-fused program may plan for (Mosaic's scoped limit is 16 MiB)
_BSHD_VMEM_BUDGET = 14 * 1024 * 1024


def _pick_blocks_bshd(S, causal, HD, itemsize):
    """Block sizes for the head-fused forward, dq and dkdv kernels, shrunk
    until the VMEM footprint fits. Worst case of the three is the dkdv
    backward: two FULL (S, HD) operands + four block-sized operands, all
    double-buffered by the pipeline. Deterministic in (S, causal, HD,
    itemsize) so the forward and backward passes agree on blk_q (the
    saved-LSE layout depends on it). Where blk_k comes out as S the
    backward is the ONE fused kernel instead, which sizes itself
    (:func:`_fused_bwd_group`)."""
    bq, bk = _pick_blocks(S, causal)

    def fits(bq, bk):
        vmem = 2 * (2 * S + 4 * bk + bq) * HD * itemsize
        return vmem <= _BSHD_VMEM_BUDGET

    def shrink(b):
        b -= 128
        while b > 128 and S % b:
            b -= 128
        return max(b, 128)

    while bk > 128 and not fits(bq, bk):
        bk = shrink(bk)
    while bq > 128 and not fits(bq, bk):
        bq = shrink(bq)
    if causal and (bk > bq or bq % bk):
        # the VMEM shrink can break the blk_k-divides-blk_q invariant the
        # causal block-skip arithmetic (n_iter = (qi+1)*(blk_q//blk_k))
        # relies on; restore it with the largest 128-multiple divisor of bq
        # no bigger than the budget-respecting bk (128 always qualifies)
        cap = min(bq, bk)
        bk = 128
        for cand in range(cap, 127, -128):
            if bq % cand == 0:
                bk = cand
                break
    return bq, bk
NEG_INF = -1e30


def flash_attention_usable(q_shape, causal=False):
    """Whether the pallas path supports this problem size."""
    B, H, S, D = q_shape
    return S % BLOCK_Q == 0 and S >= BLOCK_Q and D <= 256


# --------------------------------------------------------------- dropout rng

_U32 = jnp.uint32


def _lowbias32(x):
    """lowbias32 integer hash (public-domain constant set): good avalanche
    at 2 multiply + 3 xorshift — plenty for dropout bits, runs on the VPU
    as plain uint32 lane math."""
    x = x ^ (x >> _U32(16))
    x = x * _U32(0x7FEB352D)
    x = x ^ (x >> _U32(15))
    x = x * _U32(0x846CA68B)
    x = x ^ (x >> _U32(16))
    return x


def _seed_parts(seed_ref, b, h):
    """``(seed, bh)`` from the (1, 3) seed operand ``[seed, bh_base,
    bh_stride]`` (see :func:`_seed_operand`): ``bh`` is the GLOBAL
    batch*heads index of this program's local ``(b, h)``."""
    return seed_ref[0, 0], b * seed_ref[0, 2] + h + seed_ref[0, 1]


def _flat_seed_parts(seed_ref, num_heads):
    """:func:`_seed_parts` for the BHSD kernels, whose grid dim 0 is the
    flat local ``b * num_heads + h``."""
    H = jnp.int32(num_heads)
    pid = pl.program_id(0)
    return _seed_parts(seed_ref, jax.lax.div(pid, H), jax.lax.rem(pid, H))


def _keep_bits(seed, bh, q0, k0, blk_q, blk_k, keep_prob, keys_first=False):
    """Deterministic keep-mask tile for global element (bh, q0+i, k0+j).

    Identical calls from the forward and the backward kernels regenerate
    identical bits — the dropout mask is never materialised. The tile is
    (blk_q, blk_k), or its transpose (blk_k, blk_q) with ``keys_first``.
    """
    shape, q_dim = ((blk_k, blk_q), 1) if keys_first else ((blk_q, blk_k), 0)
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    ki = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    c = (qi.astype(_U32) * _U32(0x9E3779B9)) ^ \
        (ki.astype(_U32) * _U32(0x85EBCA6B)) ^ \
        (bh.astype(_U32) * _U32(0xC2B2AE35)) ^ seed.astype(_U32)
    bits = _lowbias32(c)
    thresh = _U32(min(int(keep_prob * 4294967296.0), 4294967295))
    return bits < thresh


# ----------------------------------------------------------- shared tile math

def _tile_dead(causal, q0, k0, blk_q, blk_k, mask_row):
    """Combined causal/key-padding invalid-position mask for one tile
    (None when every position is live)."""
    dead = None
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        dead = q_pos < k_pos
    if mask_row is not None:
        mdead = mask_row == 0
        dead = mdead if dead is None else (dead | mdead)
    return dead


def _scores(q, k, extra):
    """``q k^T`` in float32, plus ``q2 k2^T`` where ``extra = (q2, k2)``."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if extra is not None:
        q2, k2 = extra
        s = s + jax.lax.dot_general(q2, k2.astype(q2.dtype),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    return s


def _fwd_tile_update(q, k, v, carry, dead, seed, bh, q0, k0, blk_q, blk_k,
                     dropout, scale, extra=None):
    """One online-softmax accumulation step over a (q-block, k-block)
    tile — the single implementation both the BHSD and the head-fused
    BSHD forward kernels run. Masked positions contribute EXACTLY zero
    (not exp(-1e30 - m)): fully-masked rows keep l = 0 and the epsilon
    guard at the end returns 0 output instead of garbage. The normalizer
    l accumulates PRE-dropout probabilities (dropout rescales P, never
    the softmax denominator). ``extra = (q2, k2)`` adds a second dot
    product to the score (latent attention's rotary part: other widths,
    and a key that is not this head's alone)."""
    acc, m_i, l_i = carry
    # matmuls run in the OPERAND dtype (bf16 inputs ride the fast MXU
    # path, 3x the f32 rate) with f32 accumulation; all softmax math
    # stays f32. k/v follow q's dtype so partially-AMP'd models with
    # mixed q/k/v precisions still trace (dot_general requires equal
    # operand dtypes).
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    s = _scores(q, k, extra) * jnp.float32(scale)
    if dead is not None:
        s = jnp.where(dead, jnp.float32(NEG_INF), s)
    m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    if dead is not None:
        p = jnp.where(dead, jnp.float32(0.0), p)
    corr = jnp.exp(m_i - m_new)
    l_new = l_i * corr + jnp.sum(p, axis=-1)
    if dropout > 0.0:
        keep = _keep_bits(seed, bh, q0, k0, blk_q, blk_k, 1.0 - dropout)
        p = jnp.where(keep, p / jnp.float32(1.0 - dropout),
                      jnp.float32(0.0))
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, (acc * corr[:, None] + pv, m_new, l_new)


def _bwd_tile_ds(q, k, v, do, lse, delta, mask_row, causal, dropout,
                 scale, seed, bh, q0, k0, blk_q, blk_k, extra=None,
                 dead=None):
    """Recompute dS = P o (dP - delta) for one tile (and Pdrop for dV) —
    the single implementation all four backward kernels run. ``dead``: the
    tile's own mask of positions not seen, where neither ``causal`` nor
    ``mask_row`` says it (the grouped kernels' stacked rows)."""
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    do = do.astype(q.dtype)
    p, pd, keep = _recompute_tile(q, k, lse, seed, bh, q0, k0, mask_row,
                                  causal, dropout, scale, blk_q, blk_k, extra,
                                  dead)
    dpd = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    if dropout > 0.0:
        dp = jnp.where(keep, dpd / jnp.float32(1.0 - dropout),
                       jnp.float32(0.0))
    else:
        dp = dpd
    ds = p * (dp - delta[:, None])
    return ds, pd


# ------------------------------------------------------------------- forward

def _attn_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                     lse_ref, *, scale, causal, blk_q, blk_k, seq_len,
                     dropout, has_mask, num_heads):
    """One (batch*head, q-block) program: stream K/V blocks with online
    softmax accumulation in fp32. Also writes the per-row logsumexp the
    backward kernels recompute probability tiles from."""
    qi = pl.program_id(1)
    seed, bh = _flat_seed_parts(seed_ref, num_heads)
    q = q_ref[0]                                  # (blk_q, D), raw dtype

    n_kb = seq_len // blk_k

    def body(kb, carry):
        k = k_ref[0, pl.ds(kb * blk_k, blk_k), :]
        v = v_ref[0, pl.ds(kb * blk_k, blk_k), :]
        mrow = mask_ref[0, 0:1, pl.ds(kb * blk_k, blk_k)] \
            if has_mask else None
        dead = _tile_dead(causal, qi * blk_q, kb * blk_k, blk_q, blk_k,
                          mrow)
        _, carry = _fwd_tile_update(q, k, v, carry, dead, seed, bh,
                                    qi * blk_q, kb * blk_k, blk_q, blk_k,
                                    dropout, scale)
        return carry

    D = q.shape[-1]
    acc = jnp.zeros((blk_q, D), jnp.float32)
    m_i = jnp.full((blk_q,), jnp.float32(NEG_INF), jnp.float32)
    l_i = jnp.zeros((blk_q,), jnp.float32)
    if causal:
        # only blocks up to (and including) the diagonal contribute
        n_iter = qi * (blk_q // blk_k) + (blk_q // blk_k)
    else:
        n_iter = n_kb
    # int32 loop bounds: under x64 a Python-int bound makes the induction
    # variable i64 and the `kb * blk_k` block-index arithmetic mixes
    # i64/i32 ('arith.muli' verification error in Mosaic)
    acc, m_i, l_i = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_iter),
                                      body, (acc, m_i, l_i))
    l_safe = jnp.maximum(l_i, jnp.float32(1e-20))
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :] = m_i + jnp.log(l_safe)


# ------------------------------------------------------------ backward tiles

def _recompute_tile(q, k, lse, seed, bh, q0, k0, mask_row, causal,
                    dropout, scale, blk_q, blk_k, extra=None, dead=None):
    """Recompute (P, Pdrop, keep, dead) for one (q-block, k-block) tile
    from the saved logsumexp. Shared by the dq and dkdv kernels."""
    s = _scores(q, k, extra) * jnp.float32(scale)
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        dead = q_pos < k_pos
    if mask_row is not None:
        mdead = mask_row == 0
        dead = mdead if dead is None else (dead | mdead)
    p = jnp.exp(s - lse[:, None])
    if dead is not None:
        p = jnp.where(dead, jnp.float32(0.0), p)
    keep = None
    pd = p
    if dropout > 0.0:
        keep = _keep_bits(seed, bh, q0, k0, blk_q, blk_k, 1.0 - dropout)
        pd = jnp.where(keep, p / jnp.float32(1.0 - dropout),
                       jnp.float32(0.0))
    return p, pd, keep


def _attn_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, mask_ref, dq_ref, *, scale, causal,
                        blk_q, blk_k, seq_len, dropout, has_mask,
                        num_heads):
    """grad wrt Q: one (batch*head, q-block) program streaming K blocks.
    dS = P o (dP - delta); dQ = dS K * scale (flash-attention-2 eq. 4)."""
    qi = pl.program_id(1)
    seed, bh = _flat_seed_parts(seed_ref, num_heads)
    q = q_ref[0]
    do = do_ref[0]                               # (blk_q, D)
    lse = lse_ref[0, 0, :]                       # (blk_q,)
    delta = delta_ref[0, 0, :]                   # (blk_q,)

    def body(kb, dq_acc):
        k = k_ref[0, pl.ds(kb * blk_k, blk_k), :]
        v = v_ref[0, pl.ds(kb * blk_k, blk_k), :]
        mask_row = None
        if has_mask:
            mask_row = mask_ref[0, 0:1, pl.ds(kb * blk_k, blk_k)]
        ds, _ = _bwd_tile_ds(q, k, v, do, lse, delta, mask_row, causal,
                             dropout, scale, seed, bh, qi * blk_q,
                             kb * blk_k, blk_q, blk_k)
        return dq_acc + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_iter = qi * (blk_q // blk_k) + (blk_q // blk_k)
    else:
        n_iter = seq_len // blk_k
    dq = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(n_iter), body,
        jnp.zeros((blk_q, q.shape[-1]), jnp.float32))
    dq_ref[0] = (dq * jnp.float32(scale)).astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, mask_ref, dk_ref, dv_ref, *, scale,
                         causal, blk_q, blk_k, seq_len, dropout, has_mask,
                         num_heads):
    """grads wrt K and V: one (batch*head, k-block) program streaming Q
    blocks. dV = Pdrop^T dO; dK = dS^T Q * scale."""
    ki = pl.program_id(1)
    seed, bh = _flat_seed_parts(seed_ref, num_heads)
    k = k_ref[0]                                 # (blk_k, D)
    v = v_ref[0]
    mask_row = None
    if has_mask:
        mask_row = mask_ref[0, 0:1, pl.ds(ki * blk_k, blk_k)]

    def body(qj, carry):
        dk_acc, dv_acc = carry
        # causal: q-blocks before the diagonal contribute nothing; qb
        # indexes the tail [diag_start, nQ)
        if causal:
            qb = qj + ki * (blk_k // blk_q)
        else:
            qb = qj
        q = q_ref[0, pl.ds(qb * blk_q, blk_q), :]
        do = do_ref[0, pl.ds(qb * blk_q, blk_q), :]
        lse = lse_ref[0, 0, pl.ds(qb * blk_q, blk_q)]
        delta = delta_ref[0, 0, pl.ds(qb * blk_q, blk_q)]
        ds, pd = _bwd_tile_ds(q, k, v, do, lse, delta, mask_row, causal,
                              dropout, scale, seed, bh, qb * blk_q,
                              ki * blk_k, blk_q, blk_k)
        dv_acc = dv_acc + jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    n_qb = seq_len // blk_q
    if causal:
        n_iter = n_qb - ki * (blk_k // blk_q)
    else:
        n_iter = n_qb
    D = k.shape[-1]
    dk, dv = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(n_iter), body,
        (jnp.zeros((blk_k, D), jnp.float32),
         jnp.zeros((blk_k, D), jnp.float32)))
    dk_ref[0] = (dk * jnp.float32(scale)).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ----------------------------------------------------------- pallas plumbing

def _seed_operand(seed, num_heads):
    """The kernels' (1, 3) int32 seed operand ``[seed, bh_base,
    bh_stride]``. Dropout keep-bits are keyed on the global index
    ``bh = b * bh_stride + h + bh_base`` of a program's local ``(b, h)``.
    A scalar ``seed`` means the call holds the whole batch and all heads
    (``bh_base = 0``, ``bh_stride = num_heads``); a ``shard_map`` shard
    passes the 3-vector with its own offsets, so a sharded call draws the
    mask the unsharded call would."""
    if seed is None:
        return jnp.zeros((1, 3), jnp.int32)
    seed = jnp.asarray(seed, jnp.int32)
    if seed.ndim == 0:
        seed = jnp.stack([seed, jnp.int32(0), jnp.int32(num_heads)])
    return seed.reshape(1, 3)


def _prep(q, k, v, kv_mask, seed):
    B, H, S, D = q.shape
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)
    if kv_mask is None:
        mr = jnp.ones((B, 1, S), jnp.int32)  # dummy operand, loads elided
    else:
        mr = kv_mask.astype(jnp.int32).reshape(B, 1, S)
    return qr, kr, vr, mr, _seed_operand(seed, H)


def _flash_fwd_impl(q, k, v, kv_mask, seed, causal, dropout, interpret):
    B, H, S, D = q.shape
    # plain Python float: np.float64 is strongly typed and would promote
    # the f32 kernel to f64 under x64 (TPU Mosaic has no 64-bit types)
    scale = float(1.0 / np.sqrt(D))
    blk_q, blk_k = _pick_blocks(S, causal)
    qr, kr, vr, mr, sr = _prep(q, k, v, kv_mask, seed)
    grid = (B * H, S // blk_q)
    kernel = functools.partial(
        _attn_fwd_kernel, scale=scale, causal=causal, blk_q=blk_q,
        blk_k=blk_k, seq_len=S, dropout=float(dropout),
        has_mask=kv_mask is not None, num_heads=H)
    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 3), lambda b, i: (0, 0)),          # seed
            pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda b, i, H=H: (b // H, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i))),
        interpret=interpret,
        name="flash_bhsd_fwd",
    )
    # trace with x64 off: this framework enables jax_enable_x64 globally
    # (int64 index parity), but Mosaic's grid machinery then emits i64
    # scalars that fail to legalize ('func.return') on the TPU compiler —
    # the kernel itself is pure f32/i32
    with jax.enable_x64(False):
        out, lse = call(sr, qr, kr, vr, mr)
    return out.reshape(B, H, S, D), lse


def _flash_bwd_impl(q, k, v, kv_mask, seed, o, lse, g, causal, dropout,
                    interpret):
    B, H, S, D = q.shape
    scale = float(1.0 / np.sqrt(D))
    blk_q, blk_k = _pick_blocks(S, causal)
    qr, kr, vr, mr, sr = _prep(q, k, v, kv_mask, seed)
    gr = g.reshape(B * H, S, D)
    orr = o.reshape(B * H, S, D)
    # delta_i = rowsum(dO o O): one fused XLA elementwise+reduce, O(S·D)
    delta = jnp.sum(gr.astype(jnp.float32) * orr.astype(jnp.float32),
                    axis=-1)[:, None, :]
    common = dict(scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                  seq_len=S, dropout=float(dropout),
                  has_mask=kv_mask is not None, num_heads=H)
    seed_spec = pl.BlockSpec((1, 3), lambda b, i: (0, 0))
    mask_spec = pl.BlockSpec((1, 1, S), lambda b, i, H=H: (b // H, 0, 0))
    full_spec = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))
    row_full = pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0))

    dq_call = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, **common),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        grid=(B * H, S // blk_q),
        in_specs=[
            seed_spec,
            pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),  # q
            full_spec,                                              # k
            full_spec,                                              # v
            pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),  # do
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),  # lse
            pl.BlockSpec((1, 1, blk_q), lambda b, i: (b, 0, i)),  # delta
            mask_spec,
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i: (b, i, 0)),
        interpret=interpret,
        name="flash_bhsd_dq",
    )
    dkv_call = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, **common),
        out_shape=(jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, D), v.dtype)),
        grid=(B * H, S // blk_k),
        in_specs=[
            seed_spec,
            full_spec,                                              # q
            pl.BlockSpec((1, blk_k, D), lambda b, i: (b, i, 0)),  # k
            pl.BlockSpec((1, blk_k, D), lambda b, i: (b, i, 0)),  # v
            full_spec,                                              # do
            row_full,                                               # lse
            row_full,                                               # delta
            mask_spec,
        ],
        out_specs=(pl.BlockSpec((1, blk_k, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, blk_k, D), lambda b, i: (b, i, 0))),
        interpret=interpret,
        name="flash_bhsd_dkv",
    )
    with jax.enable_x64(False):
        dq = dq_call(sr, qr, kr, vr, gr, lse, delta, mr)
        dk, dv = dkv_call(sr, qr, kr, vr, gr, lse, delta, mr)
    return (dq.reshape(B, H, S, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D))


# ---------------------------------------------------------------- public API

def _reference_attention(q, k, v, causal, kv_mask=None):
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    if causal:
        S = s.shape[-1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, NEG_INF)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :].astype(bool), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention(q, k, v, kv_mask=None, seed=None, causal=False,
                    dropout=0.0, interpret=False):
    """Blockwise exact attention, (B, H, S, D) layout.

    kv_mask: optional (B, S) key keep-mask (nonzero = attend).
    seed:    int32 scalar for attention dropout (required if dropout > 0),
             or the 3-vector of :func:`_seed_operand` from a shard.
    dropout: STATIC attention-probability dropout rate (traced under jit
             per distinct value; rates are fixed hyperparameters).
    """
    out, _ = _flash_fwd_impl(q, k, v, kv_mask, seed, causal, dropout,
                             interpret)
    return out


def _fa_fwd(q, k, v, kv_mask, seed, causal, dropout, interpret):
    out, lse = _flash_fwd_impl(q, k, v, kv_mask, seed, causal, dropout,
                               interpret)
    return out, (q, k, v, kv_mask, seed, out, lse)


def _fa_bwd(causal, dropout, interpret, res, g):
    q, k, v, kv_mask, seed, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, kv_mask, seed, o, lse, g,
                                 causal, dropout, interpret)
    return dq, dk, dv, None, None


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ===================================================================== BSHD
# Head-fused kernels operating on (B, S, H*D) rows: the transformer's
# natural layout straight out of the qkv projection. Eliminates the
# (B,T,H,D)->(B,H,T,D) physical transposes the BHSD kernels force around
# every attention. Mosaic's tiling rule forbids per-head blocks
# ((..,1,D) over (..,H,D)), so each program loads full (blk, H*D) rows —
# every byte of which it needs — and statically unrolls the head loop.
# Requires H*D % 128 == 0. The operands are three (B, S, H*D) arrays
# (`flash_attention_bshd`: (B, S, H, D) views, whose reshapes cost a
# relayout copy each way on the chip) or column blocks 0, 1, 2 of the one
# packed (B, S, 3*H*D) projection (`flash_attention_packed`: no view, no
# copy, one packed gradient written in place): same kernels, the block
# specs' column index is the only difference. Forward: `flash_bshd_fwd`.
# Backward (`_bshd_bwd_impl`): `flash_bshd_bwd`, one kernel, where the
# sequence is one key block (S <= 512, causal S <= 256) and a head group
# fits VMEM; `flash_bshd_dq` then `flash_bshd_dkv` for longer sequences.

def flash_attention_bshd_usable(q_shape, head_dim):
    B, S, HD = q_shape[0], q_shape[1], int(np.prod(q_shape[2:]))
    # Each program holds two FULL (S, H*D) operands in VMEM (K+V in the
    # forward; Q+dO in the dkdv backward; the fused backward holds less)
    # plus block-sized tiles and fp32 accumulators. Bound that footprint
    # well under the ~16 MB VMEM so long-sequence/many-head shapes fall
    # back to the per-head BHSD path instead of failing Mosaic compilation.
    full_operand_bytes = 2 * S * HD * 4
    return (S % BLOCK_Q == 0 and S >= BLOCK_Q and HD % 128 == 0
            and head_dim <= 256
            and full_operand_bytes <= 8 * 1024 * 1024)


def _bshd_fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                     lse_ref, *, scale, causal, blk_q, blk_k, seq_len,
                     dropout, has_mask, num_heads, head_dim):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    n_kb = seq_len // blk_k
    H, D = num_heads, head_dim

    for h in range(H):                            # static unroll
        q = q_ref[0, :, h * D:(h + 1) * D]
        seed, bh = _seed_parts(seed_ref, b, jnp.int32(h))

        def body(kb, carry, h=h, q=q, seed=seed, bh=bh):
            k = k_ref[0, pl.ds(kb * blk_k, blk_k), h * D:(h + 1) * D]
            v = v_ref[0, pl.ds(kb * blk_k, blk_k), h * D:(h + 1) * D]
            mrow = mask_ref[0, 0:1, pl.ds(kb * blk_k, blk_k)] \
                if has_mask else None
            dead = _tile_dead(causal, qi * blk_q, kb * blk_k, blk_q,
                              blk_k, mrow)
            _, carry = _fwd_tile_update(q, k, v, carry, dead, seed, bh,
                                        qi * blk_q, kb * blk_k, blk_q,
                                        blk_k, dropout, scale)
            return carry

        acc = jnp.zeros((blk_q, D), jnp.float32)
        m_i = jnp.full((blk_q,), jnp.float32(NEG_INF), jnp.float32)
        l_i = jnp.zeros((blk_q,), jnp.float32)
        if causal:
            n_iter = qi * (blk_q // blk_k) + (blk_q // blk_k)
        else:
            n_iter = n_kb
        acc, m_i, l_i = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_iter),
                                          body, (acc, m_i, l_i))
        l_safe = jnp.maximum(l_i, jnp.float32(1e-20))
        o_ref[0, :, h * D:(h + 1) * D] = \
            (acc / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :, h] = m_i + jnp.log(l_safe)


def _bshd_bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                        lse_ref, mask_ref, dq_ref, delta_ref, *, scale,
                        causal, blk_q, blk_k, seq_len, dropout, has_mask,
                        num_heads, head_dim):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    H, D = num_heads, head_dim

    for h in range(H):
        q = q_ref[0, :, h * D:(h + 1) * D]
        do = do_ref[0, :, h * D:(h + 1) * D]
        lse = lse_ref[0, 0, :, h]
        # delta = rowsum_d(dO o O): this program holds both blocks, so the
        # product never goes to HBM; written out for the dkdv kernel
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32),
                        axis=-1)
        delta_ref[0, 0, :, h] = delta
        seed, bh = _seed_parts(seed_ref, b, jnp.int32(h))

        def body(kb, dq_acc, h=h, q=q, do=do, lse=lse, delta=delta,
                 seed=seed, bh=bh):
            k = k_ref[0, pl.ds(kb * blk_k, blk_k), h * D:(h + 1) * D]
            v = v_ref[0, pl.ds(kb * blk_k, blk_k), h * D:(h + 1) * D]
            mask_row = None
            if has_mask:
                mask_row = mask_ref[0, 0:1, pl.ds(kb * blk_k, blk_k)]
            ds, _ = _bwd_tile_ds(q, k, v, do, lse, delta, mask_row,
                                 causal, dropout, scale, seed, bh,
                                 qi * blk_q, kb * blk_k, blk_q, blk_k)
            return dq_acc + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            n_iter = qi * (blk_q // blk_k) + (blk_q // blk_k)
        else:
            n_iter = seq_len // blk_k
        dq = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_iter), body,
                               jnp.zeros((blk_q, D), jnp.float32))
        dq_ref[0, :, h * D:(h + 1) * D] = \
            (dq * jnp.float32(scale)).astype(dq_ref.dtype)


def _bshd_bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, mask_ref, dk_ref, dv_ref, *, scale,
                         causal, blk_q, blk_k, seq_len, dropout, has_mask,
                         num_heads, head_dim):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    H, D = num_heads, head_dim
    mask_row = None
    if has_mask:
        mask_row = mask_ref[0, 0:1, pl.ds(ki * blk_k, blk_k)]

    n_qb = seq_len // blk_q
    for h in range(H):
        k = k_ref[0, :, h * D:(h + 1) * D]
        v = v_ref[0, :, h * D:(h + 1) * D]
        seed, bh = _seed_parts(seed_ref, b, jnp.int32(h))

        def body(qj, carry, h=h, k=k, v=v, seed=seed, bh=bh):
            dk_acc, dv_acc = carry
            if causal:
                qb = qj + ki * (blk_k // blk_q)
            else:
                qb = qj
            q = q_ref[0, pl.ds(qb * blk_q, blk_q), h * D:(h + 1) * D]
            do = do_ref[0, pl.ds(qb * blk_q, blk_q), h * D:(h + 1) * D]
            lse = lse_ref[0, qb, :, h]
            delta = delta_ref[0, qb, :, h]
            ds, pd = _bwd_tile_ds(q, k, v, do, lse, delta, mask_row,
                                  causal, dropout, scale, seed, bh,
                                  qb * blk_q, ki * blk_k, blk_q, blk_k)
            dv_acc = dv_acc + jax.lax.dot_general(
                pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc = dk_acc + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_acc, dv_acc

        if causal:
            n_iter = n_qb - ki * (blk_k // blk_q)
        else:
            n_iter = n_qb
        dk, dv = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(n_iter), body,
            (jnp.zeros((blk_k, D), jnp.float32),
             jnp.zeros((blk_k, D), jnp.float32)))
        dk_ref[0, :, h * D:(h + 1) * D] = \
            (dk * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0, :, h * D:(h + 1) * D] = dv.astype(dv_ref.dtype)


def _bshd_mask(kv_mask, B, S):
    if kv_mask is None:
        return jnp.ones((B, 1, S), jnp.int32)   # dummy operand, loads elided
    return kv_mask.astype(jnp.int32).reshape(B, 1, S)


def _bshd_specs(B, S, HD, H, blk_q, blk_k, packed):
    """Block specs of the head-fused kernels' operands. q, k and v are
    read at a COLUMN BLOCK (in units of H*D) of the array each comes in:
    0, 0, 0 of three (B, S, H*D) arrays, or 0, 1, 2 of the one ``packed``
    (B, S, 3*H*D) projection — all that differs between the two forms on
    the way in."""
    cq, ck, cv = (0, 1, 2) if packed else (0, 0, 0)
    n_q = S // blk_q

    def rows(blk, col):
        return pl.BlockSpec((1, blk, HD), lambda b, i: (b, i, col))

    def full(col):
        return pl.BlockSpec((1, S, HD), lambda b, i: (b, 0, col))

    return dict(
        seed=pl.BlockSpec((1, 3), lambda b, i: (0, 0)),
        mask=pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0)),
        q_blk=rows(blk_q, cq), q_full=full(cq),
        k_blk=rows(blk_k, ck), k_full=full(ck),
        v_blk=rows(blk_k, cv), v_full=full(cv),
        blkq=rows(blk_q, 0), blkk=rows(blk_k, 0), full=full(0),
        lse_blk=pl.BlockSpec((1, 1, blk_q, H), lambda b, i: (b, i, 0, 0)),
        lse_full=pl.BlockSpec((1, n_q, blk_q, H),
                              lambda b, i: (b, 0, 0, 0)))


def _bshd_fwd_impl(qf, kf, vf, packed, num_heads, kv_mask, seed, causal,
                   dropout, interpret):
    """Forward over 3-D operands: three (B, S, H*D) arrays, or the one
    ``packed`` (B, S, 3*H*D) projection given three times. Returns ``(out
    (B, S, H*D), lse (B, n_q, blk_q, H))``."""
    B, S = qf.shape[:2]
    H = num_heads
    HD = qf.shape[2] // (3 if packed else 1)
    D = HD // H
    scale = float(1.0 / np.sqrt(D))
    blk_q, blk_k = _pick_blocks_bshd(S, causal, HD, qf.dtype.itemsize)
    n_q = S // blk_q
    sp = _bshd_specs(B, S, HD, H, blk_q, blk_k, packed)
    kernel = functools.partial(
        _bshd_fwd_kernel, scale=scale, causal=causal, blk_q=blk_q,
        blk_k=blk_k, seq_len=S, dropout=float(dropout),
        has_mask=kv_mask is not None, num_heads=H, head_dim=D)
    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, S, HD), qf.dtype),
                   jax.ShapeDtypeStruct((B, n_q, blk_q, H),
                                        jnp.float32)),
        grid=(B, n_q),
        in_specs=[sp["seed"], sp["q_blk"], sp["k_full"], sp["v_full"],
                  sp["mask"]],
        out_specs=(sp["blkq"], sp["lse_blk"]),
        interpret=interpret,
        name="flash_bshd_fwd",
    )
    # x64 off: under the package's jax_enable_x64 an index map's Python
    # ints become i64 and Mosaic cannot legalize the map's return
    with jax.enable_x64(False):
        return call(_seed_operand(seed, H), qf, kf, vf,
                    _bshd_mask(kv_mask, B, S))


def _bshd_bwd_dkv_packed_kernel(*refs, num_heads, head_dim, **kw):
    """The dkdv kernel writing dk and dv as the two halves of ONE
    (blk_k, 2*H*D) output block: columns [H*D, 3*H*D) of the packed
    gradient, whose buffer (the dq call's output, aliased) it never
    reads."""
    *ins, _packed_grad, dkv_ref = refs
    hd = num_heads * head_dim
    _bshd_bwd_dkv_kernel(*ins, dkv_ref.at[:, :, :hd], dkv_ref.at[:, :, hd:],
                         num_heads=num_heads, head_dim=head_dim, **kw)


def _bshd_bwd_split(qf, kf, vf, packed, num_heads, kv_mask, seed, o, lse, g,
                    causal, dropout, interpret):
    """The two-kernel backward (any number of key blocks): dq, which also
    forms delta, then dkdv. For the packed projection both calls write
    their column blocks of the one buffer (dq: block 0; dkdv: blocks 1 and
    2 of the buffer it takes over from the dq call), so no concatenate,
    pad or copy assembles it afterwards."""
    B, S = qf.shape[:2]
    H = num_heads
    HD = o.shape[2]
    D = HD // H
    scale = float(1.0 / np.sqrt(D))
    blk_q, blk_k = _pick_blocks_bshd(S, causal, HD, qf.dtype.itemsize)
    n_q = S // blk_q
    common = dict(scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                  seq_len=S, dropout=float(dropout),
                  has_mask=kv_mask is not None, num_heads=H, head_dim=D)
    sp = _bshd_specs(B, S, HD, H, blk_q, blk_k, packed)
    grad = jax.ShapeDtypeStruct((B, S, (3 if packed else 1) * HD), qf.dtype)

    # the dq kernel also computes delta = rowsum_d(dO o O) per head, in the
    # LSE's (B, n_q, blk_q, H) layout, which the dkdv kernel then reads
    dq_call = pl.pallas_call(
        functools.partial(_bshd_bwd_dq_kernel, **common),
        out_shape=(grad, jax.ShapeDtypeStruct((B, n_q, blk_q, H),
                                              jnp.float32)),
        grid=(B, n_q),
        in_specs=[sp["seed"], sp["q_blk"], sp["k_full"], sp["v_full"],
                  sp["blkq"], sp["blkq"], sp["lse_blk"], sp["mask"]],
        out_specs=(sp["blkq"], sp["lse_blk"]),
        interpret=interpret,
        name="flash_bshd_dq",
    )
    # dkdv: two (B, S, H*D) outputs, or — packed — the two halves of ONE
    # element-indexed block (Mosaic wants every dim so indexed, or none):
    # rows i*blk_k on, 2*H*D columns from column H*D on, of the dq call's
    # buffer, taken over through the alias and never read
    dkv = dict(kernel=_bshd_bwd_dkv_kernel, out_shape=(grad, grad),
               out_specs=(sp["blkk"], sp["blkk"]), in_specs=[], aliases={})
    if packed:
        dkv = dict(
            kernel=_bshd_bwd_dkv_packed_kernel, out_shape=grad,
            out_specs=pl.BlockSpec(
                (pl.Element(1), pl.Element(blk_k), pl.Element(2 * HD)),
                lambda b, i: (b, i * blk_k, HD)),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)], aliases={8: 0})
    dkv_call = pl.pallas_call(
        functools.partial(dkv["kernel"], **common),
        out_shape=dkv["out_shape"],
        grid=(B, S // blk_k),
        in_specs=[sp["seed"], sp["q_full"], sp["k_blk"], sp["v_blk"],
                  sp["full"], sp["lse_full"], sp["lse_full"], sp["mask"]]
        + dkv["in_specs"],
        out_specs=dkv["out_specs"],
        input_output_aliases=dkv["aliases"],
        interpret=interpret,
        name="flash_bshd_dkv",
    )
    sr, mr = _seed_operand(seed, H), _bshd_mask(kv_mask, B, S)
    with jax.enable_x64(False):
        dq, delta = dq_call(sr, qf, kf, vf, g, o, lse, mr)
        if packed:
            return dkv_call(sr, qf, kf, vf, g, lse, delta, mr, dq)
        dk, dv = dkv_call(sr, qf, kf, vf, g, lse, delta, mr)
    return dq, dk, dv


# ---- the fused backward: one kernel where one key block spans the sequence
# With blk_k == S the program that holds a row's K and V forms dS of every
# query against every key, so dQ = dS K needs no sum across programs: the
# scores, their exp and dP are computed ONCE a head and all three gradients
# come from them: five products of S x S x D and one exp a score where the
# two kernels make seven and two, q, k, v, dO read once, delta never in HBM.
# The tile is TRANSPOSED, keys on the sublanes and queries on the lanes
# (S^T = K Q^T), so dV = P^T dO and dK = dS^T Q are plain products of the
# tile, and dQ^T = K^T dS^T leaves only (S, D)-sized transposes to Mosaic;
# LSE and delta are laid along the lanes for it. Measured on the chip at
# 96 x 512 x 12 heads of 64 (PR 28), a layer's backward: the two kernels
# 4.21 ms; fused, query blocks of 256: queries on the sublanes (the two
# kernels' tile, dV and dK transposing it) 2.33, keys on the sublanes with
# dQ transposing the tile 2.44, with dQ^T 2.17; the whole sequence as ONE
# tile: 2.19, 2.16, 2.02. So one tile, dQ^T.
# The grid is (batch, groups of heads): a group is a lane-aligned run of
# whole heads' columns (2.02 ms at 384 columns, 2.15 at 256, 2.44 at 128),
# so a program holds (S, group) of each operand; the packed gradient's
# (S, 3*H*D) block stays in VMEM over a row's groups and is written once.

def _lane_pick(blk, h):
    """Column ``h`` (a traced index) of a (rows, H) block as a (rows,)
    vector: a masked sum over the lanes, exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.sum(jnp.where(lane == h, blk, jnp.float32(0.0)), axis=-1)


def _as_row(col):
    """An (n,) vector laid along the lanes, (1, n): the transpose of its
    broadcast over one 128-lane tile."""
    return jnp.broadcast_to(col[:, None], (col.shape[0], 128)).T[0:1, :]


def _bwd_tile_ds_t(q, k, v, do, lse_row, delta_row, dead_t, dropout, scale,
                   seed, bh):
    """:func:`_bwd_tile_ds` for a whole head with the KEYS on the sublanes:
    ``(dS^T, Pdrop^T)``, both (keys, queries). The operands share q's
    dtype; ``dead_t`` is the transposed invalid-position mask or None."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    p = jnp.exp(s * jnp.float32(scale) - lse_row)
    if dead_t is not None:
        p = jnp.where(dead_t, jnp.float32(0.0), p)
    dpd = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    pd, dp = p, dpd
    if dropout > 0.0:
        keep = _keep_bits(seed, bh, 0, 0, q.shape[0], k.shape[0],
                          1.0 - dropout, keys_first=True)
        pd = jnp.where(keep, p / jnp.float32(1.0 - dropout),
                       jnp.float32(0.0))
        dp = jnp.where(keep, dpd / jnp.float32(1.0 - dropout),
                       jnp.float32(0.0))
    return p * (dp - delta_row), pd


def _bshd_bwd_fused_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                           lse_ref, mask_ref, *out_refs, scale, causal,
                           seq_len, dropout, has_mask, head_dim, group,
                           packed_cols):
    """One (batch row, head group) program: dq, dk and dv of the group's
    heads from one pass over each head's (S, S) scores. ``out_refs`` is
    three (1, S, group) blocks, or — ``packed_cols`` = H*D — the row's one
    (1, S, 3*H*D) block, of which this program writes its three column
    runs."""
    b = pl.program_id(0)
    gi = pl.program_id(1)
    S, D = seq_len, head_dim
    if packed_cols:
        (out_ref,) = out_refs
        dq_ref, dk_ref, dv_ref = (
            out_ref.at[0, :, pl.ds(pl.multiple_of(c * packed_cols
                                                  + gi * group, 128), group)]
            for c in range(3))
    else:
        dq_ref, dk_ref, dv_ref = (r.at[0] for r in out_refs)
    dead_t = None
    if causal:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        dead_t = q_pos < k_pos
    if has_mask:
        # the key mask as a column: (S, 1)
        mdead = jnp.broadcast_to(mask_ref[0, 0:1, :], (128, S)).T[:, 0:1] == 0
        dead_t = mdead if dead_t is None else (dead_t | mdead)
    lse_all = lse_ref[0].reshape(S, -1)          # (n_q, blk_q, H) -> (S, H)

    for j in range(group // D):                  # static unroll
        h = gi * (group // D) + j
        cols = slice(j * D, (j + 1) * D)
        # the matmuls run in q's dtype, as in the other kernels
        q = q_ref[0, :, cols]
        k = k_ref[0, :, cols].astype(q.dtype)
        v = v_ref[0, :, cols].astype(q.dtype)
        do = do_ref[0, :, cols]
        seed, bh = _seed_parts(seed_ref, b, h)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, :, cols].astype(jnp.float32), axis=-1)
        do = do.astype(q.dtype)
        ds_t, pd_t = _bwd_tile_ds_t(
            q, k, v, do, _as_row(_lane_pick(lse_all, h)), _as_row(delta),
            dead_t, dropout, scale, seed, bh)
        ds_t = ds_t.astype(q.dtype)
        dv = jax.lax.dot_general(pd_t.astype(q.dtype), do,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dk = jax.lax.dot_general(ds_t, q, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # dQ^T = K^T dS^T: Mosaic transposes K, not the (S, S) tile
        dq = jax.lax.dot_general(k, ds_t, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32).T
        dq_ref[:, cols] = (dq * jnp.float32(scale)).astype(dq_ref.dtype)
        dk_ref[:, cols] = (dk * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[:, cols] = dv.astype(dv_ref.dtype)


def _fused_bwd_group(S, HD, D, itemsize, packed):
    """Columns of the fused backward's head groups: the widest lane-aligned
    run of whole heads that divides H*D and whose footprint fits
    ``_BSHD_VMEM_BUDGET``, or None where none does (the two kernels run). The footprint: q, k, v, dO, O and the gradient blocks,
    double-buffered (packed: the row's whole (S, 3*H*D) gradient), the LSE
    block at 128 lanes, and four float32 (S, S) tiles of the head at
    work."""
    unit = int(np.lcm(D, 128))
    tiles = (4 * S * S + 2 * S * 128) * 4
    for group in range(HD, 0, -unit):
        if HD % group:
            continue
        out_cols = 3 * HD if packed else 3 * group
        operands = 2 * (5 * group + out_cols) * S * itemsize
        if operands + tiles <= _BSHD_VMEM_BUDGET:
            return group
    return None


def _bshd_bwd_fused(qf, kf, vf, packed, num_heads, kv_mask, seed, o, lse, g,
                    causal, dropout, interpret, group):
    """The one-kernel backward, for a sequence that is one key block.
    ``lse`` comes as the forward wrote it, (B, n_q, blk_q, H)."""
    B, S = qf.shape[:2]
    H = num_heads
    HD = o.shape[2]
    D = HD // H
    n_groups = HD // group
    cq, ck, cv = (0, n_groups, 2 * n_groups) if packed else (0, 0, 0)

    def cols(c):
        return pl.BlockSpec((1, S, group), lambda b, i: (b, 0, c + i))

    grad = jax.ShapeDtypeStruct((B, S, (3 if packed else 1) * HD), qf.dtype)
    if packed:
        out_shape = grad
        out_specs = pl.BlockSpec((1, S, 3 * HD), lambda b, i: (b, 0, 0))
    else:
        out_shape, out_specs = (grad,) * 3, (cols(0),) * 3
    call = pl.pallas_call(
        functools.partial(
            _bshd_bwd_fused_kernel, scale=float(1.0 / np.sqrt(D)),
            causal=causal, seq_len=S, dropout=float(dropout),
            has_mask=kv_mask is not None, head_dim=D, group=group,
            packed_cols=HD if packed else 0),
        out_shape=out_shape,
        grid=(B, n_groups),
        in_specs=[pl.BlockSpec((1, 3), lambda b, i: (0, 0)),
                  cols(cq), cols(ck), cols(cv), cols(0), cols(0),
                  pl.BlockSpec((1,) + lse.shape[1:],
                               lambda b, i: (b, 0, 0, 0)),
                  pl.BlockSpec((1, 1, S), lambda b, i: (b, 0, 0))],
        out_specs=out_specs,
        interpret=interpret,
        name="flash_bshd_bwd",
    )
    with jax.enable_x64(False):
        return call(_seed_operand(seed, H), qf, kf, vf, g, o, lse,
                    _bshd_mask(kv_mask, B, S))


# Where :func:`_bshd_bwd_impl` sent each backward it traced (once a trace,
# not once a step): "fused" = the one kernel, "split" = dq then dkdv.
_BACKWARDS = {"fused": 0, "split": 0}


def flash_backward_stats():
    """Snapshot of the head-fused backward's path counts since the process
    started: ``{"fused", "split"}``."""
    return dict(_BACKWARDS)


def _bshd_bwd_impl(qf, kf, vf, packed, num_heads, kv_mask, seed, o, lse, g,
                   causal, dropout, interpret):
    """Backward over the forward's 3-D operands; ``o`` and ``g`` are
    (B, S, H*D). Returns 3-D ``(dq, dk, dv)`` for separate operands and
    ONE (B, S, 3*H*D) gradient for the packed projection. Takes the fused
    kernel by what it can see in its input: the sequence is one key block
    as :func:`_pick_blocks_bshd` chose it (then dq needs no sum across
    programs) and a head group fits VMEM; the two kernels otherwise."""
    S, HD = qf.shape[1], o.shape[2]
    itemsize = qf.dtype.itemsize
    group = None
    if _pick_blocks_bshd(S, causal, HD, itemsize)[1] == S:
        group = _fused_bwd_group(S, HD, HD // num_heads, itemsize, packed)
    _BACKWARDS["fused" if group else "split"] += 1
    if group:
        return _bshd_bwd_fused(qf, kf, vf, packed, num_heads, kv_mask, seed,
                               o, lse, g, causal, dropout, interpret, group)
    return _bshd_bwd_split(qf, kf, vf, packed, num_heads, kv_mask, seed, o,
                           lse, g, causal, dropout, interpret)


def _flat3(x):
    B, S, H, D = x.shape
    return x.reshape(B, S, H * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_bshd(q, k, v, kv_mask=None, seed=None, causal=False,
                         dropout=0.0, interpret=False):
    """Blockwise exact attention over (B, S, H, D) operands, head-fused:
    no (B,S,H,D)->(B,H,S,D) transpose between the qkv projection and the
    kernel. Same mask/dropout semantics as `flash_attention`. A caller
    that holds the packed projection uses :func:`flash_attention_packed`,
    which also spares the reshapes' relayouts."""
    return _fab_fwd(q, k, v, kv_mask, seed, causal, dropout, interpret)[0]


def _fab_fwd(q, k, v, kv_mask, seed, causal, dropout, interpret):
    out, lse = _bshd_fwd_impl(_flat3(q), _flat3(k), _flat3(v), False,
                              q.shape[2], kv_mask, seed, causal, dropout,
                              interpret)
    return out.reshape(q.shape), (q, k, v, kv_mask, seed, out, lse)


def _fab_bwd(causal, dropout, interpret, res, g):
    q, k, v, kv_mask, seed, o, lse = res
    grads = _bshd_bwd_impl(_flat3(q), _flat3(k), _flat3(v), False,
                           q.shape[2], kv_mask, seed, o, lse, _flat3(g),
                           causal, dropout, interpret)
    return tuple(d.reshape(q.shape) for d in grads) + (None, None)


flash_attention_bshd.defvjp(_fab_fwd, _fab_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 4, 5, 6))
def flash_attention_packed(qkv, num_heads, kv_mask=None, seed=None,
                           causal=False, dropout=0.0, interpret=False):
    """Self-attention straight from the packed projection: ``qkv`` is
    (B, S, 3*H*D) as the QKV Dense produced it ([q | k | v] along the last
    axis), the result (B, S, H*D) as the output projection reads it. The
    same kernels as :func:`flash_attention_bshd`, handed the one
    array three times at column blocks 0, 1, 2; nothing has more than three
    dimensions, and the backward returns ONE (B, S, 3*H*D) gradient."""
    return _fap_fwd(qkv, num_heads, kv_mask, seed, causal, dropout,
                    interpret)[0]


def _fap_fwd(qkv, num_heads, kv_mask, seed, causal, dropout, interpret):
    out, lse = _bshd_fwd_impl(qkv, qkv, qkv, True, num_heads, kv_mask,
                              seed, causal, dropout, interpret)
    return out, (qkv, kv_mask, seed, out, lse)


def _fap_bwd(num_heads, causal, dropout, interpret, res, g):
    qkv, kv_mask, seed, o, lse = res
    d_qkv = _bshd_bwd_impl(qkv, qkv, qkv, True, num_heads, kv_mask,
                           seed, o, lse, g, causal, dropout, interpret)
    return d_qkv, None, None


flash_attention_packed.defvjp(_fap_fwd, _fap_bwd)


# =================================================================== latent
# Multi-head latent attention in its training form: the score of head h is
# q_nope_h . k_nope_h + q_rope_h . k_rope, with ONE rotary key a position
# shared by the heads, and the keys (nope + rope) are wider than the values.
# The operands stay as the projections made them: q_nope (B, S, H*nope) and
# the latent's up-projection kv (B, S, H*(nope + v)), [k_nope_h | v_h] head
# by head, are read as column blocks; q_rope is (B, H, S, rope) (a block's
# last dim must be a multiple of 128 or the whole dim, and the rotary op
# writes a new array anyway); k_rope (B, S, rope) is read by every head
# from the one array. Nothing is padded or broadcast in memory. The key
# blocks are a GRID axis (sequential), so VMEM holds one block of each
# operand and accumulators in scratch; a causal tile wholly above the
# diagonal is skipped and its block index clamped, so nothing is fetched for
# it. The dq + dkv pair run the tile bodies of the BHSD and BSHD kernels
# (`_bwd_tile_ds`, `_tile_dead`), given the rotary pair as their second dot
# product; the forward and the fused backward have their own. Backward
# (`_latent_bwd_impl`):
# `flash_latent_bwd`, ONE kernel, where a (row, head)'s float32 dq fits VMEM
# beside the tile (`_latent_bwd_vmem`; 6 MiB of 30 planned at 8,192, up to
# about 120,000 positions at these widths); `flash_latent_dq` then
# `flash_latent_dkv`, whose VMEM does not grow with the sequence, beyond.

# measured on the chip at 2 x 8192 x 16 heads (PR 27), forward + backward:
# 256/256 70.7 ms, 512/512 38.2, 1024/512 36.4, 256/1024 37.0, 256/2048
# 35.4, 512/1024 32.6, 1024/1024 30.8 (512/2048: dkv refused, VMEM)
# The forward alone on the chip at 4 x 8192 x 16 heads, ms a call (host
# clock, median of six; the kernel's device time in brackets): the body that
# shared the BHSD tile update, queries on the sublanes and every live tile
# masked, 1024/1024 20.2 [18.85]. Keys on the sublanes, the diagonal's tiles
# masked: 1024/1024 16.0 [14.69], 2048/1024 16.2, 2048/512 16.6, 1024/2048
# 16.9, 1024/512 17.2, 512/1024 18.2, 512/512 18.6, 512/2048 18.7; with exp2
# [13.97]. Queries on the sublanes, the statistics lane-replicated: 1024/1024
# 15.3 [13.89], 1024/512 16.6, 512/1024 16.9 (2048/1024: VMEM); with exp2
# 14.4 [13.08], with the scores one product 14.1 [12.63].
_PREF_LATENT_Q = _PREF_LATENT_K = 1024


def flash_attention_latent_usable(seq, nope, rope, v_dim):
    """Whether the latent kernels take this problem."""
    return (seq % 128 == 0 and nope % 128 == 0 and v_dim % 128 == 0
            and rope % 8 == 0 and rope <= 128)


def _pick_blocks_latent(seq):
    """(blk_q, blk_k): the largest multiples of 128 that divide ``seq``, up
    to the preferred sizes. They need not divide each other: a tile is
    skipped by its positions, not by block arithmetic."""
    def pick(pref):
        b = max(128, min(pref // 128 * 128, seq))
        while seq % b:
            b -= 128
        return b
    return pick(_PREF_LATENT_Q), pick(_PREF_LATENT_K)


def _latent_live(causal, q0, k0, blk_q):
    """Whether tile (q0, k0) has any position at or below the diagonal."""
    return (k0 <= q0 + (blk_q - 1)) if causal else True


# ---- the latent forward's own tile body
# The queries on the sublanes, S = Q K^T (queries, keys), so that both
# products stream a 1,024-row operand through the MXU against few weight
# tiles, the keys' and V's (with the keys on the sublanes, as the fused
# backward has them, P^T would be the weights of P V: measured slower, see
# the sizes above); the nope and rope columns of the scores are ONE product
# over their concatenation. The running maximum and sum live in scratch
# replicated over 128 lanes, (blk_q, 128), so reading, rescaling and
# broadcasting them moves nothing between lanes and sublanes; the
# log-sum-exp is turned into its lane row once a query block. The maximum is
# taken on the unscaled scores, and the scale and log2(e) are one multiplier
# in the one exp2 sweep (the maximum and the log-sum-exp carry them). The
# causal mask is applied on the tiles that straddle the diagonal only. A
# masked score is -1e30 and its exp2 exactly 0: key block 0 is live and
# visited first for every query, so no row's maximum is still -1e30 when a
# later tile masks it whole.

def _latent_below(q0, k0, blk_k):
    """Whether causal tile (q0, k0) has no position above the diagonal."""
    return k0 + (blk_k - 1) <= q0


def _lanes(x, n):
    """A (rows, 128) block of lane-replicated values as (rows, n)."""
    return x if n == 128 else jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _latent_fwd_kernel(qn_ref, qr_ref, kv_ref, kr_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref, *, scale, causal, blk_q, blk_k,
                       nope):
    """One (batch, head, q-block, k-block) program: one online-softmax
    step into the scratch accumulators (``m_ref`` and ``l_ref`` (blk_q,
    128), in base 2); the last k-block writes the output and the
    log-sum-exp."""
    kj = pl.program_id(3)
    q0, k0 = pl.program_id(2) * blk_q, kj * blk_k
    log2e = jnp.float32(scale * np.log2(np.e))

    @pl.when(kj == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def tile(masked):
        # ONE product over the keys' nope + rope columns, in the wider of
        # the two query parts' dtypes, accumulated in float32
        dt = jnp.promote_types(qn_ref.dtype, qr_ref.dtype)
        q = jnp.concatenate([qn_ref[0].astype(dt), qr_ref[0, 0].astype(dt)], 1)
        k = jnp.concatenate([kv_ref[0, :, :nope].astype(dt),
                             kr_ref[0].astype(dt)], 1)
        v = kv_ref[0, :, nope:].astype(qn_ref.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos < k_pos, jnp.float32(NEG_INF), s)
        m_i = m_ref[...]
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True) * log2e)
        p = jnp.exp2(s * log2e - _lanes(m_new, blk_k))
        corr = jnp.exp2(m_i - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) + \
            jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        below = _latent_below(q0, k0, blk_k)
        pl.when(below)(lambda: tile(False))
        pl.when(jnp.logical_and(_latent_live(True, q0, k0, blk_q),
                                jnp.logical_not(below)))(lambda: tile(True))
    else:
        tile(False)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        l_safe = jnp.maximum(l_ref[...], jnp.float32(1e-20))
        o_ref[0] = (acc_ref[...] / _lanes(l_safe, acc_ref.shape[1])).astype(
            o_ref.dtype)
        lse = m_ref[...] * jnp.float32(np.log(2.0)) + jnp.log(l_safe)
        lse_ref[0] = lse.T[0:1, :]


# Tiles of one (row, head) the latent forwards traced so far compute, and
# those of them that straddle the diagonal and are masked (counted where
# :func:`_latent_fwd_impl` builds its call, once a trace, by the predicates
# the kernel's ``pl.when``s branch on).
_LATENT_FWD_TILES = {"live": 0, "masked": 0}


def latent_forward_stats():
    """:func:`flash_backward_stats` for the latent forward's tiles."""
    return dict(_LATENT_FWD_TILES)


def _latent_fwd_tiles(seq, blk_q, blk_k, causal):
    """(live, masked) tiles of one (row, head) of the latent forward."""
    tiles = [(q0, k0) for q0 in range(0, seq, blk_q)
             for k0 in range(0, seq, blk_k)]
    if not causal:
        return len(tiles), 0
    live = [t for t in tiles if _latent_live(True, *t, blk_q)]
    return len(live), sum(not _latent_below(q0, k0, blk_k)
                          for q0, k0 in live)


def _latent_dq_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref,
                      delta_ref, dqn_ref, dqr_ref, dqn_acc, dqr_acc, *, scale,
                      causal, blk_q, blk_k, nope):
    """grad wrt both parts of Q: one (batch, head, q-block, k-block)
    program; dQ = dS K * scale, part by part."""
    kj = pl.program_id(3)
    q0, k0 = pl.program_id(2) * blk_q, kj * blk_k

    @pl.when(kj == 0)
    def _():
        dqn_acc[...] = jnp.zeros(dqn_acc.shape, jnp.float32)
        dqr_acc[...] = jnp.zeros(dqr_acc.shape, jnp.float32)

    @pl.when(_latent_live(causal, q0, k0, blk_q))
    def _():
        kn, kr = kv_ref[0, :, :nope], kr_ref[0]
        ds, _ = _bwd_tile_ds(
            qn_ref[0], kn, kv_ref[0, :, nope:], do_ref[0], lse_ref[0, 0, :],
            delta_ref[0, 0, :], None, causal, 0.0, scale, None, None, q0, k0,
            blk_q, blk_k, extra=(qr_ref[0, 0], kr))
        dqn_acc[...] += jax.lax.dot_general(
            ds.astype(kn.dtype), kn, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dqr_acc[...] += jax.lax.dot_general(
            ds.astype(kr.dtype), kr, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        dqn_ref[0] = (dqn_acc[...] * jnp.float32(scale)).astype(dqn_ref.dtype)
        dqr_ref[0, 0] = (dqr_acc[...] * jnp.float32(scale)).astype(
            dqr_ref.dtype)


def _latent_dkv_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref,
                       delta_ref, dkv_ref, dkr_ref, dkn_acc, dv_acc, dkr_acc,
                       *, scale, causal, blk_q, blk_k, nope):
    """grads wrt K's own part, V and this head's share of the shared rotary
    key: one (batch, head, k-block, q-block) program. dV = P^T dO; dK = dS^T
    Q * scale, part by part. The heads' shares of dk_rope are summed
    outside (float32)."""
    qi = pl.program_id(3)
    k0, q0 = pl.program_id(2) * blk_k, qi * blk_q

    @pl.when(qi == 0)
    def _():
        dkn_acc[...] = jnp.zeros(dkn_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        dkr_acc[...] = jnp.zeros(dkr_acc.shape, jnp.float32)

    @pl.when(_latent_live(causal, q0, k0, blk_q))
    def _():
        qn, qr, do = qn_ref[0], qr_ref[0, 0], do_ref[0]
        ds, pd = _bwd_tile_ds(
            qn, kv_ref[0, :, :nope], kv_ref[0, :, nope:], do,
            lse_ref[0, 0, :], delta_ref[0, 0, :], None, causal, 0.0, scale,
            None, None, q0, k0, blk_q, blk_k, extra=(qr, kr_ref[0]))
        over_q = (((0,), (0,)), ((), ()))
        dv_acc[...] += jax.lax.dot_general(
            pd.astype(do.dtype), do, over_q,
            preferred_element_type=jnp.float32)
        dkn_acc[...] += jax.lax.dot_general(
            ds.astype(qn.dtype), qn, over_q,
            preferred_element_type=jnp.float32)
        dkr_acc[...] += jax.lax.dot_general(
            ds.astype(qr.dtype), qr, over_q,
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dkv_ref[0, :, :nope] = (dkn_acc[...] * jnp.float32(scale)).astype(
            dkv_ref.dtype)
        dkv_ref[0, :, nope:] = dv_acc[...].astype(dkv_ref.dtype)
        dkr_ref[0, 0] = dkr_acc[...] * jnp.float32(scale)


# ---- the fused latent backward: one pass over each live tile's scores
# Grid (batch, head, key block, query block), both block axes sequential.
# dk_nope, dv and the head's share of dk_rope accumulate over the inner query
# sweep as in the dkv kernel; dq accumulates over the OUTER key axis into a
# float32 scratch that holds the whole sequence of one (row, head) and is
# cast and written during the last key block's sweep (the dq blocks' index
# stands still until then, so nothing is written back early): eight MXU
# passes a tile (a 64-deep or 64-wide product costs a 128 one) where dq + dkv
# make eleven, one exp and one mask sweep where they make two. The tile has
# the KEYS on the sublanes (S^T = K Q^T), so dV = P^T dO and dK = dS^T Q are
# plain products of it, LSE and delta are read as the lane rows they are
# stored as, and dQ^T = K^T dS^T accumulates TRANSPOSED ((nope + rope) x S
# float32, no lane padding of the 64-wide rotary part), turned once a query
# block on the way out. The causal mask is applied on tiles that straddle the
# diagonal only. Measured on the chip at 4 x 8192 x 16 heads (PR 30), a
# layer's backward with delta and the dk_rope sum: the pair 43.1 ms; fused,
# 1024/1024: queries on the sublanes 31.3, keys on the sublanes with dQ
# transposing the tile 30.8, with dQ^T 29.2 (29.7 masking every live tile);
# 512/1024 30.7, 1024/512 30.6, 512/512 32.3. Same bits as the pair.

def _latent_bwd_kernel(qn_ref, qr_ref, kv_ref, kr_ref, do_ref, lse_ref,
                       delta_ref, dqn_ref, dqr_ref, dkv_ref, dkr_ref,
                       dqn_acc, dqr_acc, dkn_acc, dv_acc, dkr_acc, *, scale,
                       causal, blk_q, blk_k, nope):
    """One (batch, head, k-block, q-block) program of the one-kernel
    backward. ``dqn_acc`` / ``dqr_acc`` are (n_q, nope | rope, blk_q):
    dQ^T of the whole sequence, block by block."""
    kj, qi = pl.program_id(2), pl.program_id(3)
    k0, q0 = kj * blk_k, qi * blk_q
    nn, tn = (((1,), (0,)), ((), ())), (((0,), (0,)), ((), ()))

    @pl.when(qi == 0)
    def _():
        dkn_acc[...] = jnp.zeros(dkn_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        dkr_acc[...] = jnp.zeros(dkr_acc.shape, jnp.float32)

    @pl.when(kj == 0)
    def _():
        dqn_acc[qi] = jnp.zeros(dqn_acc.shape[1:], jnp.float32)
        dqr_acc[qi] = jnp.zeros(dqr_acc.shape[1:], jnp.float32)

    def tile(masked):
        # the matmuls run in q's dtype, as in the other kernels
        qn, qr = qn_ref[0], qr_ref[0, 0]
        kn = kv_ref[0, :, :nope].astype(qn.dtype)
        v = kv_ref[0, :, nope:].astype(qn.dtype)
        kr = kr_ref[0].astype(qr.dtype)
        do = do_ref[0].astype(qn.dtype)
        p_t = jnp.exp(_scores(kn, qn, (kr, qr)) * jnp.float32(scale)
                      - lse_ref[0])
        if masked:
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 0)
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, p_t.shape, 1)
            p_t = jnp.where(q_pos < k_pos, jnp.float32(0.0), p_t)
        ds_t = (p_t * (_scores(v, do, None) - delta_ref[0])).astype(qn.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, nn, preferred_element_type=jnp.float32)
        dkn_acc[...] += jax.lax.dot_general(
            ds_t, qn, nn, preferred_element_type=jnp.float32)
        dkr_acc[...] += jax.lax.dot_general(
            ds_t, qr, nn, preferred_element_type=jnp.float32)
        dqn_acc[qi] += jax.lax.dot_general(
            kn, ds_t, tn, preferred_element_type=jnp.float32)
        dqr_acc[qi] += jax.lax.dot_general(
            kr, ds_t, tn, preferred_element_type=jnp.float32)

    if causal:
        below = k0 + (blk_k - 1) <= q0       # no position above the diagonal
        pl.when(below)(lambda: tile(False))
        pl.when(jnp.logical_and(_latent_live(True, q0, k0, blk_q),
                                jnp.logical_not(below)))(lambda: tile(True))
    else:
        tile(False)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dkv_ref[0, :, :nope] = (dkn_acc[...] * jnp.float32(scale)).astype(
            dkv_ref.dtype)
        dkv_ref[0, :, nope:] = dv_acc[...].astype(dkv_ref.dtype)
        dkr_ref[0, 0] = dkr_acc[...] * jnp.float32(scale)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        dqn_ref[0] = (dqn_acc[qi].T * jnp.float32(scale)).astype(
            dqn_ref.dtype)
        dqr_ref[0, 0] = (dqr_acc[qi].T * jnp.float32(scale)).astype(
            dqr_ref.dtype)


def _latent_specs(H, nope, rope, v_dim, blk_q, blk_k, causal, q_inner):
    """Block specs of the latent kernels' operands on a (batch, head, outer
    block, inner block) grid; ``q_inner`` says which of the two block axes
    the innermost grid axis walks (the dkv kernel's). A causal tile that is
    skipped takes the block index of the nearest live one, so its operands
    are not fetched again."""
    if q_inner:
        def qk(j, i):
            return (jnp.maximum(i, (j * blk_k) // blk_q) if causal else i), j
    else:
        def qk(i, j):
            return i, (jnp.minimum(j, (i * blk_q + blk_q - 1) // blk_k)
                       if causal else j)

    def q_side(spec):
        return lambda b, h, x, y: spec(b, h, qk(x, y)[0])

    def k_side(spec):
        return lambda b, h, x, y: spec(b, h, qk(x, y)[1])

    return {
        "q_nope": pl.BlockSpec((1, blk_q, nope),
                               q_side(lambda b, h, i: (b, i, h))),
        "q_rope": pl.BlockSpec((1, 1, blk_q, rope),
                               q_side(lambda b, h, i: (b, h, i, 0))),
        "out": pl.BlockSpec((1, blk_q, v_dim),
                            q_side(lambda b, h, i: (b, i, h))),
        "row": pl.BlockSpec((1, 1, blk_q),
                            q_side(lambda b, h, i: (b * H + h, 0, i))),
        "kv": pl.BlockSpec((1, blk_k, nope + v_dim),
                           k_side(lambda b, h, j: (b, j, h))),
        "k_rope": pl.BlockSpec((1, blk_k, rope),
                               k_side(lambda b, h, j: (b, j, 0))),
        "dk_rope": pl.BlockSpec((1, 1, blk_k, rope),
                                k_side(lambda b, h, j: (b, h, j, 0))),
    }


def _latent_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
                 interpret, outer="parallel", vmem_limit=None):
    """``outer`` is the outer block axis's semantics; ``vmem_limit`` the
    bytes Mosaic may plan for where the default 16 MiB are too few."""
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", outer, "arbitrary"),
            vmem_limit_bytes=vmem_limit),
        interpret=interpret, name=name)


def _latent_dims(q_nope, q_rope, kv, num_heads, blocks):
    B, S, _ = q_nope.shape
    nope, rope = q_nope.shape[-1] // num_heads, q_rope.shape[-1]
    v_dim = kv.shape[-1] // num_heads - nope
    blk_q, blk_k = blocks or _pick_blocks_latent(S)
    return B, S, nope, rope, v_dim, blk_q, blk_k


def _latent_fwd_impl(q_nope, q_rope, kv, k_rope, num_heads, causal, blocks,
                     interpret):
    H = num_heads
    B, S, nope, rope, v_dim, blk_q, blk_k = _latent_dims(
        q_nope, q_rope, kv, H, blocks)
    spec = _latent_specs(H, nope, rope, v_dim, blk_q, blk_k, causal, False)
    live, masked = _latent_fwd_tiles(S, blk_q, blk_k, causal)
    _LATENT_FWD_TILES["live"] += live
    _LATENT_FWD_TILES["masked"] += masked
    kernel = functools.partial(
        _latent_fwd_kernel, scale=float(1.0 / np.sqrt(nope + rope)),
        causal=causal, blk_q=blk_q, blk_k=blk_k, nope=nope)
    call = _latent_call(
        kernel, "flash_latent_fwd", (B, H, S // blk_q, S // blk_k),
        [spec["q_nope"], spec["q_rope"], spec["kv"], spec["k_rope"]],
        (spec["out"], spec["row"]),
        (jax.ShapeDtypeStruct((B, S, H * v_dim), q_nope.dtype),
         jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)),
        [(blk_q, v_dim), (blk_q, 128), (blk_q, 128)], interpret)
    with jax.enable_x64(False):
        return call(q_nope, q_rope, kv, k_rope)


def _latent_bwd_split(operands, H, causal, dims, interpret):
    """The two-kernel backward: dq over the key blocks, then dkv over the
    query blocks, each recomputing the tile. VMEM holds blocks only."""
    q_nope, q_rope, kv = operands[:3]
    B, S, nope, rope, v_dim, blk_q, blk_k = dims
    common = dict(scale=float(1.0 / np.sqrt(nope + rope)), causal=causal,
                  blk_q=blk_q, blk_k=blk_k, nope=nope)
    by_k = _latent_specs(H, nope, rope, v_dim, blk_q, blk_k, causal, False)
    by_q = _latent_specs(H, nope, rope, v_dim, blk_q, blk_k, causal, True)

    def ins(s):
        return [s["q_nope"], s["q_rope"], s["kv"], s["k_rope"], s["out"],
                s["row"], s["row"]]

    dq_call = _latent_call(
        functools.partial(_latent_dq_kernel, **common), "flash_latent_dq",
        (B, H, S // blk_q, S // blk_k), ins(by_k),
        (by_k["q_nope"], by_k["q_rope"]),
        (jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
         jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype)),
        [(blk_q, nope), (blk_q, rope)], interpret)
    dkv_call = _latent_call(
        functools.partial(_latent_dkv_kernel, **common), "flash_latent_dkv",
        (B, H, S // blk_k, S // blk_q), ins(by_q),
        (by_q["kv"], by_q["dk_rope"]),
        (jax.ShapeDtypeStruct(kv.shape, kv.dtype),
         jax.ShapeDtypeStruct((B, H, S, rope), jnp.float32)),
        [(blk_k, nope), (blk_k, v_dim), (blk_k, rope)], interpret)
    return dq_call(*operands) + dkv_call(*operands)


# VMEM the fused latent backward may plan for: the v5e's 128 MiB less what
# the compiler keeps for itself
_LATENT_VMEM_BUDGET = 112 * 1024 * 1024


def _latent_bwd_vmem(seq, nope, rope, v_dim, blk_q, blk_k, itemsize):
    """Bytes of VMEM the one-kernel backward plans for: the blocks of its
    seven operands and four gradients, double-buffered by the pipeline (a
    last dim under 128 takes 128 lanes, a row vector 8 sublanes), the key
    block's three float32 accumulators, four float32 (blk_k, blk_q) tiles,
    and dQ^T of the whole sequence in float32, the one term that grows with
    it. The compiler's own count at 1024 / 1024 is 15.6 MiB + dQ^T; this
    plans 23.6."""
    lanes = -(-rope // 128) * 128
    blocks = (blk_q * (nope + lanes + v_dim)
              + blk_k * (nope + v_dim + lanes)) * itemsize + 2 * 8 * blk_q * 4
    grads = (blk_q * (nope + lanes) + blk_k * (nope + v_dim)) * itemsize \
        + blk_k * lanes * 4
    accs = blk_k * (nope + v_dim + lanes) * 4
    tiles = 4 * blk_q * blk_k * 4
    return 2 * (blocks + grads) + accs + tiles + seq * (nope + rope) * 4


def _latent_bwd_fused(operands, H, causal, dims, interpret):
    """The one-kernel backward (see :func:`_latent_bwd_kernel`)."""
    q_nope, q_rope, kv = operands[:3]
    B, S, nope, rope, v_dim, blk_q, blk_k = dims
    n_q, n_k = S // blk_q, S // blk_k
    spec = _latent_specs(H, nope, rope, v_dim, blk_q, blk_k, causal, True)

    def last_sweep(block, index):
        """A dq block: query block i during the last key block's sweep,
        block 0 (unwritten, so never written back) before it."""
        return pl.BlockSpec(block, lambda b, h, j, i: index(
            b, h, jnp.where(j == n_k - 1, i, 0)))

    call = _latent_call(
        functools.partial(
            _latent_bwd_kernel, scale=float(1.0 / np.sqrt(nope + rope)),
            causal=causal, blk_q=blk_q, blk_k=blk_k, nope=nope),
        "flash_latent_bwd", (B, H, n_k, n_q),
        [spec["q_nope"], spec["q_rope"], spec["kv"], spec["k_rope"],
         spec["out"], spec["row"], spec["row"]],
        (last_sweep((1, blk_q, nope), lambda b, h, i: (b, i, h)),
         last_sweep((1, 1, blk_q, rope), lambda b, h, i: (b, h, i, 0)),
         spec["kv"], spec["dk_rope"]),
        (jax.ShapeDtypeStruct(q_nope.shape, q_nope.dtype),
         jax.ShapeDtypeStruct(q_rope.shape, q_rope.dtype),
         jax.ShapeDtypeStruct(kv.shape, kv.dtype),
         jax.ShapeDtypeStruct((B, H, S, rope), jnp.float32)),
        [(n_q, nope, blk_q), (n_q, rope, blk_q), (blk_k, nope),
         (blk_k, v_dim), (blk_k, rope)], interpret, outer="arbitrary",
        vmem_limit=_latent_bwd_vmem(S, nope, rope, v_dim, blk_q, blk_k,
                                    q_nope.dtype.itemsize))
    return call(*operands)


# Where :func:`_latent_bwd_impl` sent each backward it traced (once a trace):
# "fused" = the one kernel, "split" = dq then dkv.
_LATENT_BACKWARDS = {"fused": 0, "split": 0}


def latent_backward_stats():
    """:func:`flash_backward_stats` for the latent family."""
    return dict(_LATENT_BACKWARDS)


def _latent_bwd_impl(q_nope, q_rope, kv, k_rope, o, lse, g, num_heads,
                     causal, blocks, interpret):
    """Backward of the latent attention. Takes the one kernel by what it
    can see in its input: dQ^T of a (row, head)'s whole sequence fits VMEM
    beside the tile (:func:`_latent_bwd_vmem`); the dq + dkv pair
    otherwise."""
    H = num_heads
    dims = _latent_dims(q_nope, q_rope, kv, H, blocks)
    B, S, nope, rope, v_dim, blk_q, blk_k = dims
    # delta_i = rowsum(dO o O) per head: one fused XLA elementwise + reduce
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(B, S, H, v_dim), axis=-1)
    delta = jnp.transpose(delta, (0, 2, 1)).reshape(B * H, 1, S)
    fused = _latent_bwd_vmem(S, nope, rope, v_dim, blk_q, blk_k,
                             q_nope.dtype.itemsize) <= _LATENT_VMEM_BUDGET
    _LATENT_BACKWARDS["fused" if fused else "split"] += 1
    run = _latent_bwd_fused if fused else _latent_bwd_split
    with jax.enable_x64(False):
        dqn, dqr, dkv, dkr = run((q_nope, q_rope, kv, k_rope, g, lse, delta),
                                 H, causal, dims, interpret)
    return dqn, dqr, dkv, jnp.sum(dkr, axis=1).astype(k_rope.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_latent(q_nope, q_rope, kv, k_rope, num_heads, causal, blocks,
                  interpret):
    return _latent_fwd_impl(q_nope, q_rope, kv, k_rope, num_heads, causal,
                            blocks, interpret)[0]


def _fl_fwd(q_nope, q_rope, kv, k_rope, num_heads, causal, blocks, interpret):
    out, lse = _latent_fwd_impl(q_nope, q_rope, kv, k_rope, num_heads,
                                causal, blocks, interpret)
    return out, (q_nope, q_rope, kv, k_rope, out, lse)


def _fl_bwd(num_heads, causal, blocks, interpret, res, g):
    q_nope, q_rope, kv, k_rope, out, lse = res
    return _latent_bwd_impl(q_nope, q_rope, kv, k_rope, out, lse, g,
                            num_heads, causal, blocks, interpret)


_flash_latent.defvjp(_fl_fwd, _fl_bwd)


def flash_attention_latent(q_nope, q_rope, kv, k_rope, num_heads,
                           causal=True, blocks=None, interpret=False):
    """Blockwise exact latent attention. ``q_nope (B, S, H*nope)``;
    ``q_rope (B, S, H, rope)`` and ``k_rope (B, S, rope)``, both already
    rotated; ``kv (B, S, H*(nope + v))``, ``[k_nope_h | v_h]`` head by
    head. Returns ``(B, S, H*v)``. ``blocks = (blk_q, blk_k)`` overrides
    the block sizes (multiples of 128 that divide S; they need not be
    equal)."""
    out = _flash_latent(q_nope, jnp.transpose(q_rope, (0, 2, 1, 3)), kv,
                        k_rope, int(num_heads), bool(causal), blocks,
                        interpret)
    return out


# ====================================================================== eva
# The aggregation of EVA attention: the query at t sees the positions of ITS
# OWN window at or before t, and the chunk summaries of every EARLIER window,
# under one softmax. q, k, v (B, S, H*D) and the summaries kt, vt
# (B, S / chunk, H*D) stay as the projections and the pooling made them and
# are read as column blocks, D a multiple of 128. As in the latent family the
# key blocks are a GRID axis (sequential) and the accumulators live in
# scratch, so VMEM holds one block of each operand whatever the sequence;
# unlike it the axis covers only the tiles that can be live: first the
# summary blocks, then the blocks of the query's own window, whose index the
# index map works out from the query block's. A summary tile of windows not
# earlier than the query's, and a window tile above the diagonal, is skipped
# and its block index clamped to the nearest live one (nothing is fetched for
# it); a tile that straddles is masked. The tile bodies are the BHSD
# kernels' (`_fwd_tile_update`, `_bwd_tile_ds`, `_tile_dead`): the summaries'
# mask rides as their key-padding row. Backward: `flash_eva_bwd` over the
# forward's grid with the query blocks sequential gives dq and, from ONE pass
# over each window tile's scores, the positions' dk and dv (a window's
# accumulators in scratch); `flash_eva_dsum` gives the summaries' gradients,
# a summary block against the query blocks of every later window. The
# pooling, and its gradient, are XLA's (`ops/nn.py`).

# measured on the chip at 1 x 32,768 x 32 heads of 128, windows of 2,048,
# chunks of 16 (PR 31), forward / forward + backward, ms: 1024/1024/1024
# 19.6 / 51.9 (with `flash_eva_dq` + `_dkv` in place of the one `_bwd`:
# 57.4), 1024/1024/512 22.9 / 53.5 (58.9), 2048/1024/1024 22.3 / 57.1,
# 2048/1024/512 24.7 / 56.9, 1024/512/512 29.3 / 60.4, 512/512/512 32.4 /
# 65.7, 2048/2048/1024 56.0 / 90.9, 2048/2048/512 80.7 / 112.6
_PREF_EVA = (1024, 1024, 1024)      # queries, positions, summaries a block


def flash_attention_eva_usable(seq, head_dim, window, chunk):
    """Whether the EVA kernels take this problem."""
    return (head_dim % 128 == 0 and window % 128 == 0 and window % chunk == 0
            and seq % window == 0 and (seq // chunk) % 128 == 0)


def _pick_blocks_eva(seq, window, chunk):
    """(blk_q, blk_k, blk_s): the largest multiples of 128 up to the
    preferred sizes that divide the window (queries, positions: a query
    block lies in one window) and the number of summaries."""
    def pick(pref, whole):
        b = max(128, min(pref // 128 * 128, whole))
        while whole % b:
            b -= 128
        return b
    pq, pk, ps = _PREF_EVA
    return pick(pq, window), pick(pk, window), pick(ps, seq // chunk)


class _EvaPlan:
    """The shapes and block arithmetic the EVA kernels share. ``n_s``
    summary blocks of ``blk_s``, ``n_w`` position blocks of ``blk_k`` a
    window, ``per_window`` summaries a window."""

    def __init__(self, q, kt, num_heads, window, blocks):
        self.B, self.S, HD = q.shape
        self.H, self.W = num_heads, window
        self.D = HD // num_heads
        self.n_sum = kt.shape[1]
        self.per_window = self.n_sum // (self.S // window)
        chunk = self.S // self.n_sum
        self.blk_q, self.blk_k, self.blk_s = \
            blocks or _pick_blocks_eva(self.S, window, chunk)
        self.n_q = self.S // self.blk_q
        self.n_s, self.n_w = self.n_sum // self.blk_s, window // self.blk_k
        self.scale = float(1.0 / np.sqrt(self.D))

    # -- which tiles are live, from block indices (traced or plain ints)
    def live_summaries(self, i):
        """Summaries the queries of block ``i`` see: those of the windows
        before theirs."""
        return (i * self.blk_q) // self.W * self.per_window

    def last_summary_block(self, i):
        return jnp.maximum(-(-self.live_summaries(i) // self.blk_s) - 1, 0)

    def first_position_block(self, i):
        """Index, among all position blocks, of the first of block ``i``'s
        window."""
        return (i * self.blk_q) // self.W * self.n_w

    def last_position_step(self, i):
        """The last live step ``jj`` among the window's position blocks:
        the one that holds the query block's last position."""
        return ((i * self.blk_q) % self.W + self.blk_q - 1) // self.blk_k

    def first_later_query_block(self, j):
        """First query block that sees summary block ``j``: the first of
        the window after the one its first summary belongs to."""
        return ((j * self.blk_s) // self.per_window + 1) \
            * (self.W // self.blk_q)

    # -- the liveness the kernels branch on and :meth:`tiles` counts
    def summary_step_live(self, i, j):
        """Step ``j`` of query block ``i``'s sweep is a summary tile that
        holds a summary the block sees."""
        return (j < self.n_s) & (j * self.blk_s < self.live_summaries(i))

    def position_step_live(self, i, j):
        """Step ``j`` is a tile of the block's own window that does not lie
        wholly above the diagonal."""
        return (j >= self.n_s) & (j - self.n_s <= self.last_position_step(i))

    def seen_by_query_block(self, j, i):
        """Summary block ``j`` holds a summary that query block ``i`` sees
        (the dsum kernel's grid: summary blocks by query blocks)."""
        return i >= self.first_later_query_block(j)

    def tiles(self):
        """``(stepped, live)`` key tiles of one (row, head) over the three
        kernels' grids, by the predicates the kernels branch on."""
        sweep = [bool(self.summary_step_live(i, j))
                 or bool(self.position_step_live(i, j))
                 for i in range(self.n_q) for j in range(self.n_s + self.n_w)]
        dsum = [bool(self.seen_by_query_block(j, i))
                for j in range(self.n_s) for i in range(self.n_q)]
        # the forward's and the backward's grids are the same sweep
        return 2 * len(sweep) + len(dsum), 2 * sum(sweep) + sum(dsum)


# Key tiles the grids of the EVA kernels traced so far step through, and
# those of them that hold a live pair (counted where the planner decides,
# once a trace, for one (row, head) of each call, forward and backward).
_EVA_TILES = {"stepped": 0, "live": 0}


def eva_tile_stats():
    """:func:`flash_backward_stats` for the EVA family's grids."""
    return dict(_EVA_TILES)


def _summary_keep_row(s0, blk_s, seen):
    """(1, blk_s) keep-row of a summary tile: 1 where the summary's index is
    below ``seen``."""
    idx = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, blk_s), 1)
    return (idx < seen).astype(jnp.int32)


def _eva_fwd_kernel(q_ref, k_ref, v_ref, kt_ref, vt_ref, o_ref, lse_ref,
                    acc_ref, m_ref, l_ref, *, plan):
    """One (batch, head, q-block, key step) program: an online-softmax step
    into the scratch accumulators over a summary tile (steps below ``n_s``)
    or a tile of the query's own window; the last step writes the output
    and the log-sum-exp."""
    p = plan
    i, j = pl.program_id(2), pl.program_id(3)
    q0 = i * p.blk_q

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def step(k, v, dead, k0, blk_k):
        carry = (acc_ref[...], m_ref[0, :], l_ref[0, :])
        _, (acc, m_i, l_i) = _fwd_tile_update(
            q_ref[0], k, v, carry, dead, None, None, q0, k0, p.blk_q, blk_k,
            0.0, p.scale)
        acc_ref[...] = acc
        m_ref[0, :] = m_i
        l_ref[0, :] = l_i

    # a live tile is either whole (no mask) or straddles the edge of what
    # the block sees (the summaries' end, the diagonal)
    seen, s0 = p.live_summaries(i), j * p.blk_s
    live, whole = p.summary_step_live(i, j), s0 + p.blk_s <= seen

    @pl.when(jnp.logical_and(live, whole))
    def _():
        step(kt_ref[0], vt_ref[0], None, s0, p.blk_s)

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _():
        row = _summary_keep_row(s0, p.blk_s, seen)
        step(kt_ref[0], vt_ref[0],
             _tile_dead(False, q0, s0, p.blk_q, p.blk_s, row), s0, p.blk_s)

    k0 = q0 // p.W * p.W + (j - p.n_s) * p.blk_k
    live, whole = p.position_step_live(i, j), k0 + (p.blk_k - 1) <= q0

    @pl.when(jnp.logical_and(live, whole))
    def _():
        step(k_ref[0], v_ref[0], None, k0, p.blk_k)

    @pl.when(jnp.logical_and(live, jnp.logical_not(whole)))
    def _():
        step(k_ref[0], v_ref[0],
             _tile_dead(True, q0, k0, p.blk_q, p.blk_k, None), k0, p.blk_k)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l_safe = jnp.maximum(l_ref[0, :], jnp.float32(1e-20))
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, :] = m_ref[0, :] + jnp.log(l_safe)


def _eva_bwd_kernel(q_ref, k_ref, v_ref, kt_ref, vt_ref, do_ref, lse_ref,
                    delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                    *, plan):
    """grads wrt Q and wrt the POSITIONS' keys and values in one pass over
    the forward's grid, the query blocks sequential: dQ = dS K * scale over
    the live summary tiles and the live tiles of the query's own window; a
    window's dK and dV accumulate in scratch over the query blocks of that
    window (its tiles' scores are computed once, five MXU passes a tile
    where a dq and a dkv kernel make seven: 14% of a layer's backward on
    the chip) and are written when its last query block ends. VMEM holds a window's accumulators whatever
    the sequence."""
    p = plan
    i, j = pl.program_id(2), pl.program_id(3)
    q0 = i * p.blk_q
    per = p.W // p.blk_q                    # query blocks a window
    last = j == pl.num_programs(3) - 1

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(jnp.logical_and(j == 0, i % per == 0))
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    seen, s0 = p.live_summaries(i), j * p.blk_s

    @pl.when(p.summary_step_live(i, j))
    def _():
        kt = kt_ref[0]
        ds, _ = _bwd_tile_ds(
            q_ref[0], kt, vt_ref[0], do_ref[0], lse_ref[0, 0, :],
            delta_ref[0, 0, :], _summary_keep_row(s0, p.blk_s, seen), False,
            0.0, p.scale, None, None, q0, s0, p.blk_q, p.blk_s)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(kt.dtype), kt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    off = (j - p.n_s) * p.blk_k             # the tile's place in the window
    k0 = q0 // p.W * p.W + off

    @pl.when(p.position_step_live(i, j))
    def _():
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        ds, pd = _bwd_tile_ds(
            q, k, v_ref[0], do, lse_ref[0, 0, :], delta_ref[0, 0, :], None,
            True, 0.0, p.scale, None, None, q0, k0, p.blk_q, p.blk_k)
        ds = ds.astype(q.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k.astype(q.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(off, p.blk_k), p.blk_k)
        over_q = (((0,), (0,)), ((), ()))
        dk_acc[rows, :] += jax.lax.dot_general(
            ds, q, over_q, preferred_element_type=jnp.float32)
        dv_acc[rows, :] += jax.lax.dot_general(
            pd.astype(q.dtype), do.astype(q.dtype), over_q,
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[0] = (dq_acc[...] * jnp.float32(p.scale)).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(last, i % per == per - 1))
    def _():
        dk_ref[0] = (dk_acc[...] * jnp.float32(p.scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _eva_dsum_kernel(q_ref, kt_ref, vt_ref, do_ref, lse_ref, delta_ref,
                     dkt_ref, dvt_ref, dk_acc, dv_acc, *, plan):
    """grads wrt one block of chunk summaries on a (batch, head, summary
    block, query block) grid: dV~ = P^T dO, dK~ = dS^T Q * scale, summed over
    the query blocks of every window after the one the block's first summary
    belongs to."""
    p = plan
    sj, qi = pl.program_id(2), pl.program_id(3)
    s0, q0 = sj * p.blk_s, qi * p.blk_q

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(p.seen_by_query_block(sj, qi))
    def _():
        q, do = q_ref[0], do_ref[0]
        ds, pd = _bwd_tile_ds(
            q, kt_ref[0], vt_ref[0], do, lse_ref[0, 0, :], delta_ref[0, 0, :],
            _summary_keep_row(s0, p.blk_s, p.live_summaries(qi)), False, 0.0,
            p.scale, None, None, q0, s0, p.blk_q, p.blk_s)
        over_q = (((0,), (0,)), ((), ()))
        dv_acc[...] += jax.lax.dot_general(
            pd.astype(do.dtype), do.astype(q.dtype), over_q,
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, over_q,
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dkt_ref[0] = (dk_acc[...] * jnp.float32(p.scale)).astype(
            dkt_ref.dtype)
        dvt_ref[0] = dv_acc[...].astype(dvt_ref.dtype)


def _eva_specs(p):
    """Block specs on the forward's (batch, head, q-block, key step) grid:
    the summary block stands still once the steps reach the window's
    positions, the position block while they are among the summaries, and
    a dead step takes the index of the nearest live one."""
    def summary_block(i, j):
        return jnp.minimum(j, p.last_summary_block(i))

    def position_block(i, j):
        jj = jnp.clip(j - p.n_s, 0, p.last_position_step(i))
        return p.first_position_block(i) + jj

    D = p.D
    return {
        "q": pl.BlockSpec((1, p.blk_q, D), lambda b, h, i, j: (b, i, h)),
        "row": pl.BlockSpec((1, 1, p.blk_q),
                            lambda b, h, i, j: (b * p.H + h, 0, i)),
        "k": pl.BlockSpec((1, p.blk_k, D), lambda b, h, i, j: (
            b, position_block(i, j), h)),
        "kt": pl.BlockSpec((1, p.blk_s, D), lambda b, h, i, j: (
            b, summary_block(i, j), h)),
    }


def _eva_summary_specs(p):
    """Block specs on :func:`_eva_dsum_kernel`'s (batch, head, summary
    block, query block) grid; a dead step takes the first live query
    block's index."""
    def q_block(sj, qi):
        return jnp.minimum(jnp.maximum(qi, p.first_later_query_block(sj)),
                           p.n_q - 1)

    return {
        "q": pl.BlockSpec((1, p.blk_q, p.D), lambda b, h, sj, qi: (
            b, q_block(sj, qi), h)),
        "row": pl.BlockSpec((1, 1, p.blk_q), lambda b, h, sj, qi: (
            b * p.H + h, 0, q_block(sj, qi))),
        "kt": pl.BlockSpec((1, p.blk_s, p.D),
                           lambda b, h, sj, qi: (b, sj, h)),
    }


# VMEM an EVA program may plan for (blocks and tiles only, whatever the
# sequence): room for tiles past Mosaic's default 16 MiB, of the chip's 128
_EVA_VMEM_LIMIT = 64 * 1024 * 1024


def _eva_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
              interpret, outer="parallel"):
    """``outer`` is the outer block axis's semantics."""
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", outer, "arbitrary"),
            vmem_limit_bytes=_EVA_VMEM_LIMIT),
        interpret=interpret, name=name)


def _eva_fwd_impl(q, k, v, kt, vt, num_heads, window, blocks, interpret):
    p = _EvaPlan(q, kt, num_heads, window, blocks)
    spec = _eva_specs(p)
    call = _eva_call(
        functools.partial(_eva_fwd_kernel, plan=p), "flash_eva_fwd",
        (p.B, p.H, p.n_q, p.n_s + p.n_w),
        [spec["q"], spec["k"], spec["k"], spec["kt"], spec["kt"]],
        (spec["q"], spec["row"]),
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((p.B * p.H, 1, p.S), jnp.float32)),
        [(p.blk_q, p.D), (1, p.blk_q), (1, p.blk_q)], interpret)
    with jax.enable_x64(False):
        return call(q, k, v, kt, vt)


def _eva_bwd_impl(q, k, v, kt, vt, o, lse, g, num_heads, window, blocks,
                  interpret):
    """``(dq, dk, dv, dkt, dvt)``: dq and the positions' gradients over the
    forward's grid, then the summaries' over the query blocks that see
    them."""
    p = _EvaPlan(q, kt, num_heads, window, blocks)
    stepped, live = p.tiles()
    _EVA_TILES["stepped"] += stepped
    _EVA_TILES["live"] += live
    # delta_i = rowsum(dO o O) per head: one fused XLA elementwise + reduce
    delta = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(p.B, p.S, p.H, p.D), axis=-1)
    delta = jnp.transpose(delta, (0, 2, 1)).reshape(p.B * p.H, 1, p.S)
    spec, by_s = _eva_specs(p), _eva_summary_specs(p)
    like = jax.ShapeDtypeStruct(q.shape, q.dtype)
    window_block = pl.BlockSpec((1, p.W, p.D), lambda b, h, i, j: (
        b, (i * p.blk_q) // p.W, h))
    bwd_call = _eva_call(
        functools.partial(_eva_bwd_kernel, plan=p), "flash_eva_bwd",
        (p.B, p.H, p.n_q, p.n_s + p.n_w),
        [spec["q"], spec["k"], spec["k"], spec["kt"], spec["kt"], spec["q"],
         spec["row"], spec["row"]],
        (spec["q"], window_block, window_block), (like,) * 3,
        [(p.blk_q, p.D), (p.W, p.D), (p.W, p.D)], interpret,
        outer="arbitrary")
    dsum_call = _eva_call(
        functools.partial(_eva_dsum_kernel, plan=p), "flash_eva_dsum",
        (p.B, p.H, p.n_s, p.n_q),
        [by_s["q"], by_s["kt"], by_s["kt"], by_s["q"], by_s["row"],
         by_s["row"]], (by_s["kt"], by_s["kt"]),
        (jax.ShapeDtypeStruct(kt.shape, kt.dtype),) * 2,
        [(p.blk_s, p.D), (p.blk_s, p.D)], interpret)
    with jax.enable_x64(False):
        dq, dk, dv = bwd_call(q, k, v, kt, vt, g, lse, delta)
        dkt, dvt = dsum_call(q, kt, vt, g, lse, delta)
    return dq, dk, dv, dkt, dvt


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_eva(q, k, v, kt, vt, num_heads, window, blocks, interpret):
    return _eva_fwd_impl(q, k, v, kt, vt, num_heads, window, blocks,
                         interpret)[0]


def _fe_fwd(q, k, v, kt, vt, num_heads, window, blocks, interpret):
    from jax.ad_checkpoint import checkpoint_name
    out, lse = _eva_fwd_impl(q, k, v, kt, vt, num_heads, window, blocks,
                             interpret)
    # named, so that a caller that recomputes its forward in the backward
    # pass can keep these two and spare this kernel its second run
    out = checkpoint_name(out, "eva_attention_out")
    lse = checkpoint_name(lse, "eva_attention_lse")
    return out, (q, k, v, kt, vt, out, lse)


def _fe_bwd(num_heads, window, blocks, interpret, res, g):
    q, k, v, kt, vt, out, lse = res
    return _eva_bwd_impl(q, k, v, kt, vt, out, lse, g, num_heads, window,
                         blocks, interpret)


_flash_eva.defvjp(_fe_fwd, _fe_bwd)


def flash_attention_eva(q, k, v, kt, vt, num_heads, window, blocks=None,
                        interpret=False):
    """Blockwise exact aggregation of EVA attention. ``q``, ``k``, ``v``
    (B, S, H*D), q and k already rotated; ``kt``, ``vt`` (B, S / chunk, H*D)
    the chunk summaries. Returns (B, S, H*D). ``blocks = (blk_q, blk_k,
    blk_s)`` overrides the block sizes (multiples of 128; queries and
    positions divide the window, summaries their number)."""
    return _flash_eva(q, k, v, kt, vt, int(num_heads), int(window), blocks,
                      interpret)


# ================================================================== grouped
# Causal grouped-query attention over STREAMED key blocks. q (B, H, S, D) and
# k, v (B, KV, S, D), heads before positions (the op transposes: one pass
# each way over arrays the kernels then read many times). As in the latent
# family the key blocks are a sequential grid axis and the accumulators live
# in scratch, so VMEM holds one block of each operand whatever the sequence.
# The G = H / KV query heads of a group are STACKED along the rows of one
# tile, (G * blk_q, D) against their one (blk_k, D) key-value block: a key
# block is fetched once a group, and dk and dv, contracted over the stacked
# rows, come out summed over the group. A row's position is its index modulo
# blk_q. The tile bodies are the BHSD kernels' (`_fwd_tile_update`,
# `_bwd_tile_ds`), handed the stacked tile's own causal mask; a tile above
# the diagonal is skipped and its block index clamped to the nearest live
# one. Backward: `flash_grouped_dq` over the forward's grid, then
# `flash_grouped_dkv` with the query blocks sequential.

# measured on the chip at 1 x 32,768 x 32 / 8 heads of 64 (PR 33), forward /
# forward + backward, ms, queries a head x keys a block: 256/256 338.7 /
# 596.6, 128/512 202.8 / 426.4, 256/512 173.8 / 373.8, 512/512 158.4 / 342.0,
# 128/1024 118.0 / 317.3, 256/1024 107.9 / 297.6, 512/1024 106.7 / 293.3,
# 128/2048 92.1 / 282.3, 256/2048 86.7 / 274.2, 512/2048 87.2 / 272.3 (the XLA
# form, 512 queries at a time: 627.9 / 2,041.3)
_GROUPED_ROWS = 1024        # stacked query rows a tile (G * blk_q)
_PREF_GROUPED_K = 2048
_GROUPED_VMEM_LIMIT = 64 * 1024 * 1024


def flash_attention_grouped_usable(seq, head_dim, heads, kv_heads):
    """Whether the grouped kernels take this problem."""
    return (seq % 128 == 0 and seq >= 128 and heads % kv_heads == 0
            and head_dim % 8 == 0 and head_dim <= 256)


def _pick_blocks_grouped(seq, group):
    """(blk_q, blk_k): the largest multiples of 128 that divide ``seq``, up
    to ``_GROUPED_ROWS / group`` queries and ``_PREF_GROUPED_K`` keys."""
    def pick(pref):
        b = max(128, min(pref // 128 * 128, seq))
        while seq % b:
            b -= 128
        return b
    return pick(_GROUPED_ROWS // group), pick(_PREF_GROUPED_K)


def _grouped_dead(q0, k0, group, blk_q, blk_k):
    """The causal mask of a stacked tile: row r holds position ``q0 + r %
    blk_q`` of query head ``r // blk_q`` of the group."""
    q_pos = q0 + jax.lax.broadcasted_iota(
        jnp.int32, (group, blk_q, blk_k), 1).reshape(group * blk_q, blk_k)
    k_pos = k0 + jax.lax.broadcasted_iota(
        jnp.int32, (group * blk_q, blk_k), 1)
    return q_pos < k_pos


def _grouped_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                        l_ref, *, scale, group, blk_q, blk_k):
    """One (batch, kv head, q-block, k-block) program: one online-softmax
    step of the group's stacked rows into the scratch accumulators; the last
    k-block writes the output and the log-sum-exp."""
    kj = pl.program_id(3)
    q0, k0 = pl.program_id(2) * blk_q, kj * blk_k
    rows, d = group * blk_q, q_ref.shape[-1]

    @pl.when(kj == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def step(dead):
        carry = (acc_ref[...], m_ref[0, :], l_ref[0, :])
        _, (acc, m_i, l_i) = _fwd_tile_update(
            q_ref[0].reshape(rows, d), k_ref[0, 0], v_ref[0, 0], carry, dead,
            None, None, q0, k0, rows, blk_k, 0.0, scale)
        acc_ref[...] = acc
        m_ref[0, :] = m_i
        l_ref[0, :] = l_i

    live, whole = _latent_live(True, q0, k0, blk_q), k0 + (blk_k - 1) <= q0
    pl.when(jnp.logical_and(live, whole))(lambda: step(None))
    pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
        lambda: step(_grouped_dead(q0, k0, group, blk_q, blk_k)))

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        l_safe = jnp.maximum(l_ref[0, :], jnp.float32(1e-20))
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).reshape(
            group, blk_q, d).astype(o_ref.dtype)
        lse_ref[0, 0, :] = m_ref[0, :] + jnp.log(l_safe)


def _grouped_tile_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q0, k0,
                     scale, group, blk_q, blk_k):
    """``(ds, p, q, do)`` of one stacked tile, through `_bwd_tile_ds`."""
    rows, d = group * blk_q, q_ref.shape[-1]
    q, do = q_ref[0].reshape(rows, d), do_ref[0].reshape(rows, d)
    ds, pd = _bwd_tile_ds(
        q, k_ref[0, 0], v_ref[0, 0], do, lse_ref[0, 0, :], delta_ref[0, 0, :],
        None, False, 0.0, scale, None, None, q0, k0, rows, blk_k,
        dead=_grouped_dead(q0, k0, group, blk_q, blk_k))
    return ds, pd, q, do


def _grouped_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, dq_acc, *, scale, group, blk_q, blk_k):
    """grad wrt the group's Q: one (batch, kv head, q-block, k-block)
    program; dQ = dS K * scale."""
    kj = pl.program_id(3)
    q0, k0 = pl.program_id(2) * blk_q, kj * blk_k

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(_latent_live(True, q0, k0, blk_q))
    def _():
        ds, _, q, _ = _grouped_tile_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q0, k0, scale,
            group, blk_q, blk_k)
        k = k_ref[0, 0].astype(q.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * jnp.float32(scale)).reshape(
            group, blk_q, dq_ref.shape[-1]).astype(dq_ref.dtype)


def _grouped_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, scale, group,
                        blk_q, blk_k):
    """grads wrt one key-value head's K and V, summed over the group's query
    heads by the contraction over the stacked rows: one (batch, kv head,
    k-block, q-block) program. dV = P^T dO; dK = dS^T Q * scale."""
    qi = pl.program_id(3)
    k0, q0 = pl.program_id(2) * blk_k, qi * blk_q

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(_latent_live(True, q0, k0, blk_q))
    def _():
        ds, pd, q, do = _grouped_tile_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q0, k0, scale,
            group, blk_q, blk_k)
        over_q = (((0,), (0,)), ((), ()))
        dv_acc[...] += jax.lax.dot_general(
            pd.astype(q.dtype), do.astype(q.dtype), over_q,
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, over_q, preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = (dk_acc[...] * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _grouped_specs(KV, group, D, n_q, blk_q, blk_k, q_inner):
    """Block specs of the grouped kernels' operands on a (batch, kv head,
    outer block, inner block) grid (`_latent_specs`' clamping: a skipped
    tile takes the block index of the nearest live one). A row vector (the
    log-sum-exp, delta) of a (batch, kv head, q-block) is the group's stacked
    rows, (1, 1, group * blk_q)."""
    if q_inner:
        def qk(j, i):
            return jnp.maximum(i, (j * blk_k) // blk_q), j
    else:
        def qk(i, j):
            return i, jnp.minimum(j, (i * blk_q + blk_q - 1) // blk_k)

    def q_side(spec):
        return lambda b, g, x, y: spec(b, g, qk(x, y)[0])

    def k_side(spec):
        return lambda b, g, x, y: spec(b, g, qk(x, y)[1])

    return {
        "q": pl.BlockSpec((1, group, blk_q, D),
                          q_side(lambda b, g, i: (b, g, i, 0))),
        "kv": pl.BlockSpec((1, 1, blk_k, D),
                           k_side(lambda b, g, j: (b, g, j, 0))),
        "row": pl.BlockSpec(
            (1, 1, group * blk_q),
            q_side(lambda b, g, i: ((b * KV + g) * n_q + i, 0, 0))),
    }


def _grouped_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
                  interpret):
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_GROUPED_VMEM_LIMIT),
        interpret=interpret, name=name)


def _grouped_dims(q, k, blocks):
    B, H, S, D = q.shape
    KV = k.shape[1]
    group = H // KV
    blk_q, blk_k = blocks or _pick_blocks_grouped(S, group)
    return B, H, KV, group, S, D, blk_q, blk_k


def _grouped_fwd_impl(q, k, v, blocks, interpret):
    B, H, KV, group, S, D, blk_q, blk_k = _grouped_dims(q, k, blocks)
    n_q, rows = S // blk_q, group * blk_q
    spec = _grouped_specs(KV, group, D, n_q, blk_q, blk_k, False)
    kernel = functools.partial(
        _grouped_fwd_kernel, scale=float(1.0 / np.sqrt(D)), group=group,
        blk_q=blk_q, blk_k=blk_k)
    call = _grouped_call(
        kernel, "flash_grouped_fwd", (B, KV, n_q, S // blk_k),
        [spec["q"], spec["kv"], spec["kv"]], (spec["q"], spec["row"]),
        (jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((B * KV * n_q, 1, rows), jnp.float32)),
        [(rows, D), (1, rows), (1, rows)], interpret)
    with jax.enable_x64(False):
        return call(q, k, v)


def _grouped_bwd_impl(q, k, v, o, lse, g, blocks, interpret):
    B, H, KV, group, S, D, blk_q, blk_k = _grouped_dims(q, k, blocks)
    n_q, rows = S // blk_q, group * blk_q
    common = dict(scale=float(1.0 / np.sqrt(D)), group=group, blk_q=blk_q,
                  blk_k=blk_k)
    # delta_i = rowsum(dO o O), laid out as the log-sum-exp is: a group's
    # stacked rows a (batch, kv head, q-block)
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.transpose(delta.reshape(B, KV, group, n_q, blk_q),
                          (0, 1, 3, 2, 4)).reshape(B * KV * n_q, 1, rows)
    by_k = _grouped_specs(KV, group, D, n_q, blk_q, blk_k, False)
    by_q = _grouped_specs(KV, group, D, n_q, blk_q, blk_k, True)

    def ins(s):
        return [s["q"], s["kv"], s["kv"], s["q"], s["row"], s["row"]]

    dq_call = _grouped_call(
        functools.partial(_grouped_dq_kernel, **common), "flash_grouped_dq",
        (B, KV, n_q, S // blk_k), ins(by_k), by_k["q"],
        jax.ShapeDtypeStruct(q.shape, q.dtype), [(rows, D)], interpret)
    dkv_call = _grouped_call(
        functools.partial(_grouped_dkv_kernel, **common), "flash_grouped_dkv",
        (B, KV, S // blk_k, n_q), ins(by_q), (by_q["kv"], by_q["kv"]),
        (jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)),
        [(blk_k, D), (blk_k, D)], interpret)
    operands = (q, k, v, g, lse, delta)
    with jax.enable_x64(False):
        return (dq_call(*operands),) + tuple(dkv_call(*operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_grouped(q, k, v, blocks, interpret):
    return _grouped_fwd_impl(q, k, v, blocks, interpret)[0]


def _fg_fwd(q, k, v, blocks, interpret):
    from jax.ad_checkpoint import checkpoint_name
    out, lse = _grouped_fwd_impl(q, k, v, blocks, interpret)
    # named, so that a caller that recomputes its forward in the backward
    # pass can keep these two and spare this kernel its second run
    out = checkpoint_name(out, "grouped_attention_out")
    lse = checkpoint_name(lse, "grouped_attention_lse")
    return out, (q, k, v, out, lse)


def _fg_bwd(blocks, interpret, res, g):
    q, k, v, out, lse = res
    return _grouped_bwd_impl(q, k, v, out, lse, g, blocks, interpret)


_flash_grouped.defvjp(_fg_fwd, _fg_bwd)


def flash_attention_grouped(q, k, v, num_heads, num_kv_heads, blocks=None,
                            interpret=False):
    """Blockwise exact causal grouped-query attention with the 1/sqrt(D)
    scale. ``q (B, S, H*D)``, ``k`` and ``v (B, S, KV*D)`` as the projections
    made them, query head j reading key-value head ``j // (H / KV)``; returns
    ``(B, S, H*D)``. ``blocks = (blk_q, blk_k)`` overrides the block sizes
    (multiples of 128 that divide S)."""
    B, S, _ = q.shape
    H, KV = int(num_heads), int(num_kv_heads)

    def heads_first(a, n):
        return jnp.transpose(a.reshape(B, S, n, -1), (0, 2, 1, 3))

    out = _flash_grouped(heads_first(q, H), heads_first(k, KV),
                         heads_first(v, KV), blocks, interpret)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(q.shape)


# ====================================================================== ssd
# The chunked scan of a Mamba-2 state-space layer (Dao and Gu 2024), one
# group of B and C, heads of 64. x (B, S, H*64) stays as the convolution made
# it and is read as column blocks; b, c (B, S, N) are shared by the heads; dt
# (the step after its softplus) and cs (the log-decay summed over each
# chunk's positions, which XLA makes in float32: a product on the MXU would
# round it) come in TWO layouts, (B, H / hb, S, hb) for a head's column over
# the chunk's positions and (B, H, S) for its row, because a tile needs
# both. The grid is (batch, block of hb heads, chunk) with the chunk axis
# SEQUENTIAL: the states of the block's heads, (hb * 64, N) float32, ride in
# VMEM scratch from chunk to chunk, as `flash_latent_bwd`'s dq rides along
# its key axis. Inside a step: G = C B^T once (shared by the heads), then for
# each head M = G o L o dt with L[i, j] = exp(cs_i - cs_j) for j <= i, Y = M
# X + (exp(cs) o C) S_prev^T, and S = exp(cs_last) S_prev + X^T (exp(cs_last
# - cs) o dt o B). Heads of 64 on 128 lanes: two heads share a 128-column
# slab of x; each head's products run on the whole slab and a lane mask
# picks its half (a 64-wide product costs the MXU a 128-wide one anyway), so
# nothing is sliced off the lane tiling. Every decay, and the sums that make
# the gradients of dt and cs, in float32; the products in x's type with
# float32 accumulation. The forward can also write each chunk's START state,
# (B, S / Q, H * 64, N) float32: the backward reads it and is then one
# kernel over the chunks in REVERSE with the state's gradient in scratch.
# KEPT, not recomputed: 268 MB a layer at 1 x 32,768 x 64 heads x 128, alive
# from the layer's (recomputed) forward to its backward only. Measured on the
# chip there (PR 33), forward / forward + backward, ms: 16 heads a step 2.95 /
# 14.49, 8 heads 3.02 / 14.91, 32 heads 2.97 / 14.25; the XLA form 10.32 /
# 23.16.

_SSD_HEADS = 16             # heads a grid step
_SSD_VMEM_LIMIT = 64 * 1024 * 1024


def ssm_scan_usable(seq, heads, head_dim, state, chunk):
    """Whether the chunked-scan kernels take this problem."""
    return (head_dim == 64 and heads % 2 == 0 and state % 128 == 0
            and chunk % 128 == 0 and seq % chunk == 0)


def _ssd_heads_block(heads):
    """Heads a grid step: the largest divisor of ``heads`` up to
    ``_SSD_HEADS`` that is a multiple of 8 (the row layout's sublane tile),
    else all of them."""
    for hb in range(min(_SSD_HEADS, heads) // 8 * 8, 0, -8):
        if heads % hb == 0:
            return hb
    return heads


_NT = (((1,), (1,)), ((), ()))      # a b^T
_NN = (((1,), (0,)), ((), ()))      # a b
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _f32_dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _ssd_last(cs_r):
    """(1, 1): the row's last entry, the log of the chunk's whole decay (a
    masked sum: Mosaic does not broadcast a (1, 1) slice off lane Q - 1)."""
    at = jax.lax.broadcasted_iota(jnp.int32, cs_r.shape, 1)
    return jnp.sum(jnp.where(at == cs_r.shape[1] - 1, cs_r, 0.0), axis=1,
                   keepdims=True)


def _ssd_tile(cs_c, cs_r, tri):
    """``L[i, j] = exp(cs_i - cs_j)`` for j <= i, nought above."""
    return jnp.exp(jnp.where(tri, cs_c - cs_r, jnp.float32(NEG_INF)))


def _ssd_fwd_kernel(x_ref, dtc_ref, csc_ref, dtr_ref, csr_ref, b_ref, c_ref,
                    y_ref, *rest, hb, keep_states):
    """One (batch, head block, chunk) program of the forward; ``state`` is
    (hb / 2, 128, N): a pair of heads' states stacked, pair by pair."""
    s0_ref, state = rest if keep_states else (None,) + rest
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    if keep_states:
        s0_ref[0, 0] = state[...].reshape(s0_ref.shape[2:])
    bm, cm = b_ref[0], c_ref[0]
    bf, cf = bm.astype(jnp.float32), cm.astype(jnp.float32)
    pairs = _f32_dot(cm, bm, _NT)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    low = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) < 64
    dtc, csc = dtc_ref[0, 0], csc_ref[0, 0]
    for p in range(hb // 2):
        cols = slice(p * 128, (p + 1) * 128)
        xp = x_ref[0, :, cols]
        prev = state[p]
        prev_x = prev.astype(xp.dtype)
        halves = []
        for s in range(2):
            h = 2 * p + s
            cs_c, dt_c = csc[:, h:h + 1], dtc[:, h:h + 1]
            cs_r, dt_r = csr_ref[0, h:h + 1, :], dtr_ref[0, h:h + 1, :]
            inside = (pairs * _ssd_tile(cs_c, cs_r, tri) * dt_r).astype(
                xp.dtype)
            from_start = (cf * jnp.exp(cs_c)).astype(xp.dtype)
            halves.append(_f32_dot(inside, xp, _NN)
                          + _f32_dot(from_start, prev_x, _NT))
            cs_last = _ssd_last(cs_r)
            to_end = (bf * (jnp.exp(cs_last - cs_c) * dt_c)).astype(xp.dtype)
            rows = slice(s * 64, s * 64 + 64)
            state[p, rows, :] = jnp.exp(cs_last) * prev[rows, :] \
                + _f32_dot(xp, to_end, _TN)[rows, :]
        y_ref[0, :, cols] = jnp.where(low, halves[0], halves[1]).astype(
            y_ref.dtype)


def _ssd_bwd_kernel(x_ref, dy_ref, dtc_ref, csc_ref, dtr_ref, csr_ref, b_ref,
                    c_ref, s0_ref, dx_ref, ddtc_ref, dcsc_ref, ddtr_ref,
                    dcsr_ref, db_ref, dc_ref, dstate, dye, xw, *, hb):
    """One (batch, head block, chunk) program of the backward, the chunks
    in reverse: ``dstate`` is the gradient of the chunk's END state on the
    way in and of its START state on the way out. Besides dx: the gradients
    of dt and cs in both layouts (a tile's row sums are a column, its column
    sums a row; XLA adds the two), and this head block's share of db and dc
    (float32; XLA sums the blocks). ``dye`` = dy scaled by the decay from the
    chunk's start and ``xw`` = x scaled by dt and the decay to its end, head
    by head, are built in scratch so that the products that run over ALL the
    block's heads (the states' and b's and c's) are one matmul each."""
    Q = x_ref.shape[1]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros(dstate.shape, f32)

    bm, cm = b_ref[0], c_ref[0]
    bf, cf = bm.astype(f32), cm.astype(f32)
    pairs = _f32_dot(cm, bm, _NT)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    low = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1) < 64
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    lane_h = jax.lax.broadcasted_iota(jnp.int32, (Q, hb), 1)
    row_h = jax.lax.broadcasted_iota(jnp.int32, (hb, Q), 0)
    dtc, csc = dtc_ref[0, 0], csc_ref[0, 0]
    end_grad = dstate[...].reshape(hb * 64, -1).astype(x_ref.dtype)
    d_pairs = jnp.zeros((Q, Q), f32)
    ddt_c = jnp.zeros((Q, hb), f32)
    dcs_c = jnp.zeros((Q, hb), f32)
    ddt_r = jnp.zeros((hb, Q), f32)
    dcs_r = jnp.zeros((hb, Q), f32)

    def total(a):       # (1, 1)
        return jnp.sum(jnp.sum(a, axis=1, keepdims=True), axis=0,
                       keepdims=True)

    for p in range(hb // 2):
        cols = slice(p * 128, (p + 1) * 128)
        xp, dyp = x_ref[0, :, cols], dy_ref[0, :, cols]
        xf, dyf = xp.astype(f32), dyp.astype(f32)
        start = s0_ref[0, 0, cols, :]
        start_x = start.astype(xp.dtype)
        grad = dstate[p]
        grad_x = grad.astype(xp.dtype)
        dxs, dyes, xws = [], [], []
        for s in range(2):
            h = 2 * p + s
            mine = low if s == 0 else jnp.logical_not(low)
            rows = slice(s * 64, s * 64 + 64)
            cs_c, dt_c = csc[:, h:h + 1], dtc[:, h:h + 1]
            cs_r, dt_r = csr_ref[0, h:h + 1, :], dtr_ref[0, h:h + 1, :]
            decay = _ssd_tile(cs_c, cs_r, tri)
            inside = pairs * decay * dt_r
            # the tile's own gradient: only this head's lanes of dy count
            d_inside = _f32_dot(jnp.where(mine, dyp, jnp.zeros_like(dyp)),
                                xp, _NT)
            d_pairs = d_pairs + d_inside * decay * dt_r
            per_dt = d_inside * pairs * decay       # d_inside o inside / dt
            to_dt = jnp.sum(per_dt, axis=0, keepdims=True)          # (1, Q)
            to_cs = jnp.sum(per_dt * dt_r, axis=1, keepdims=True)   # (Q, 1)
            # the part read off the chunk's start state
            e_c = jnp.exp(cs_c)
            from_start = (cf * e_c).astype(xp.dtype)
            y_start = _f32_dot(from_start, start_x, _NT)
            to_cs = to_cs + jnp.sum(
                jnp.where(mine, dyf * y_start, 0.0), axis=1, keepdims=True)
            # the part that feeds the chunk's end state
            cs_last = _ssd_last(cs_r)
            total_decay = jnp.exp(cs_last)
            end_decay = jnp.exp(cs_last - cs_c)
            to_end = (bf * (end_decay * dt_c)).astype(xp.dtype)
            fed = jnp.sum(_f32_dot(jnp.where(mine, xp, jnp.zeros_like(xp)),
                                   grad_x, _NN) * bf, axis=1, keepdims=True)
            weighed = fed * end_decay * dt_c
            at_last = total(weighed) + total_decay * total(
                grad[rows, :] * start[rows, :])
            to_cs = to_cs - weighed + jnp.where(last, at_last, 0.0)
            dcs_c = jnp.where(lane_h == h, to_cs, dcs_c)
            ddt_c = jnp.where(lane_h == h, fed * end_decay, ddt_c)
            dcs_r = jnp.where(row_h == h, -to_dt * dt_r, dcs_r)
            ddt_r = jnp.where(row_h == h, to_dt, ddt_r)
            dxs.append(_f32_dot(inside.astype(xp.dtype), dyp, _TN)
                       + _f32_dot(to_end, grad_x, _NT))
            dyes.append(dyf * e_c)
            xws.append(xf * (end_decay * dt_c))
            dstate[p, rows, :] = total_decay * grad[rows, :]
        dx_ref[0, :, cols] = jnp.where(low, dxs[0], dxs[1]).astype(
            dx_ref.dtype)
        dye[:, cols] = jnp.where(low, dyes[0], dyes[1]).astype(dye.dtype)
        xw[:, cols] = jnp.where(low, xws[0], xws[1]).astype(xw.dtype)

    d_pairs = d_pairs.astype(bm.dtype)
    dc_ref[0, 0] = _f32_dot(d_pairs, bm, _NN) + _f32_dot(
        dye[...], s0_ref[0, 0].astype(dye.dtype), _NN)
    db_ref[0, 0] = _f32_dot(d_pairs, cm, _TN) + _f32_dot(xw[...], end_grad,
                                                         _NN)
    dstate[...] += _f32_dot(dye[...], cm, _TN).reshape(dstate.shape)
    ddtc_ref[0, 0], dcsc_ref[0, 0] = ddt_c, dcs_c
    ddtr_ref[0], dcsr_ref[0] = ddt_r, dcs_r


def _ssd_specs(hb, Q, N, nc, reverse):
    """Block specs on the (batch, head block, chunk) grid; ``reverse``: the
    chunk axis walks from the last chunk to the first."""
    def at(c):
        return nc - 1 - c if reverse else c

    return {
        "x": pl.BlockSpec((1, Q, hb * 64), lambda b, g, c: (b, at(c), g)),
        "col": pl.BlockSpec((1, 1, Q, hb), lambda b, g, c: (b, g, at(c), 0)),
        "row": pl.BlockSpec((1, hb, Q), lambda b, g, c: (b, g, at(c))),
        "bc": pl.BlockSpec((1, Q, N), lambda b, g, c: (b, at(c), 0)),
        "dbc": pl.BlockSpec((1, 1, Q, N), lambda b, g, c: (b, g, at(c), 0)),
        "state": pl.BlockSpec((1, 1, hb * 64, N),
                              lambda b, g, c: (b, at(c), g, 0)),
    }


def _ssd_call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
              interpret):
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        kernel, out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(shape, dtype) for shape, dtype in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_SSD_VMEM_LIMIT),
        interpret=interpret, name=name)


def _ssd_layouts(dt, hb):
    """``(column layout (B, H / hb, S, hb), row layout (B, H, S))`` of a
    (B, S, H) float32 array."""
    B, S, H = dt.shape
    return (jnp.transpose(dt.reshape(B, S, H // hb, hb), (0, 2, 1, 3)),
            jnp.transpose(dt, (0, 2, 1)))


def _ssd_fwd_impl(x, dt, cs, b, c, heads, chunk, keep_states, interpret):
    B, S, HP = x.shape
    N, Q, nc = b.shape[-1], chunk, S // chunk
    hb = _ssd_heads_block(heads)
    spec = _ssd_specs(hb, Q, N, nc, False)
    out_specs, out_shape = [spec["x"]], [jax.ShapeDtypeStruct(x.shape,
                                                              x.dtype)]
    if keep_states:
        out_specs.append(spec["state"])
        out_shape.append(jax.ShapeDtypeStruct((B, nc, HP, N), jnp.float32))
    call = _ssd_call(
        functools.partial(_ssd_fwd_kernel, hb=hb, keep_states=keep_states),
        "ssd_scan_fwd", (B, heads // hb, nc),
        [spec["x"], spec["col"], spec["col"], spec["row"], spec["row"],
         spec["bc"], spec["bc"]], out_specs, out_shape,
        [((hb // 2, 128, N), jnp.float32)], interpret)
    dt_c, dt_r = _ssd_layouts(dt, hb)
    cs_c, cs_r = _ssd_layouts(cs, hb)
    with jax.enable_x64(False):
        return call(x, dt_c, cs_c, dt_r, cs_r, b, c)


def _ssd_bwd_impl(x, dt, cs, b, c, states, g, heads, chunk, interpret):
    B, S, HP = x.shape
    N, Q, nc = b.shape[-1], chunk, S // chunk
    hb = _ssd_heads_block(heads)
    nb = heads // hb
    spec = _ssd_specs(hb, Q, N, nc, True)
    f32 = jnp.float32
    call = _ssd_call(
        functools.partial(_ssd_bwd_kernel, hb=hb), "ssd_scan_bwd",
        (B, nb, nc),
        [spec["x"], spec["x"], spec["col"], spec["col"], spec["row"],
         spec["row"], spec["bc"], spec["bc"], spec["state"]],
        [spec["x"], spec["col"], spec["col"], spec["row"], spec["row"],
         spec["dbc"], spec["dbc"]],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((B, nb, S, hb), f32),
         jax.ShapeDtypeStruct((B, nb, S, hb), f32),
         jax.ShapeDtypeStruct((B, heads, S), f32),
         jax.ShapeDtypeStruct((B, heads, S), f32),
         jax.ShapeDtypeStruct((B, nb, S, N), f32),
         jax.ShapeDtypeStruct((B, nb, S, N), f32)],
        [((hb // 2, 128, N), f32), ((Q, hb * 64), x.dtype),
         ((Q, hb * 64), x.dtype)], interpret)
    dt_c, dt_r = _ssd_layouts(dt, hb)
    cs_c, cs_r = _ssd_layouts(cs, hb)
    with jax.enable_x64(False):
        dx, ddt_c, dcs_c, ddt_r, dcs_r, db, dc = call(
            x, g, dt_c, cs_c, dt_r, cs_r, b, c, states)

    def whole(col, row):    # the two layouts' shares of a (B, S, H) gradient
        return jnp.transpose(col, (0, 2, 1, 3)).reshape(B, S, heads) \
            + jnp.transpose(row, (0, 2, 1))

    return (dx, whole(ddt_c, ddt_r), whole(dcs_c, dcs_r),
            jnp.sum(db, axis=1).astype(b.dtype),
            jnp.sum(dc, axis=1).astype(c.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd_scan(x, dt, cs, b, c, heads, chunk, interpret):
    return _ssd_fwd_impl(x, dt, cs, b, c, heads, chunk, False, interpret)[0]


def _ssd_vjp_fwd(x, dt, cs, b, c, heads, chunk, interpret):
    y, states = _ssd_fwd_impl(x, dt, cs, b, c, heads, chunk, True, interpret)
    return y, (x, dt, cs, b, c, states)


def _ssd_vjp_bwd(heads, chunk, interpret, res, g):
    x, dt, cs, b, c, states = res
    return _ssd_bwd_impl(x, dt, cs, b, c, states, g, heads, chunk, interpret)


_ssd_scan.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssm_scan(x, dt, cs, b, c, num_heads, chunk, interpret=False):
    """The chunked scan as kernels, forward and backward: ``y[t, h] = h_t
    c[t]`` of the recurrence ``h_t = exp(dt[t, h] A[h]) h_{t-1} + dt[t, h]
    x[t, h] b[t]^T`` (no ``D`` skip). ``x (B, S, H*64)``; ``dt (B, S, H)``
    float32, the step after its softplus; ``cs (B, S, H)`` float32, ``dt *
    A`` summed over each chunk's positions up to and with each (the log of
    the decay since the chunk began); ``b`` and ``c (B, S, N)``. Returns
    ``(B, S, H*64)``; differentiable in all five."""
    return _ssd_scan(x, dt.astype(jnp.float32), cs.astype(jnp.float32), b, c,
                     int(num_heads), int(chunk), interpret)


# ================================================================= ssm conv
# The causal depthwise convolution of a Mamba-2 mixer with its SiLU: x (B, S,
# C) read once and y written once forward; x and dy read once and dx written
# once backward, with the taps' and the bias's gradients summed on the way. A
# column only ever meets itself, so the grid is (batch, column block,
# position block) with the position axis SEQUENTIAL (`_ssd_call`'s
# semantics) and a block's K - 1 rows of history carried in VMEM scratch:
# forward the last rows of x from the block before; backward, the blocks
# walked in REVERSE, the first rows of dpre (= dy silu'(pre)) from the block
# after, and the rows of x before the block read as a second view of x one
# 16-row tile back. Inside a grid step an
# inner loop walks tiles of `_CONV_TILE` positions so that a tile's whole
# chain (float32 whatever the storage type: the shifted rows by a sublane
# roll of the tile with the 8 rows before it, the taps in the XLA form's
# order, SiLU, one rounding) stays in registers. No float32 array of the
# sequence's size exists, forward or backward. Measured on the chip at 1 x
# 32,768 x 4,352 bfloat16, 4 taps (PR 34), forward / forward + backward, ms on
# the host's clock (an empty program's round trip reads 0.68): (2,048, 256) a
# step 1.73 / 3.79, (1,024, 256) 1.84 / 4.14, (512, 256) 2.06 / 4.38, (1,024,
# 128) 2.19 / 4.91, (256, 2,176) 1.94 / 5.26; tiles of 16 / 32 / 64 positions
# 2.03 / 1.84 / 1.78 forward; the XLA form 2.86 / 12.12. On the device's
# clock: forward 1.01 ms (its reads and writes at the memory's full rate:
# 0.70), backward 1.63 (1.04); the XLA form 2.23 and 9.13.

_CONV_BLOCK = (2048, 256)   # positions, columns a grid step
_CONV_TILE = 32             # positions an inner step
_CONV_UNROLL = 4            # inner steps a loop iteration (Mosaic unrolls a
#                             ``fori_loop`` wholly or not at all)


def causal_conv_usable(seq, channels, taps):
    """Whether the convolution kernels take this problem (a sequence of any
    length: it is padded to whole position blocks)."""
    return seq >= 1 and channels % 128 == 0 and 1 <= taps <= 8


def _conv_blocks(seq, channels, blocks):
    """``(positions, columns)`` of a grid step: the fewest position blocks
    of at most ``blocks[0]`` rows in whole tiles, and the widest multiple
    of 128 up to ``blocks[1]`` that divides ``channels``."""
    rows, cols = blocks or _CONV_BLOCK
    count = -(-seq // rows)
    rows = -(-seq // (count * _CONV_TILE)) * _CONV_TILE
    cols = max(128, min(cols, channels) // 128 * 128)
    while channels % cols:
        cols -= 128
    return rows, cols


def _conv_pre(before, cur, taps, bias):
    """``(pre, shifted)`` of a tile: ``cur (R, cb)`` float32 with the 8 rows
    ``before`` it; ``shifted[j][t] = x[t - (K - 1) + j]`` and ``pre = bias +
    tap_0 shifted[0] + ... + tap_{K-1} shifted[K-1]``, added in that order
    (the XLA form's, so that the two agree to the bit)."""
    from jax.experimental.pallas import tpu as pltpu
    window = jnp.concatenate([before, cur], axis=0)
    shifted = [cur if d == 0 else pltpu.roll(window, d, 0)[8:]
               for d in range(len(taps) - 1, -1, -1)]
    pre = bias
    for tap, rows in zip(taps, shifted):
        pre = pre + tap * rows
    return pre, shifted


def _fold8(a):
    """(R, cb) -> (8, cb): the 8-row tiles added up (no sublane crosses)."""
    return sum(a[r:r + 8] for r in range(8, a.shape[0], 8)) + a[:8]


def _conv_loop(tiles, step, init):
    """``fori_loop(0, tiles, step, init)``, ``_CONV_UNROLL`` steps an
    iteration where that divides ``tiles``."""
    unroll = _CONV_UNROLL if tiles % _CONV_UNROLL == 0 else 1

    def group(i, carry):
        for k in range(unroll):
            carry = step(i * unroll + k, carry)
        return carry

    return jax.lax.fori_loop(0, tiles // unroll, group, init)


def _conv_fwd_kernel(x_ref, w_ref, b_ref, y_ref, tail):
    """One (batch, column block, position block) program of the forward;
    ``tail``: the 8 rows of x before the block, in float32."""
    f32, tile = jnp.float32, _CONV_TILE

    @pl.when(pl.program_id(2) == 0)
    def _():
        tail[...] = jnp.zeros(tail.shape, f32)

    taps = [w_ref[j:j + 1, :] for j in range(w_ref.shape[0])]
    bias = b_ref[...]

    def step(i, before):
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        cur = x_ref[0, at, :].astype(f32)
        pre, _ = _conv_pre(before, cur, taps, bias)
        y_ref[0, at, :] = jax.nn.silu(pre).astype(y_ref.dtype)
        return cur[tile - 8:]

    tail[...] = _conv_loop(x_ref.shape[1] // tile, step, tail[...])


def _conv_bwd_kernel(x_ref, xb_ref, dy_ref, w_ref, b_ref, dx_ref, dw_ref,
                     db_ref, head, acc):
    """One program of the backward, the position blocks (and the tiles
    inside one) in reverse: ``head`` holds the first 8 rows of dpre of the
    block after, ``acc (K + 1, 8, cb)`` the taps' and the bias's gradients
    (8 partial sums a column, added up at the sequence's start); ``xb_ref``
    the 16 rows of x before the block (the first block's are nought)."""
    from jax.experimental.pallas import tpu as pltpu
    f32, tile = jnp.float32, _CONV_TILE
    K = w_ref.shape[0]
    step_id, steps = pl.program_id(2), pl.num_programs(2)
    tiles = x_ref.shape[1] // tile

    @pl.when(step_id == 0)
    def _():
        head[...] = jnp.zeros(head.shape, f32)
        acc[...] = jnp.zeros(acc.shape, f32)

    taps = [w_ref[j:j + 1, :] for j in range(K)]
    bias = b_ref[...]
    halo = jnp.where(step_id == steps - 1, 0.0, xb_ref[0].astype(f32)[8:])

    def step(k, carry):
        after, sums = carry
        i = tiles - 1 - k
        row = pl.multiple_of(i * tile, tile)
        at = pl.ds(row, tile)
        cur = x_ref[0, at, :].astype(f32)
        inside = x_ref[0, pl.ds(pl.multiple_of(jnp.maximum(row - 16, 0), 16),
                                16), :].astype(f32)[8:]
        pre, shifted = _conv_pre(jnp.where(i == 0, halo, inside), cur, taps,
                                 bias)
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[0, at, :].astype(f32) * (sig * (1.0 + pre * (1.0 - sig)))
        window = jnp.concatenate([dpre, after], axis=0)
        dx = taps[K - 1] * dpre
        for j in range(K - 2, -1, -1):      # dx[t] += tap_j dpre[t + K-1-j]
            dx = dx + taps[j] * pltpu.roll(
                window, tile + 8 - (K - 1 - j), 0)[:tile]
        dx_ref[0, at, :] = dx.astype(dx_ref.dtype)
        sums = tuple(s + _fold8(dpre * rows)
                     for s, rows in zip(sums[:K], shifted)) \
            + (sums[K] + _fold8(dpre),)
        return dpre[:8], sums

    first, sums = _conv_loop(
        tiles, step, (head[...], tuple(acc[j] for j in range(K + 1))))
    head[...] = first
    for j in range(K + 1):
        acc[j] = sums[j]

    @pl.when(step_id == steps - 1)
    def _():
        for j in range(K):
            dw_ref[0, j:j + 1, :] = jnp.sum(sums[j], axis=0, keepdims=True)
        db_ref[0] = jnp.sum(sums[K], axis=0, keepdims=True)


def _conv_operands(w, bias):
    """The taps as rows ``(K, C)`` and the bias as one ``(1, C)``, float32."""
    return (jnp.transpose(w.astype(jnp.float32)),
            bias.astype(jnp.float32)[None, :])


# The two programs are jitted so that a step's nine mixers (each traced
# forward, again under its checkpoint, and backward) trace and lower each
# kernel ONCE: 27 traces of the unrolled bodies cost a warm set-up 13 s.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _conv_fwd_impl(x, w, bias, blocks, interpret):
    B, S, C = x.shape
    K = w.shape[1]
    rows, cols = _conv_blocks(S, C, blocks)
    block = pl.BlockSpec((1, rows, cols), lambda b, c, s: (b, s, c))
    call = _ssd_call(
        _conv_fwd_kernel, "ssm_conv_fwd", (B, C // cols, S // rows),
        [block, pl.BlockSpec((K, cols), lambda b, c, s: (0, c)),
         pl.BlockSpec((1, cols), lambda b, c, s: (0, c))],
        block, jax.ShapeDtypeStruct(x.shape, x.dtype),
        [((8, cols), jnp.float32)], interpret)
    with jax.enable_x64(False):
        return call(x, *_conv_operands(w, bias))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _conv_bwd_impl(x, w, bias, g, blocks, interpret):
    B, S, C = x.shape
    K = w.shape[1]
    rows, cols = _conv_blocks(S, C, blocks)
    steps = S // rows
    block = pl.BlockSpec((1, rows, cols),
                         lambda b, c, s: (b, steps - 1 - s, c))
    before = pl.BlockSpec(
        (1, 16, cols), lambda b, c, s: (
            b, jnp.maximum((steps - 1 - s) * (rows // 16) - 1, 0), c))
    f32 = jnp.float32
    call = _ssd_call(
        _conv_bwd_kernel, "ssm_conv_bwd", (B, C // cols, steps),
        [block, before, block,
         pl.BlockSpec((K, cols), lambda b, c, s: (0, c)),
         pl.BlockSpec((1, cols), lambda b, c, s: (0, c))],
        [block, pl.BlockSpec((1, K, cols), lambda b, c, s: (b, 0, c)),
         pl.BlockSpec((1, 1, cols), lambda b, c, s: (b, 0, c))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((B, K, C), f32),
         jax.ShapeDtypeStruct((B, 1, C), f32)],
        [((8, cols), f32), ((K + 1, 8, cols), f32)], interpret)
    with jax.enable_x64(False):
        dx, dw, db = call(x, x, g, *_conv_operands(w, bias))
    return (dx, jnp.transpose(jnp.sum(dw, axis=0)).astype(w.dtype),
            jnp.sum(db, axis=(0, 1)).astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, bias, blocks, interpret):
    return _conv_fwd_impl(x, w, bias, blocks, interpret)


def _conv_vjp_fwd(x, w, bias, blocks, interpret):
    return _conv_fwd_impl(x, w, bias, blocks, interpret), (x, w, bias)


def _conv_vjp_bwd(blocks, interpret, res, g):
    return _conv_bwd_impl(*res, g, blocks, interpret)


_conv.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def causal_conv1d(x, w, bias, blocks=None, interpret=False):
    """``silu(bias + sum_j w[:, j] x[t - (K - 1) + j])`` along axis 1 of ``x
    (B, S, C)`` as kernels, forward and backward (the residuals are the
    three operands): ``w (C, K)``, ``bias (C,)``, nothing before the
    sequence, float32 inside whatever the storage type. ``blocks``:
    ``(positions, columns)`` of a grid step. Returns ``(B, S, C)`` in x's
    type; differentiable in all three."""
    S = x.shape[1]
    rows, _ = _conv_blocks(S, x.shape[2], blocks)
    pad = -S % rows
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    y = _conv(x, w, bias, blocks, interpret)
    return y[:, :S] if pad else y
