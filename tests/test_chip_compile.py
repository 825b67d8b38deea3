"""The attention kernels of the main path, compiled for the real chip.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (``jax.experimental.topologies``), so
these tests see what interpret mode cannot: Mosaic's tiling and VMEM
limits at the real widths, and that a kernel inside a multi-chip program
is partitioned by ``shard_map`` (GSPMD refuses a bare Mosaic call).
Nothing runs — results are ``tests/test_pallas.py``'s job (interpret
mode) and ``chip_smoke.py``'s (on the chip).

The topology is described inside a module-scoped fixture, never at
import: only one process may load libtpu, and every xdist worker imports
this file. All such compiles live in this one file for the same reason.
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from mxnet_tpu.ops import nn as nn_ops
from mxnet_tpu.ops.pallas_kernels import flash_attention, flash_attention_bshd
from mxnet_tpu.parallel.mesh import AXES, mesh_scope


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _fwd_bwd(attend):
    """Sum-of-squares loss through ``attend``: forward + backward."""
    def loss(q, k, v, kv_mask, seed):
        return jnp.sum(attend(q, k, v, kv_mask, seed).astype(jnp.float32)
                       ** 2)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _specs(qkv_shape, batch, seq, sharding, mask_sharding, scalar_sharding):
    qkv = jax.ShapeDtypeStruct(qkv_shape, jnp.bfloat16, sharding=sharding)
    mask = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                sharding=mask_sharding)
    seed = jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_sharding)
    return qkv, qkv, qkv, mask, seed


_FUSED = ("flash_bshd_fwd", "flash_bshd_bwd")
_SPLIT = ("flash_bshd_fwd", "flash_bshd_dq", "flash_bshd_dkv")


@pytest.mark.parametrize("batch,seq,kernels", [
    (16, 512, _FUSED), (64, 128, _FUSED), (8, 1024, _SPLIT)],
    ids=["bert_s512_b16", "bert_s128_b64", "bert_s1024_b8"])
def test_bshd_fwd_bwd_compiles_one_chip(topo, batch, seq, kernels):
    """BERT-base widths (12 heads x 64), padding mask, dropout 0.1: one
    key block spans 128 and 512 positions, so the backward is the one
    fused kernel; 1,024 positions are two key blocks and keep dq + dkdv."""
    one = SingleDeviceSharding(topo.devices[0])
    fn = _fwd_bwd(lambda q, k, v, m, s: flash_attention_bshd(
        q, k, v, m, s, False, 0.1))
    text = fn.lower(*_specs((batch, seq, 12, 64), batch, seq, one, one,
                            one)).compile().as_text()
    assert text.count("tpu_custom_call") == len(kernels)
    # each kernel's custom call carries the name the program gave it
    for kernel in kernels:
        assert re.search(r'op_name="[^"]*\b%s\b[^"]*/pallas_call"' % kernel,
                         text), kernel


@pytest.mark.parametrize("heads,head_dim,seq,causal", [
    (8, 128, 512, False), (16, 64, 512, False), (12, 64, 256, True),
    (12, 64, 384, False)],
    ids=["h8_d128_s512", "h16_d64_s512", "causal_s256", "s384"])
def test_fused_backward_compiles_at_other_widths(topo, heads, head_dim, seq,
                                                 causal):
    """The fused backward on the packed projection inside Mosaic's VMEM
    limit where the head group is NOT BERT-base's: 128-wide heads, a
    1,024-column projection (groups of 256 of it, nearest the budget), the
    causal form and a sequence of three 128-blocks, mask and dropout on."""
    from mxnet_tpu.ops.pallas_kernels import flash_attention_packed
    one = SingleDeviceSharding(topo.devices[0])

    def loss(qkv, kv_mask, seed):
        return jnp.sum(flash_attention_packed(
            qkv, heads, kv_mask, seed, causal, 0.1).astype(jnp.float32) ** 2)
    text = jax.jit(jax.grad(loss)).lower(
        jax.ShapeDtypeStruct((8, seq, 3 * heads * head_dim), jnp.bfloat16,
                             sharding=one),
        jax.ShapeDtypeStruct((8, seq), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "flash_bshd_bwd" in text


def test_bhsd_causal_fwd_bwd_compiles_one_chip(topo):
    """TransformerLM s512 b32, 8 heads x 64, causal, no mask/dropout."""
    one = SingleDeviceSharding(topo.devices[0])
    fn = _fwd_bwd(lambda q, k, v, m, s: flash_attention(
        q, k, v, None, None, True, 0.0))
    text = fn.lower(*_specs((32, 8, 512, 64), 32, 512, one, one,
                            one)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for kernel in ("flash_bhsd_fwd", "flash_bhsd_dq", "flash_bhsd_dkv"):
        assert re.search(r'op_name="[^"]*\b%s\b[^"]*/pallas_call"' % kernel,
                         text), kernel


def test_bshd_dp_sharded_compiles_four_chips(topo, monkeypatch):
    """The dispatcher inside a dp=4 program: BERT-base s128, global batch
    64 sharded over four chips, mask + dropout. A bare Mosaic call here
    raises 'Mosaic kernels cannot be automatically partitioned'; the
    dispatcher must wrap it in shard_map over the mesh it observes, and
    the batch-sharded q/k/v must reach the kernel without an all-gather."""
    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices).reshape((4, 1, 1, 1, 1)), AXES)
    # the process's own backend is the CPU; the program is for the chip
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    key = jax.random.PRNGKey(0)

    def attend(q, k, v, kv_mask, seed):
        with mesh_scope(mesh, ("dp",)):
            return nn_ops.dot_product_attention.fn(
                q, k, v, mask=kv_mask, dropout=0.1, layout="BSHD",
                rng_key=jax.random.fold_in(key, seed), train=True)

    batch = NamedSharding(mesh, P("dp"))
    text = _fwd_bwd(attend).lower(*_specs(
        (64, 128, 12, 64), 64, 128, batch, batch,
        NamedSharding(mesh, P()))).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "all-gather" not in text
    # through the dispatcher the kernels lie under its scope, backward too
    assert re.search(r'op_name="[^"]*\battention\b[^"]*\bflash_bshd_bwd\b',
                     text)


# ------------------------------------------- the packed entry, one block

_COPY_OF_QKV = re.compile(
    r"= bf16\[96,512,(768|2304)\]\S* copy\(|"
    r"= bf16\[24,512,(768|2304)\]\S* copy\(")


def _attention_block(mesh_scope_args=None):
    """One BERT-base attention block as the model runs it: QKV Dense (no
    bias) -> the packed entry under a padding mask -> output projection;
    sum-of-squares loss, gradients of the input and both weights."""
    def loss(x, w_qkv, w_proj, mask):
        qkv = jnp.einsum("bsc,oc->bso", x, w_qkv)
        out = nn_ops.packed_self_attention.fn(
            qkv, mask=mask[:, None, None, :], num_heads=12, dropout=0.0,
            causal=False)
        y = jnp.einsum("bsc,oc->bso", out, w_proj)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def scoped(*args):
        if mesh_scope_args is None:
            return loss(*args)
        with mesh_scope(*mesh_scope_args):
            return loss(*args)
    return jax.jit(jax.value_and_grad(scoped, argnums=(0, 1, 2)))


def _block_specs(batch, rows, replicated):
    bf16 = jnp.bfloat16
    return (jax.ShapeDtypeStruct((batch, 512, 768), bf16, sharding=rows),
            jax.ShapeDtypeStruct((2304, 768), bf16, sharding=replicated),
            jax.ShapeDtypeStruct((768, 768), bf16, sharding=replicated),
            jax.ShapeDtypeStruct((batch, 512), jnp.int32, sharding=rows))


_CUSTOM_CALL_RESULT = re.compile(r"= (\([^=]*\)|\S+) custom-call\(")


def _assert_packed_block(text):
    """Two Mosaic calls under the ``attention`` scope, forward and the one
    fused backward, whose only result is the packed gradient (no ``delta``
    array handed from one kernel to another: the forward's log-sum-exp is
    the one float32 array a kernel writes), and none of the layout passes
    the split form paid: no ``pad_add`` fusion (the gradient of the QKV
    split) and no bf16 copy of a q/k/v- or qkv-sized tensor."""
    assert text.count("tpu_custom_call") == 2
    for kernel in _FUSED:
        assert re.search(r'op_name="[^"]*\battention\b[^"]*\b%s\b[^"]*'
                         r'/pallas_call"' % kernel, text), kernel
    results = [_CUSTOM_CALL_RESULT.search(line).group(1)
               for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum(r.count("f32[") for r in results) == 1, results
    backward = [r for r in results if "f32[" not in r]
    assert len(backward) == 1 and re.match(r"bf16\[\d+,512,2304\]",
                                           backward[0]), results
    assert "pad_add" not in text
    assert not _COPY_OF_QKV.search(text), _COPY_OF_QKV.search(text).group(0)


def test_packed_attention_block_compiles_without_layout_copies(topo,
                                                               monkeypatch):
    """The benchmark cell's block, b96 x s512, for one described chip."""
    from mxnet_tpu.ops.pallas_kernels import flash_backward_stats
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    before = nn_ops.attention_dispatch_stats()
    backwards = flash_backward_stats()
    text = _attention_block().lower(
        *_block_specs(96, one, one)).compile().as_text()
    _assert_packed_block(text)
    after = nn_ops.attention_dispatch_stats()
    assert after["packed"] == before["packed"] + 1
    assert (after["flash"], after["xla"]) == (before["flash"],
                                              before["xla"])
    assert flash_backward_stats() == {"fused": backwards["fused"] + 1,
                                      "split": backwards["split"]}


def test_packed_attention_block_dp_sharded_compiles_four_chips(topo,
                                                               monkeypatch):
    """The same block inside a dp=4 program (global batch 96, 24 rows a
    chip): the packed kernels under ``shard_map`` over the batch, the
    sharded projection reaching them without an all-gather."""
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape((4, 1, 1, 1, 1)), AXES)
    text = _attention_block((mesh, ("dp",))).lower(*_block_specs(
        96, NamedSharding(mesh, P("dp")),
        NamedSharding(mesh, P()))).compile().as_text()
    _assert_packed_block(text)
    assert "all-gather" not in text


# ---- the latent-attention kernels at the benchmark's widths -----------------

def _latent_fwd_bwd():
    from mxnet_tpu.ops.pallas_kernels import flash_attention_latent

    def loss(q_nope, q_rope, kv, k_rope):
        return jnp.sum(flash_attention_latent(
            q_nope, q_rope, kv, k_rope, 16, True).astype(jnp.float32) ** 2)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))


@pytest.mark.parametrize("seq", [8192, 32768])
def test_latent_kernels_compile_at_published_widths(topo, seq):
    """16 heads of 128 + 64 against 128, bfloat16, for one described chip:
    two Mosaic calls, ``flash_latent_fwd`` and the ONE backward
    ``flash_latent_bwd``, at 8,192 positions (the cell's) and at 32,768.
    The forward's VMEM holds blocks only (asserted against what Mosaic
    planned for it); the backward's grows with the sequence by dQ^T of one
    (row, head) in float32 (6 MiB at 8,192, 24 at 32,768) and asks Mosaic
    for what ``_latent_bwd_vmem`` plans, so this is where that footprint
    is proved without the chip. Past the budget (about 120,000 positions at
    these widths) ``flash_latent_dq`` + ``_dkv`` run (the per-head BHSD
    kernels are refused at 8,192 x 192)."""
    from mxnet_tpu.ops import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    rows = 2 if seq == 8192 else 1

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    backwards = pk.latent_backward_stats()
    text = _latent_fwd_bwd().lower(
        spec(rows, seq, 16 * 128), spec(rows, seq, 16, 64),
        spec(rows, seq, 16 * 256), spec(rows, seq, 64)).compile().as_text()
    assert pk.latent_backward_stats() == {"fused": backwards["fused"] + 1,
                                          "split": backwards["split"]}
    assert text.count("tpu_custom_call") == 2
    for kernel in ("flash_latent_fwd", "flash_latent_bwd"):
        assert kernel in text, kernel
    # the forward's VMEM: what Mosaic planned for it, against its blocks (the
    # four operands' and two results' double-buffered, a last dim under 128
    # taking 128 lanes and the log-sum-exp row 8 sublanes), its scratch and
    # two float32 (blk_k, blk_q) tiles, the scores and their exponentials; a
    # body that kept anything of the whole sequence would pass it at 32,768
    blk_q, blk_k = pk._pick_blocks_latent(seq)
    blocks = 2 * 2 * (blk_q * (128 + 128 + 128) + blk_k * (256 + 128)) \
        + 2 * 8 * blk_q * 4
    scratch = (128 + 2 * 8) * blk_q * 4
    used = [int(n) for line in text.splitlines()
            if "flash_latent_fwd" in line and "tpu_custom_call" in line
            for n in re.findall(
                r'used_scoped_memory_configs":\[[^]]*"size":"(\d+)"', line)]
    assert len(used) == 1
    assert used[0] <= blocks + scratch + 2 * blk_q * blk_k * 4, used


def test_bhsd_kernels_are_refused_at_the_latent_cells_length(topo):
    """Why the latent kernels exist: the per-head kernels keep K and V of a
    head whole in VMEM, and at 8,192 x 192 the chip's compiler refuses
    them."""
    one = SingleDeviceSharding(topo.devices[0])
    shape = jax.ShapeDtypeStruct((32, 1, 8192, 192), jnp.bfloat16,
                                 sharding=one)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, True, 0.0)
                       .astype(jnp.float32))
    with pytest.raises(Exception, match="vmem"):
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            shape, shape, shape).compile()


# ---- the EVA kernels at the benchmark's widths ------------------------------

def test_eva_kernels_compile_at_published_widths(topo):
    """32 heads of 128, windows of 2,048, chunks of 16, one row of 32,768
    bytes in bfloat16, for one described chip: three Mosaic calls,
    ``flash_eva_fwd``, ``_bwd`` and ``_dsum``, whose VMEM holds blocks, tiles
    and one window's accumulators; the pooling around them is XLA's."""
    from mxnet_tpu.ops import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    seq, heads, window, chunk = 32768, 32, 2048, 16

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def loss(q, k, v, mu, phi):
        kt, vt = nn_ops.eva_chunk_summaries(k, v, mu, phi, heads, chunk)
        return jnp.sum(pk.flash_attention_eva(
            q, k, v, kt, vt, heads, window).astype(jnp.float32) ** 2)

    tiles = pk.eva_tile_stats()
    wide = spec(1, seq, heads * 128)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        wide, wide, wide, spec(heads, 128), spec(heads, 128)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("flash_eva_fwd", "flash_eva_bwd", "flash_eva_dsum"):
        assert kernel in text, kernel
    after = pk.eva_tile_stats()
    # the planner at 1024 / 1024 / 1024: 320 grid steps, 228 of them live
    assert (after["stepped"] - tiles["stepped"],
            after["live"] - tiles["live"]) == (320, 228)


def test_eva_decoder_step_names_the_kernels(topo, monkeypatch):
    """One recomputed layer's training step at the published widths and
    32,768 bytes, traced by the trainer as on the chip and compiled for one
    described chip: the lowered step names the three EVA kernels, the forward
    once (the attention's output and log-sum-exp are kept by name, so the
    recomputed layer does not run it again)."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.models.eva_lm import EvaDecoder
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    net = EvaDecoder(vocab_size=320, units=4096, num_layers=1, num_heads=32,
                     hidden_size=11008, window=2048, chunk=16, pred_heads=8,
                     rope_theta=100000.0, recompute=True)
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    trainer = parallel.ShardedTrainer(
        net, lambda out, _label: out, "adam", {"learning_rate": 1e-4},
        mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]),
        dtype="bfloat16")
    trainer._build_step()

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    before = nn_ops.attention_dispatch_stats()
    compiled = trainer._step_fn.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
        [spec(v) for v in trainer._values],
        [tuple(spec(x) for x in s) for s in trainer._states], 1, 1e-4,
        ints(1, 32768), ints(1, 32768, 8),
        jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one)).compile()
    after = nn_ops.attention_dispatch_stats()
    assert after["eva"] == before["eva"] + 1 and after["xla"] == before["xla"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("flash_eva_fwd", "flash_eva_bwd", "flash_eva_dsum"):
        assert len(re.findall(r"= [^\n]*custom-call\([^\n]*%s" % kernel,
                              text)) == 1, kernel
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 8 * 2 ** 30


# ---- the held experts' combine at the latent decoder's cell ------------------

def test_held_experts_combine_runs_over_the_buffers_rows(topo, monkeypatch):
    """``held_experts_ffn`` forward + backward at the cell's shapes (32,768
    tokens of 2,048, 8 of 64 experts of 1,408 held, 6 a token, bfloat16)
    for one described chip. No gather writes a (32,768, 2,048) array in any
    of the three buffer sizes' branches (54 did while the combine ran a
    choice at a time); every branch sums by token with ``tgmm`` (42 Mosaic
    calls: 3 sizes x (3 + 1 forward, 3 recomputed + 3 ``d_lhs`` + 3 + 1
    ``tgmm`` backward)); the temporaries stay under the 5.70 GB the layer
    took before (4.16 GB when this was written)."""
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(moe, "_megablox_usable", lambda m, k, n: (
        m % 128 == 0 and k % 128 == 0 and n % 128 == 0))
    one = SingleDeviceSharding(topo.devices[0])
    tokens, d, f, held, experts, top_k = 32768, 2048, 1408, 8, 64, 6

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def loss(x, router_w, router_b, w_gate, w_up, w_down):
        y, _ = moe.held_experts_ffn(x, router_w, router_b, w_gate, w_up,
                                    w_down, first=0, top_k=top_k, scale=2.446)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    before = moe.combine_stats()
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        spec(tokens, d), spec(experts, d), spec(experts), spec(held, d, f),
        spec(held, d, f), spec(held, f, d)).compile()
    after = moe.combine_stats()
    assert (after["grouped"] - before["grouped"], after["xla"]) == (
        6, before["xla"])
    text = compiled.as_text()
    assert not re.findall(r"= \w+\[32768,2048\][^\n]* gather\(", text)
    assert text.count("tpu_custom_call") == 42
    branches = re.findall(r"conditional\([^\n]*branch_computations=\{([^}]*)\}",
                          text)
    assert len(branches) == 2 and all(b.count(",") == 2 for b in branches)
    kernels = re.findall(
        r'tpu_custom_call[^\n]*op_name="[^"]*[/(](moe_\w+)\)*/jit\((\w+)\)/', text)
    assert sorted(set(kernels)) == [
        ("moe_combine", "tgmm"), ("moe_products", "gmm"),
        ("moe_products", "tgmm")]
    assert kernels.count(("moe_combine", "tgmm")) == 3 * 2      # y and d_x
    assert compiled.memory_analysis().temp_size_in_bytes < 5.70e9


# ---- the hybrid state-space decoder's kernels at the benchmark's widths -----

def test_scan_kernels_compile_at_published_widths(topo):
    """64 scan heads of 64 over a state of 128 in chunks of 256, one row of
    32,768 tokens in bfloat16, for one described chip: two Mosaic calls,
    ``ssd_scan_fwd`` (the form that also writes the chunk-boundary states)
    and the ONE backward ``ssd_scan_bwd``; heads of 64 ride two to a
    128-lane slab and the state's float32 scratch holds 16 heads."""
    from mxnet_tpu.ops import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    seq, heads, state, chunk = 32768, 64, 128, 256
    assert pk.ssm_scan_usable(seq, heads, 64, state, chunk)

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(x, dt, cs, b, c):
        return jnp.sum(pk.ssm_scan(x, dt, cs, b, c, heads, chunk)
                       .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(
        spec(1, seq, heads * 64), spec(1, seq, heads, dtype=jnp.float32),
        spec(1, seq, heads, dtype=jnp.float32), spec(1, seq, state),
        spec(1, seq, state)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert kernel in text, kernel
    # the kept states (268 MB), the two float32 shares of db and dc, the
    # layouts of dt and the log-decay: no (heads, S / 256, 256, 256) array
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
    assert not re.search(r"\[[0-9,]*256,256\]", text)


def test_grouped_kernels_compile_at_published_widths(topo):
    """32 query heads over 8 key-value heads of 64, one row of 32,768 tokens
    in bfloat16, for one described chip: three Mosaic calls whose VMEM holds
    one block of each operand whatever the sequence (four stacked query
    heads of 256 rows against a 2,048-row key block), where the per-head BHSD
    kernels, which keep a head's whole K and V in VMEM, are refused."""
    from mxnet_tpu.ops import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    seq, heads, kv_heads = 32768, 32, 8
    assert nn_ops._attention_path("grouped", (seq, 64, heads, kv_heads)) \
        == "xla"        # no chip here: the kernels are called directly

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return jnp.sum(pk.flash_attention_grouped(q, k, v, heads, kv_heads)
                       .astype(jnp.float32) ** 2)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        spec(1, seq, heads * 64), spec(1, seq, kv_heads * 64),
        spec(1, seq, kv_heads * 64)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for kernel in ("flash_grouped_fwd", "flash_grouped_dq",
                   "flash_grouped_dkv"):
        assert kernel in text, kernel
    assert not re.search(r"\[[0-9,]*32768,32768\]", text)

    whole = spec(1, heads, seq, 64)

    def per_head(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, True, 0.0)
                       .astype(jnp.float32))
    with pytest.raises(Exception, match="vmem"):
        jax.jit(jax.grad(per_head, argnums=(0, 1, 2))).lower(
            whole, whole, whole).compile()


def test_conv_kernels_compile_at_published_widths(topo, monkeypatch):
    """Granite 4.0-H Micro's convolution (4,096 scan columns + 2 x 128 of B
    and C, 4 taps) over 32,768 positions in bfloat16, value and gradient of
    the op as it decides on the chip: one Mosaic call each way, no float32
    array of the sequence's size (the XLA form's gradient writes one, and
    four scaled copies of it: 1.71 GB of temporaries) and of temporaries
    the output's gradient alone."""
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def value_and_gradient(x, w, bias, g):
        y, vjp = jax.vjp(nn_ops.causal_conv1d.fn, x, w, bias)
        return (y,) + vjp(g)

    before = nn_ops.ssm_conv_stats()
    compiled = jax.jit(value_and_gradient).lower(
        spec(1, 32768, 4352), spec(4352, 4), spec(4352),
        spec(1, 32768, 4352)).compile()
    assert nn_ops.ssm_conv_stats() == dict(before,
                                           kernel=before["kernel"] + 1)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("ssm_conv_fwd", "ssm_conv_bwd"):
        assert len(re.findall(r"= [^\n]*custom-call\([^\n]*%s" % kernel,
                              text)) == 1, kernel
    assert "f32[1,32768,4352]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


@pytest.mark.parametrize("width,rows,targets,limit", [
    (2048, 100352, 1, 2.6e9), (4096, 320, 8, 0.1e9)],
    ids=["granite_tied_vocabulary", "evabyte_eight_heads"])
def test_loss_head_compiles_to_one_loop_of_three_products(topo, width, rows,
                                                          targets, limit):
    """The chunked loss head over 32,768 positions in chunks of 2,048,
    value and both gradients, at Granite 4.0-H Micro's widths (2,048 against
    the whole tied vocabulary) and at EvaByte's (4,096 against eight heads
    of 320 byte values): ONE ``while`` and three ``convolution``s (the
    logits, ``d W`` and ``d^T h``; the two-pass rule compiled to two loops
    and four, four loops for the eight heads), no ``(targets, positions,
    width)`` array, and of temporaries the chunk's float32 logits and the
    float32 sum of ``d(weight)``: 2.47 GB by ``memory_analysis`` at
    Granite's (the two-pass rule read 0.82 GB: the logits' and the sum's
    turns came one after the other) and 0.04 GB at EvaByte's (0.27 GB: it
    kept a head's ``d(hidden)``)."""
    one = SingleDeviceSharding(topo.devices[0])
    labels = (1, 32768) + ((targets,) if targets > 1 else ())
    compiled = jax.jit(jax.value_and_grad(
        lambda h, w, lab: nn_ops.chunked_softmax_cross_entropy.fn(
            h, w, lab, chunk=2048), (0, 1))).lower(
        jax.ShapeDtypeStruct((1, 32768, width), jnp.bfloat16, sharding=one),
        jax.ShapeDtypeStruct((targets * rows, width), jnp.bfloat16,
                             sharding=one),
        jax.ShapeDtypeStruct(labels, jnp.int32, sharding=one)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"= [^\n]* while\(", text)) == 1
    assert len(re.findall(r"= [^\n]* convolution\(", text)) == 3
    if targets > 1:
        assert "[%d,32768,%d]" % (targets, width) not in text
    assert "loss_head" in text
    assert compiled.memory_analysis().temp_size_in_bytes < limit


def test_hybrid_decoder_step_names_the_kernels(topo, monkeypatch):
    """One recomputed state-space layer and the attention layer at the
    published widths, the whole tied vocabulary and 32,768 tokens, traced by
    the trainer as on the chip and compiled for one described chip: the
    lowered step names the scan's forward and the convolution's twice (the
    layer's forward is run again, and that run writes the states the one
    backward reads) and the grouped forward once (its output and log-sum-exp
    are kept by name). Ten layers make 57 Mosaic calls: six a recomputed
    mixer (30 before the convolution had kernels)."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.models.hybrid_ssm import HybridDecoder
    from mxnet_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(nn_ops, "_on_accelerator", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    net = HybridDecoder(
        vocab_size=100352, units=2048, layer_types=("mamba", "attention"),
        hidden_size=8192, mamba_heads=64, mamba_head_dim=64, mamba_state=128,
        num_heads=32, num_kv_heads=8, attention_multiplier=0.015625,
        residual_multiplier=0.22, embedding_multiplier=12.0,
        logits_scaling=8.0, recompute=True)
    net.initialize(mx.init.Zero())
    net.cast("bfloat16")
    trainer = parallel.ShardedTrainer(
        net, lambda out, _label: out, "adam", {"learning_rate": 1e-4},
        mesh=parallel.make_mesh(dp=1, devices=jax.devices()[:1]),
        dtype="bfloat16")
    trainer._build_step()

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    before, scans = nn_ops.attention_dispatch_stats(), nn_ops.ssm_scan_stats()
    convs = nn_ops.ssm_conv_stats()
    compiled = trainer._step_fn.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
        [spec(v) for v in trainer._values],
        [tuple(spec(x) for x in s) for s in trainer._states], 1, 1e-4,
        ints(1, 32768), ints(1, 32768),
        jax.ShapeDtypeStruct((1,), jnp.float32, sharding=one)).compile()
    after = nn_ops.attention_dispatch_stats()
    assert after == dict(before, grouped=before["grouped"] + 1)
    assert nn_ops.ssm_scan_stats() == dict(scans, kernel=scans["kernel"] + 1)
    assert nn_ops.ssm_conv_stats() == dict(convs, kernel=convs["kernel"] + 1)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 9
    for kernel, times in (("ssd_scan_fwd", 2), ("ssd_scan_bwd", 1),
                          ("ssm_conv_fwd", 2), ("ssm_conv_bwd", 1),
                          ("flash_grouped_fwd", 1), ("flash_grouped_dq", 1),
                          ("flash_grouped_dkv", 1)):
        assert len(re.findall(r"= [^\n]*custom-call\([^\n]*%s" % kernel,
                              text)) == times, kernel
    assert not re.search(r"\[[0-9,]*(256,256|32768,32768)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2 ** 30
