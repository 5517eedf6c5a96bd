"""The flash kernels, forward and backward, compiled by the TPU's own
compiler for a described (not attached) v5e: lane-packed at the GPT-2
cells' shapes and at the scoped-VMEM clamp shape; and, for the
latent-attention cell, folded at 192-wide keys and 128-wide values; and the
grouped-matmul kernels at the held experts' shapes; and the index scores'
backward kernel at the selecting cell's; and the per-head norm and rotation
at the two 128-wide-head cells' head counts; and the head-wise gate at the
Laguna cell's; and the flash backward at five cells' layer shapes, for a
float32 view of the heads outside its kernels. Nothing runs: this guards the
16 MB scoped-VMEM limit and the lane / sublane alignment of the in-kernel
sub-tile slices, which interpret mode cannot see, at no chip time
(on-chip-measurement guide, third rehearsal; the whole step programs are
``benchmarks/rehearsal/compile_v5e.py``'s).

Kept in ONE file: the worker that runs it loads libtpu and keeps its lock.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tpu.ops import flash_attention as fa
from distributed_tpu.ops import grouped_matmul as gm
from distributed_tpu.ops import head_gate as hg
from distributed_tpu.ops import head_norm_rope as hn
from distributed_tpu.ops import index_scores as ix


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Lower to Mosaic although the default backend is the CPU, with the
    persistent compile cache off (an entry for a described device cannot be
    read back) and no cached custom_vjp from an interpret-mode test."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(gm, "_interpret", lambda: False)
    monkeypatch.setattr(ix, "_interpret", lambda: False)
    monkeypatch.setattr(hn, "_interpret", lambda: False)
    monkeypatch.setattr(hg, "_interpret", lambda: False)
    fa._flash_cached.cache_clear()
    for jitted in (hn._forward, hn._backward, hg._forward, hg._backward):
        jitted.clear_cache()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    fa._flash_cached.cache_clear()
    for jitted in (hn._forward, hn._backward, hg._forward, hg._backward):
        jitted.clear_cache()


@pytest.mark.parametrize("shape,causal", [
    ((8, 1024, 16, 64), True),   # gpt2-medium.train.1chip
    ((4, 1024, 20, 64), True),   # gpt2-large.train.fsdp4, rows of one chip
    ((1, 4096, 16, 64), True),   # the bf16 clamp: block_q 512, block_k 1024
    # Not causal, every row of sub-tiles is like the next: two heads' merged
    # passes fit the scoped VMEM only under ``_PASS_SCORES``.
    ((8, 1024, 16, 64), False),
])
def test_packed_flash_grad_compiles_for_v5e(shape, causal, one_chip, mosaic):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    for name in ("dtpu_flash_fwd_packed", "dtpu_flash_dq_packed",
                 "dtpu_flash_dkv_packed"):
        assert name in text


@pytest.mark.parametrize("causal", [True, False])
def test_folded_flash_grad_at_latent_attentions_widths_compiles_for_v5e(
        causal, one_chip, mosaic):
    """kanana2-30b.train.ep8share: (1, 4096, 32) heads, q and k 192 wide (in
    256 lanes), v 128 wide; block_q 512, block_k 1024: three block views of
    the unrolled walk a kernel under causality, one without."""
    qk = jax.ShapeDtypeStruct((1, 4096, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).compile()
    text = compiled.as_text()
    for name in ("dtpu_flash_fwd", "dtpu_flash_dq", "dtpu_flash_dkv"):
        assert name in text
    assert "_packed" not in text


@pytest.mark.parametrize("t,kv_heads,dtype,selecting", [
    (8192, 4, jnp.bfloat16, True), (8192, 4, jnp.bfloat16, False),
    # The cell's layer off the cell's shape, at the blocks a call resolves
    # to: block_q 1024 at T <= 2048 under bf16, float32 inputs, a group of
    # sixteen, and one K/V head for all 32 (multi-query attention).
    (1024, 4, jnp.bfloat16, True), (2048, 4, jnp.bfloat16, True),
    (2048, 4, jnp.bfloat16, False), (8192, 4, jnp.float32, True),
    (8192, 2, jnp.bfloat16, True), (2048, 1, jnp.float32, False),
])
def test_grouped_query_flash_grad_compiles_for_v5e(t, kv_heads, dtype,
                                                   selecting, one_chip,
                                                   mosaic):
    """keye-vl2-30b.train.dsa8k: (1, 8192, 32) query heads over 4 K/V heads
    of 128, block_q 512, block_k 1024, with the int8 selection operand (its
    block converted and and-ed with the masks in VMEM, its flags in scalar
    memory) and without; dk/dv walk a group's eight query heads a K/V head,
    and the forward and dq as many of them on one fetch of K, V and the
    selection as the 16 MB a kernel may use hold of their q-side blocks and
    scratch (``_heads_a_fetch``: all eight in the cell)."""
    _grouped_query_grad_compiles(t, 32, kv_heads, dtype, selecting, None,
                                 one_chip)


def _grouped_query_grad_compiles(t, heads, kv_heads, dtype, selecting,
                                 window, one_chip):
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    q = spec((1, t, heads, 128), dtype)
    kv = spec((1, t, kv_heads, 128), dtype)
    sel = spec((1, t, t), jnp.int8) if selecting else None

    def loss(q, k, v, sel):
        out = fa.flash_attention(q, k, v, causal=True, selection=sel,
                                 window=window)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, sel).compile().as_text()
    suffix = "_sel" if selecting else "_swa" if window else "_packed"
    for name in ("dtpu_flash_fwd", "dtpu_flash_dq", "dtpu_flash_dkv"):
        assert name + suffix in text


@pytest.mark.parametrize("t,heads,dtype,window", [
    (8192, 64, jnp.bfloat16, 512),   # the cell's three sliding layers
    (8192, 48, jnp.bfloat16, None),  # its two full layers: groups of six
    # Off the cell's shape: block_q 1024 at T <= 2048 (two heads a fetch),
    # float32 inputs, a window no multiple of the sub-tile, and one wider
    # than a kv block.
    (2048, 64, jnp.bfloat16, 512), (8192, 64, jnp.float32, 512),
    (4096, 48, jnp.bfloat16, 200), (8192, 64, jnp.bfloat16, 1536),
])
def test_windowed_grouped_query_flash_grad_compiles_for_v5e(
        t, heads, dtype, window, one_chip, mosaic):
    """laguna-xs2.train.swa8k: (1, 8192, 64) query heads over 8 K/V heads of
    128 with a window of 512 keys (``dtpu_flash_*_swa``: the band's index
    maps, ``_band_block``, and its masks lower to Mosaic; a q block's band
    is 2 of the 8 kv blocks), and 48 over 8 with none (groups of six on the
    plain grouped kernels)."""
    _grouped_query_grad_compiles(t, heads, 8, dtype, False, window, one_chip)


@pytest.mark.parametrize("pairs,groups,hidden", [
    (4096 * 6, 16, 768), (8192 * 8, 16, 768), (8192 * 4, 8, 1792)])
def test_grouped_gated_mlp_grad_compiles_for_v5e(pairs, groups, hidden,
                                                 one_chip, mosaic):
    """The held experts' nine calls at the three expert cells' shapes
    (kanana, Keye, LFM2: a buffer for every pair of a step, 2048 wide, 16
    experts of 768 or 8 of 1792): grids that end at ``tiles_used``, read at
    run time; the weight block, the f32 d rhs block, the row tiles and the
    epilogues' g, u, dg and du tiles fit the 16 MB a kernel may use."""
    rows, d = gm.buffer_rows(pairs, groups), 2048
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)

    def both(buf, w_gate, w_up, w_down, tile_group, used, d_out):
        out, vjp = jax.vjp(lambda *a: gm.grouped_gated_mlp(
            *a, tile_group, used), buf, w_gate, w_up, w_down)
        return out, vjp(d_out)

    text = jax.jit(both).lower(
        spec((rows, d)), spec((groups, d, hidden)), spec((groups, d, hidden)),
        spec((groups, hidden, d)), spec((rows // gm.TILE_M,), jnp.int32),
        spec((1,), jnp.int32), spec((rows, d))).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="[^"]*/(dtpu_gmm\w*)/pallas_call"', text)
    assert sorted(calls) == (["dtpu_gmm"] * 3 + ["dtpu_gmm_nt"] * 3
                             + ["dtpu_gmm_tn"] * 3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_index_scores_gradient_compiles_for_v5e(dtype, one_chip, mosaic):
    """keye-vl2-30b.train.dsa8k: a block of 512 queries of the indexer's 16
    heads of 64 against 8,192 keys, the cotangent (512, 8192) float32: the
    heads in pairs, ``(w qi)^T``, G's accumulator, a key tile of 512 (256
    under float32 inputs) and its products fit the 16 MB a kernel may use,
    and every in-kernel slice is tile-aligned."""
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)

    def loss(qi, ki, w, d_scores, row0):
        return jnp.sum(ix.block_index_scores(qi, ki, w, row0) * d_scores)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec((512, 16, 64), dtype), spec((8192, 64), dtype),
        spec((512, 16), jnp.float32), spec((512, 8192), jnp.float32),
        spec((), jnp.int32)).compile().as_text()
    assert "dtpu_index_scores_bwd" in text
    assert not re.search(r"(?:f32|bf16|pred)\[512,16,8192\]", text)


@pytest.mark.parametrize("rotation", ["theta", "yarn64"])
@pytest.mark.parametrize("heads", [64, 48, 32, 8, 4])
def test_head_norm_rope_compiles_for_v5e(heads, rotation, one_chip, mosaic):
    """laguna-xs2.train.swa8k (64 and 48 query heads, 8 K/V heads; all 128
    dimensions at theta 1e4, or YaRN over 64 of them) and
    keye-vl2-30b.train.dsa8k (32 over 4) at 8,192 rows: both kernels' blocks
    and a row block's tables fit the 16 MB a kernel may use, the lane rolls
    lower, and no (T, H, 128) view is left."""
    from distributed_tpu.nn.attention import yarn_inv_freq

    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)

    def both(x, scale, g):
        rot = (yarn_inv_freq(64, 500000.0, factor=64.0,
                             original_max_position=4096, beta_fast=64.0),
               1.4159) if rotation == "yarn64" else (
            1.0 / (1e4 ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)),
            1.0)
        out, vjp = jax.vjp(lambda x, s: hn.head_norm_rope(
            x, s, rot, epsilon=1e-6), x, scale)
        return out, vjp(g)

    wide = spec((1, 8192, heads * 128), jnp.bfloat16)
    text = jax.jit(both).lower(
        wide, spec((128,), jnp.float32), wide).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="[^"]*/(dtpu_head\w*)/pallas_call"', text)
    assert sorted(calls) == ["dtpu_head_norm_rope", "dtpu_head_norm_rope_bwd"]
    assert not re.search(rf"(?:f32|bf16)\[1,8192,{heads},128\]", text)


@pytest.mark.parametrize("heads,dtype", [
    (64, jnp.bfloat16), (48, jnp.bfloat16),  # the cell's two head counts
    (64, jnp.float32), (8, jnp.bfloat16)])
def test_head_gate_compiles_for_v5e(heads, dtype, one_chip, mosaic):
    """laguna-xs2.train.swa8k's gate at 8,192 rows of 64 heads (sliding
    layers) and 48 (full ones): both kernels' blocks, all of a row block's
    heads, fit the 16 MB a kernel may use, the gate's one-lane columns lower,
    and no (T, H, 128) view is left."""
    spec = lambda shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(ctx, z, g):
        out, vjp = jax.vjp(hg.head_gate, ctx, z)
        return out, vjp(g)

    wide = spec((1, 8192, heads * 128))
    text = jax.jit(both).lower(wide, spec((1, 8192, heads)),
                               wide).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="[^"]*/(dtpu_head\w*)/pallas_call"', text)
    assert sorted(calls) == ["dtpu_head_gate", "dtpu_head_gate_bwd"]
    assert not re.search(rf"(?:f32|bf16)\[1,8192,{heads},128\]", text)


# ``%name = type opcode(`` of an instruction of the HLO text.
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.+?) ([\w\-]+)\(")


@pytest.mark.parametrize("shape,kv_heads,value_dim,window,selecting", [
    ((1, 8192, 64, 128), 8, 128, 512, False),   # Laguna's sliding layers
    ((1, 8192, 48, 128), 8, 128, None, False),  # its full layers
    ((1, 8192, 32, 128), 4, 128, None, True),   # Keye
    ((8, 1024, 16, 64), 16, 64, None, False),   # gpt2-medium
    ((1, 4096, 32, 192), 32, 128, None, False),  # kanana, folded
])
def test_flash_backward_makes_no_float32_head_view_for_v5e(
        shape, kv_heads, value_dim, window, selecting, one_chip, mosaic):
    """The backward alone (``jax.vjp``, its cotangent bf16, so no loss adds
    a float32 array): outside the ``dtpu_flash_*`` calls the entry
    computation makes no float32 array of T x H x D entries or more. The
    row statistic delta = sum(dO O) is contracted on the kernels' (b, T,
    H x D) layout: a sum over a float32 (b, T, H, D) view makes XLA:TPU
    write that view and relayout it."""
    b, t, h, d = shape
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    sel = spec((b, t, t), jnp.int8) if selecting else None

    def backward(q, k, v, sel, g):
        out, pull = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, selection=sel, window=window), q, k, v)
        return pull(g)

    text = jax.jit(backward).lower(
        spec(shape), spec((b, t, kv_heads, d)),
        spec((b, t, kv_heads, value_dim)), sel,
        spec((b, t, h, value_dim))).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")].splitlines()[2:]
    assert any("dtpu_flash_dq" in line for line in entry)
    views = []
    for line in entry:
        m = _INSTRUCTION.match(line)
        if m and not (m.group(2) == "custom-call" and "dtpu_flash" in line):
            views += [dims for dims in re.findall(r"f32\[([\d,]*)\]",
                                                  m.group(1))
                      if math.prod(int(n) for n in dims.split(",") if n)
                      >= t * h * value_dim]
    assert views == []
