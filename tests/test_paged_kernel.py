"""Fused paged-attention decode kernel (ops.paged_attention).

Two layers of parity pin the kernel:

- kernel-level matrix: the fused gather+attention output vs a dense
  gather-then-softmax reference over block_size x head_dim x dtype x
  candidate-width (the paged_verify K), including int8 {"q","scale"}
  pools dequantized in-kernel;
- engine-level token-exactness: ``Engine(decode_kernel="fused")`` must
  serve exactly the tokens the reference path serves across batch churn,
  preemption pressure, prefix-cache admission, int8 KV and speculative
  verify — plus the no-recompile contract across batch churn.

Also pins the ``_paged_view`` int8 mask-before-dequantize fix: rows the
causal mask can never expose dequantize to exact zeros, never
``garbage * scale``.

Kept lean for the 1-core tier-1 box: the kernel runs in Pallas interpret
mode here; heavy matrix cells are @slow.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_recompile

import distributed_tpu as dtpu
from distributed_tpu.ops import paged_attention as paged_ops
from distributed_tpu.quant import QKEY, SKEY, dequantize
from distributed_tpu.serving import Engine, Request


# ------------------------------------------------------- kernel-level matrix --
def _dense_ref(q, k_pool, v_pool, tables, positions):
    """Gather-then-dense reference: what the fused kernel must reproduce."""
    s, kw, h, hd = q.shape
    if isinstance(k_pool, dict):
        k_pool = dequantize(k_pool, q.dtype)
        v_pool = dequantize(v_pool, q.dtype)
    gk = np.asarray(k_pool)[tables]  # (s, nb, bs, h, hd)
    gv = np.asarray(v_pool)[tables]
    nb, bs = gk.shape[1], gk.shape[2]
    ll = nb * bs
    k = gk.reshape(s, ll, h, hd).astype(np.float32)
    v = gv.reshape(s, ll, h, hd).astype(np.float32)
    q32 = np.asarray(q).astype(np.float32)
    col = np.arange(ll)[None, None, :]
    row = (np.asarray(positions)[:, None] + np.arange(kw)[None, :])[..., None]
    vis = col <= row  # (s, kw, ll)
    sc = np.einsum("skhd,slhd->skhl", q32, k) / math.sqrt(hd)
    sc = np.where(vis[:, :, None, :], sc, -1e30)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("skhl,slhd->skhd", p, v)


def _quantize_pool(pool):
    """Row-wise per-(position, head) int8 pair, the KV-scatter scheme."""
    amax = np.max(np.abs(pool), axis=-1, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(pool / scale), -127, 127).astype(np.int8)
    return {QKEY: jnp.asarray(q), SKEY: jnp.asarray(scale)}


def _case(seed, s, nb, bs, h, hd, kw, dtype, int8=False):
    rng = np.random.default_rng(seed)
    nblocks = s * nb + 1
    kp = rng.standard_normal((nblocks, bs, h, hd)).astype(np.float32)
    vp = rng.standard_normal((nblocks, bs, h, hd)).astype(np.float32)
    q = rng.standard_normal((s, kw, h, hd)).astype(np.float32)
    # Every slot owns a disjoint table; positions spread across the span
    # (early rows leave whole blocks invisible — the masked-gather case).
    tables = (1 + np.arange(s * nb).reshape(s, nb)).astype(np.int32)
    positions = rng.integers(0, nb * bs - kw + 1, (s,)).astype(np.int32)
    if int8:
        k_pool, v_pool = _quantize_pool(kp), _quantize_pool(vp)
    else:
        k_pool = jnp.asarray(kp, dtype)
        v_pool = jnp.asarray(vp, dtype)
    return jnp.asarray(q, dtype), k_pool, v_pool, tables, positions


MATRIX = [
    # (block_size, head_dim, dtype, kw, int8, slow)
    (4, 4, jnp.float32, 1, False, False),
    (4, 8, jnp.float32, 3, False, False),
    (4, 4, jnp.bfloat16, 1, False, False),
    (4, 4, jnp.float32, 1, True, False),
    (4, 8, jnp.float32, 3, True, False),
    (8, 16, jnp.float32, 2, False, True),
    (16, 8, jnp.bfloat16, 3, False, True),
    (16, 4, jnp.bfloat16, 2, True, True),
]


@pytest.mark.parametrize(
    "bs,hd,dtype,kw,int8",
    [pytest.param(bs, hd, dt, kw, q8,
                  marks=[pytest.mark.slow] if slow else [],
                  id=f"bs{bs}-hd{hd}-{jnp.dtype(dt).name}-kw{kw}"
                     f"{'-int8' if q8 else ''}")
     for bs, hd, dt, kw, q8, slow in MATRIX],
)
def test_fused_kernel_matches_dense_reference(bs, hd, dtype, kw, int8):
    q, k_pool, v_pool, tables, positions = _case(
        seed=bs * 100 + hd + kw, s=3, nb=3, bs=bs, h=2, hd=hd, kw=kw,
        dtype=dtype, int8=int8)
    got = np.asarray(paged_ops.paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions)
    )).astype(np.float32)
    want = _dense_ref(q, k_pool, v_pool, tables, positions)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_kernel_ignores_trash_and_future_rows():
    """Poison every row the causal mask hides (the trash block and the
    positions past each slot's write head) with huge values: the output
    must not move. This is the failure mode the fused mask exists for —
    inactive table slots all point at block 0."""
    q, k_pool, v_pool, tables, positions = _case(
        seed=7, s=2, nb=2, bs=4, h=2, hd=4, kw=1, dtype=jnp.float32)
    clean = np.asarray(paged_ops.paged_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(positions)))
    kp = np.asarray(k_pool).copy()
    vp = np.asarray(v_pool).copy()
    kp[0] = 1e30  # trash block
    vp[0] = 1e30
    ll = tables.shape[1] * 4
    for s, pos in enumerate(positions):
        for j in range(int(pos) + 1, ll):  # rows past the write head
            kp[tables[s, j // 4], j % 4] = 1e30
            vp[tables[s, j // 4], j % 4] = 1e30
    poisoned = np.asarray(paged_ops.paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(positions)))
    np.testing.assert_array_equal(clean, poisoned)


def test_decode_kernel_scope_is_threadlocal_and_validated():
    assert paged_ops.current_decode_kernel() == paged_ops.REFERENCE
    with paged_ops.decode_kernel_scope(paged_ops.FUSED):
        assert paged_ops.current_decode_kernel() == paged_ops.FUSED
    assert paged_ops.current_decode_kernel() == paged_ops.REFERENCE
    with pytest.raises(ValueError, match="decode_kernel"):
        with paged_ops.decode_kernel_scope("bogus"):
            pass


# ------------------------------------------------- _paged_view int8 masking --
def test_paged_view_int8_masks_before_dequantize():
    """Invisible rows must dequantize to exact zeros (payload -> 0,
    scale -> 1) BEFORE the multiply: ``garbage * scale`` from the trash
    block or stale rows — including non-finite scales — must never reach
    the attention program."""
    mha = dtpu.nn.MultiHeadAttention(2)
    rng = np.random.default_rng(3)
    s, nb, bs, h, hd = 2, 2, 4, 2, 4
    pool = rng.standard_normal((s * nb + 1, bs, h, hd)).astype(np.float32)
    qpool = _quantize_pool(pool)
    tables = jnp.asarray(
        (1 + np.arange(s * nb).reshape(s, nb)).astype(np.int32))
    ll = nb * bs
    visible = jnp.asarray(
        np.arange(ll)[None, :] <= np.array([[2], [5]]))  # (s, ll)
    clean = np.asarray(
        mha._paged_view(qpool, tables, jnp.float32, visible=visible))
    # Poison the hidden rows with inf scales and max payloads.
    qq = np.asarray(qpool[QKEY]).copy()
    ss = np.asarray(qpool[SKEY]).copy()
    vis = np.asarray(visible)
    for si in range(s):
        for j in range(ll):
            if not vis[si, j]:
                qq[tables[si, j // bs], j % bs] = 127
                ss[tables[si, j // bs], j % bs] = np.inf
    poisoned = np.asarray(mha._paged_view(
        {QKEY: jnp.asarray(qq), SKEY: jnp.asarray(ss)}, tables,
        jnp.float32, visible=visible))
    assert np.all(np.isfinite(poisoned))
    np.testing.assert_array_equal(clean, poisoned)
    # And the hidden rows are exact zeros, bit-matching the fused kernel's
    # never-weighted treatment.
    assert np.array_equal(poisoned[~vis], np.zeros_like(poisoned[~vis]))


# --------------------------------------------------- engine token-exactness --
@pytest.fixture(scope="module")
def lm():
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=64))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((16,))
    return model


def _requests(seed=0, n=5, vocab=32, p_range=(1, 9), m_range=(3, 9)):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (int(t),)).astype(np.int32)
               for t in rng.integers(*p_range, n)]
    news = [int(m) for m in rng.integers(*m_range, n)]
    return prompts, news


def _run_both(lm, prompts, news, request_seeds=None, **kwargs):
    seeds = request_seeds or [None] * len(prompts)
    outs = {}
    for kind in (paged_ops.REFERENCE, paged_ops.FUSED):
        engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                        decode_kernel=kind, **kwargs)
        outs[kind] = engine.run(
            [Request(p, m, seed=s)
             for p, m, s in zip(prompts, news, seeds)])
    return outs


def _assert_token_exact(outs):
    for i, (w, g) in enumerate(zip(outs[paged_ops.REFERENCE],
                                   outs[paged_ops.FUSED])):
        assert np.array_equal(w, g), (
            f"request {i}: fused {list(g)} != reference {list(w)}")


def test_engine_fused_greedy_parity_with_churn(lm):
    """More requests than slots: admits mid-decode churn the batch
    composition while the fused kernel serves every dispatch."""
    prompts, news = _requests(seed=0, n=5)
    _assert_token_exact(_run_both(lm, prompts, news))


def test_engine_fused_sampled_seeded_parity(lm):
    """Sampling at a temperature from pinned seeds: both kernels must
    hand the sampler the same logits, or the streams part at the first
    token whose draw lands between them."""
    prompts, news = _requests(seed=3, n=4)
    # Without its own seed a request draws from its process-wide id, which
    # differs between the two engines' Request objects.
    outs = _run_both(lm, prompts, news, request_seeds=[0, 1, 2, 3],
                     temperature=0.8, seed=7)
    _assert_token_exact(outs)


def test_engine_fused_int8_kv_parity(lm):
    """The kernel matrix cells and
    test_paged_view_int8_masks_before_dequantize cover the dequantize
    alone; this is the engine end to end, with its own int8 decode
    compile."""
    prompts, news = _requests(seed=1, n=4)
    _assert_token_exact(_run_both(lm, prompts, news, kv_dtype="int8"))


def test_engine_fused_preemption_parity(lm):
    """Pool too small for the working set: victims are evicted and
    re-prefilled; the fused path must survive the re-admission. The
    pool (5 blocks = 4 usable at block_size 4) cannot back two contexts
    that grow past 13 tokens combined, so a running slot's mid-decode
    ``reserve`` fails and evicts the youngest — asserted via telemetry
    so the config can't silently stop exercising the path."""
    prompts, news = _requests(seed=2, n=4, m_range=(6, 10))
    outs = {}
    for kind in (paged_ops.REFERENCE, paged_ops.FUSED):
        engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                        num_blocks=5, decode_kernel=kind)
        outs[kind] = engine.run(
            [Request(p, m) for p, m in zip(prompts, news)])
        assert engine.last_run_telemetry["preemptions"] > 0, (
            f"{kind}: pool never hit pressure — preemption not exercised")
    _assert_token_exact(outs)


def test_engine_fused_prefix_cache_parity(lm):
    """Shared leading span: prefix-store admission hands the fused path
    refcounted blocks it never prefilled itself."""
    rng = np.random.default_rng(4)
    common = rng.integers(0, 32, (8,)).astype(np.int32)
    prompts = [np.concatenate([common,
                               rng.integers(0, 32, (int(t),)).astype(np.int32)])
               for t in rng.integers(1, 5, 4)]
    news = [5, 6, 4, 7]
    _assert_token_exact(_run_both(lm, prompts, news, prefix_cache=True))


@pytest.mark.slow
def test_engine_fused_spec_verify_parity(lm):
    """Speculative decoding: the K-candidate verify dispatch goes through
    the fused kernel's kw > 1 path."""
    draft = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=1, d_model=8, num_heads=2, max_len=64))
    draft.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    draft.build((16,))
    prompts, news = _requests(seed=5, n=4)
    _assert_token_exact(
        _run_both(lm, prompts, news, draft_model=draft, spec_k=3))


def test_engine_fused_no_recompile_on_batch_churn(lm):
    """The fused decode/verify dispatches jit once: a second run with a
    different request mix must reuse the compiled programs."""
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    decode_kernel="fused")
    prompts, news = _requests(seed=6, n=4)
    engine.run([Request(p, m) for p, m in zip(prompts, news)])
    prompts2, news2 = _requests(seed=7, n=5, p_range=(2, 12))
    with assert_no_recompile(engine._decode_jit):
        engine.run([Request(p, m) for p, m in zip(prompts2, news2)])


def test_engine_validates_decode_kernel(lm):
    with pytest.raises(ValueError, match="decode_kernel"):
        Engine(lm, max_slots=2, block_size=4, decode_kernel="bogus")


def test_engine_programs_selects_kernel(lm):
    from distributed_tpu.fleet.replica import EnginePrograms
    progs = EnginePrograms(lm, decode_kernel="fused")
    assert progs.decode_kernel == "fused"
    with pytest.raises(ValueError, match="decode_kernel"):
        EnginePrograms(lm, decode_kernel="bogus")
