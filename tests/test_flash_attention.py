"""Flash attention: numerics vs dense (values AND grads), shard_map routing.

The kernel runs in Pallas interpret mode on CPU (same semantics as the
Mosaic build on TPU). The sharding tests compile under the 8-device sim and
assert GSPMD never all-gathers the kernel inputs — the failure mode
parallel.auto_shard exists to prevent.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.ops.flash_attention import flash_attention


def dense_attention(q, k, v, causal):
    b, t, h, d = q.shape
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


def _qkv(shape, dtype=jnp.float32, seed=0, value_dim=None):
    """q, k of ``shape`` and v of it with ``value_dim`` columns a head."""
    rng = np.random.default_rng(seed)
    v_shape = shape[:3] + (value_dim or shape[3],)
    return tuple(
        jnp.asarray(rng.standard_normal(s), dtype)
        for s in (shape, shape, v_shape)
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 64, 2, 16), (1, 100, 3, 32)])
def test_matches_dense_values_and_grads(shape, causal):
    q, k, v = _qkv(shape)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
            * v
        )

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal) * v)

    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def test_ragged_seq_and_uneven_blocks():
    # T=257: padding rows/cols must not leak into real outputs.
    q, k, v = _qkv((1, 257, 2, 64))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)


def test_incompatible_blocks_are_repaired():
    """Mismatched block sizes are clamped to a compatible pair instead of
    silently dropping trailing rows (the grid must cover all of T)."""
    q, k, v = _qkv((1, 256, 1, 16))
    out = flash_attention(q, k, v, causal=True, block_q=96, block_k=128)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)


def test_default_blocks_midsize_sequences():
    """Default block sizes on 512 <= T < 1024 (where flash='auto' kicks in):
    block_k is clamped to the q-rounded length so padded work stays within
    one q-block, and padding must not leak into outputs."""
    for t in (513, 600):
        q, k, v = _qkv((1, t, 1, 32))
        out = flash_attention(q, k, v, causal=True)  # default blocks
        ref = dense_attention(q, k, v, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-5)


@pytest.mark.smoke
def test_bf16_inputs():
    q, k, v = _qkv((2, 128, 2, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2
    )


def test_no_allgather_under_dp_mesh(devices):
    """shard_rows must keep the kernel per-shard: compiling under a
    'data'-sharded batch may not introduce an all-gather of q/k/v."""
    strategy = dtpu.DataParallel()
    b, t, h, d = 16, 64, 2, 32
    q, k, v = _qkv((b, t, h, d))
    batch = strategy.put_batch({"x": np.asarray(q)})
    qs = batch["x"]

    from jax.sharding import PartitionSpec as P

    from distributed_tpu.parallel.auto_shard import shard_rows

    def call(q, k, v):
        with strategy.scope():
            spec = P("data", None, None, None)
            return shard_rows(
                lambda a, b2, c: flash_attention(
                    a, b2, c, causal=True, block_q=32, block_k=32
                ),
                (q, k, v), (spec, spec, spec), spec,
            )

    f = jax.jit(call)
    hlo = f.lower(qs, k, v).compile().as_text()
    assert "all-gather" not in hlo
    out = f(qs, k, v)
    ref = dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-5)


def test_mha_flash_equals_dense_model_level(devices):
    """A transformer LM with flash=True in every MHA must match the dense
    attention model's loss exactly enough for training parity."""
    import distributed_tpu.nn as nn

    def make(flash):
        return nn.Sequential([
            nn.Embedding(64, 32),
            nn.MultiHeadAttention(4, causal=True, flash=flash),
            nn.Dense(64),
        ])

    x = np.asarray(
        np.random.default_rng(0).integers(0, 64, (8, 96)), np.int32
    )
    ma, mb = make(True), make(False)
    pa, sa, _ = ma.init(jax.random.PRNGKey(0), (96,))
    logits_a, _ = ma.apply(pa, {}, x)
    logits_b, _ = mb.apply(pa, {}, x)  # identical params
    np.testing.assert_allclose(logits_a, logits_b, atol=2e-4, rtol=1e-4)


def test_fused_xent_sharded_no_allgather(devices):
    """The Pallas loss inside a DP step must also stay per-shard."""
    from distributed_tpu.ops.pallas_kernels import (
        pallas_sparse_categorical_crossentropy,
    )

    strategy = dtpu.DataParallel()
    n, c = 64, 32
    rng = np.random.default_rng(0)
    logits = np.asarray(rng.standard_normal((n, c)), np.float32)
    labels = np.asarray(rng.integers(0, c, (n,)), np.int32)
    batch = strategy.put_batch({"x": logits, "y": labels})

    def loss(lg, lb):
        with strategy.scope():
            return pallas_sparse_categorical_crossentropy(lg, lb)

    f = jax.jit(loss)
    hlo = f.lower(batch["x"], batch["y"]).compile().as_text()
    assert "all-gather" not in hlo
    got = float(f(batch["x"], batch["y"]))
    from distributed_tpu.ops import losses

    want = float(losses.sparse_categorical_crossentropy(logits, labels))
    assert abs(got - want) < 1e-5


# (shape, block_q, block_k, sub-tile side): the sub-tile walk, lane-packed.
# The side is a module constant sized for the chip (a lane tile at least);
# here it is patched down so that every sub-tile class (skipped, unmasked,
# masked by the diagonal or by the padding edge) occurs at interpret-mode
# sizes, in a grid of one block and in a grid of several.
PACKED_CASES = [
    ((2, 64, 2, 64), 32, 32, None),    # head_dim 64: two heads per lane block
    ((1, 100, 1, 128), 32, 32, None),  # head_dim 128: one head, ragged T
    ((2, 72, 4, 64), 32, 32, None),    # multiple head blocks, ragged T
    ((1, 128, 2, 64), 128, 128, 32),   # one block of 4 x 4 sub-tiles
    ((1, 120, 2, 64), 128, 128, 32),   # ... whose last column the edge crosses
    ((2, 90, 1, 128), 128, 128, 32),   # ... and whose last lies in the padding
    ((1, 256, 2, 64), 64, 128, 32),    # block_q != block_k, several blocks
    ((1, 250, 4, 64), 128, 64, 32),    # block_q > block_k, ragged T
    ((1, 192, 2, 64), 64, 64, 16),     # square blocks: diagonal and below
]


@pytest.fixture
def subtile(monkeypatch):
    """``subtile(side)`` sets the packed kernels' sub-tile side for one test
    (None: the module's own) and returns the module. The side is static
    configuration, so the cached custom_vjp functions go with it."""
    from distributed_tpu.ops import flash_attention as fa

    def clear():
        fa._flash_cached.cache_clear()
        fa.subtile_counts.cache_clear()

    def patch(side):
        if side is not None:
            monkeypatch.setattr(fa, "_SUBTILE", side)
        clear()
        return fa

    yield patch
    clear()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,block_q,block_k,side", PACKED_CASES)
def test_packed_layout_matches_dense_values_and_grads(
        shape, block_q, block_k, side, causal, subtile):
    """The lane-packed (B,T,H*D) kernels (head_dim 64/128 — no transposes)
    must match dense attention in values AND all three gradients."""
    fa = subtile(side)
    assert fa._packed_supported(shape[2], shape[3])
    q, k, v = _qkv(shape, seed=3)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    out = flash(q, k, v)
    want = dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash(q, k, v)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.sin(dense_attention(q, k, v, causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


# (shape, value width, block_q, block_k, sub-tile side, dtype): the same walk
# in the folded layout, one head a block, at head counts and widths the
# packed layout refuses: a value width of its own (latent attention: 192-wide
# keys in 256 lanes, 128-wide values, and a sub-tile of twice the side), an
# odd head count at head_dim 64 (where 1/sqrt(d) folds into q), f32 and bf16
# inputs.
FOLDED_CASES = [
    ((1, 128, 2, 48), 32, 128, 128, 32, jnp.float32),  # one block, 4 x 4
    ((1, 120, 2, 48), 48, 128, 128, 32, jnp.float32),  # the edge crosses
    ((2, 90, 1, 48), 32, 128, 128, 32, jnp.float32),   # a column in padding
    ((1, 256, 2, 48), 32, 64, 128, 32, jnp.float32),   # block_q < block_k
    ((1, 250, 3, 48), 32, 128, 64, 32, jnp.float32),   # block_q > block_k
    ((1, 192, 3, 64), 64, 64, 64, 16, jnp.float32),    # square blocks, folds
    ((1, 200, 2, 192), 128, 128, 128, 32, jnp.float32),  # 192 / 128, ragged
    ((1, 256, 2, 192), 128, 128, 256, 32, jnp.bfloat16),
    ((2, 120, 3, 64), 64, 128, 128, 32, jnp.bfloat16),
    ((1, 200, 2, 48), 32, 64, 64, None, jnp.bfloat16),  # whole-block tiles
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,value_dim,block_q,block_k,side,dtype",
                         FOLDED_CASES)
def test_folded_layout_matches_dense_values_and_grads(
        shape, value_dim, block_q, block_k, side, dtype, causal, subtile):
    """The same kernels in the folded (B*H, T, D) layout must match dense
    attention in values AND all three gradients. bf16 inputs are held to
    the dense path in f32 on the same rounded inputs, at bf16's grain."""
    fa = subtile(side)
    assert not (value_dim == shape[3]
                and fa._packed_supported(shape[2], shape[3]))
    q, k, v = _qkv(shape, dtype, seed=4, value_dim=value_dim)
    f32 = lambda xs: [x.astype(jnp.float32) for x in xs]
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else dict(
        rtol=2e-2, atol=2e-2)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    out = flash(q, k, v)
    assert out.shape == v.shape and out.dtype == dtype
    want = dense_attention(*f32((q, k, v)), causal)
    np.testing.assert_allclose(*f32((out, want)), **tol)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attn(q, k, v).astype(jnp.float32)))

    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, causal)),
                  argnums=(0, 1, 2))(*f32((q, k, v)))
    if dtype == jnp.float32:
        tol = dict(rtol=3e-4, atol=3e-4)
    for a, b in zip(f32(gf), gd):
        np.testing.assert_allclose(a, b, **tol)


def test_passes_merge_like_rows_within_the_vmem_budget(subtile):
    """Rows of sub-tiles whose columns are classed alike are one pass, as
    tall as the budget of scores allows with the heads alive together; rows
    the diagonal classes differently stay apart; rows nothing is seen of
    are one empty pass; dk/dv's passes run down the columns."""
    fa = subtile(None)
    passes = lambda blocks, heads, diag, **kw: fa._passes(
        blocks, (128, 128), heads, diag, None, **kw)
    assert passes((512, 1024), 1, None) == [(0, 512, [(0, 1024, False)])]
    assert passes((512, 1024), 1, None, kv_major=True) == [
        (0, 1024, [(0, 512, False)])]
    assert [p[:2] for p in passes((1024, 1024), 2, None)] == [
        (0, 512), (512, 1024)]
    assert passes((384, 384), 1, 0) == [
        (0, 128, [(0, 128, True)]),
        (128, 256, [(0, 128, False), (128, 256, True)]),
        (256, 384, [(0, 256, False), (256, 384, True)])]
    assert passes((384, 384), 1, 0, kv_major=True) == [
        (0, 128, [(0, 128, True), (128, 384, False)]),
        (128, 256, [(128, 256, True), (256, 384, False)]),
        (256, 384, [(256, 384, True)])]
    assert passes((384, 384), 1, -256) == [
        (0, 256, []), (256, 384, [(0, 128, True)])]


def test_folded_cases_meet_every_class_and_view(subtile):
    """The cases above put the walk through everything it distinguishes:
    every sub-tile class, a grid of one block and of several, and each way
    a grid block can lie (wholly seen, on the diagonal at its corner, on it
    further along, across the padding edge)."""
    classes, views, grids = set(), set(), set()
    for shape, _, block_q, block_k, side, _ in FOLDED_CASES:
        fa = subtile(side)
        t = shape[1]
        t_pad = -(-t // max(block_q, block_k)) * max(block_q, block_k)
        nq, nk = t_pad // block_q, t_pad // block_k
        grids.add(nq * nk > 1)
        sub_q, sub_k = (fa._subtile(x, fa._lane_pad(shape[3]))
                        for x in (block_q, block_k))
        assert block_q // sub_q * (block_k // sub_k) > 1 or side is None
        for causal in (False, True):
            for (diag, edge), _ in fa._block_views(
                    nq, nk, block_q, block_k, t, causal):
                views.add(("seen" if diag is None else
                           "corner" if diag == 0 else "along")
                          if edge is None else "edge")
                classes |= {
                    fa._tile_class(r0, sub_q, c0, sub_k, diag, edge)
                    for r0 in range(0, block_q, sub_q)
                    for c0 in range(0, block_k, sub_k)}
    assert classes == {fa._SKIPPED, fa._MASKED, fa._UNMASKED}
    assert views == {"seen", "corner", "along", "edge"}
    assert grids == {False, True}


@pytest.mark.parametrize("t,blocks,causal,lanes,want", [
    (1024, (1024, 1024), True, 128, (64, 36, 8)),    # both GPT-2 cells
    (1024, (1024, 1024), False, 128, (64, 64, 0)),
    (1000, (1024, 1024), False, 128, (64, 64, 8)),   # the padding edge
    (700, (1024, 1024), False, 128, (64, 48, 8)),  # two columns in padding
    (4096, (512, 1024), True, 128, (1024, 528, 32)),  # the bf16 clamp shape
    (4000, (512, 1024), False, 128, (1024, 1024, 32)),  # ... its padding edge
    # kanana2-30b.train.ep8share: 192-wide keys sit in 256 lanes, and the
    # sub-tile is a lane tile of the head block: 8 x 4 grid blocks a head
    (4096, (512, 1024), True, 256, (256, 136, 16)),
    (600, (600, 600), True, 128, (1, 1, 1)),       # ragged block: one sub-tile
])
def test_subtile_counts(t, blocks, causal, lanes, want):
    """The static count of the sub-tile walk, at the module's own side."""
    from distributed_tpu.ops.flash_attention import _SUBTILE, subtile_counts

    assert _SUBTILE == 128
    assert subtile_counts(t, *blocks, causal, lanes) == want


@pytest.mark.parametrize("shape,value_dim,want", [
    ((1, 1024, 2, 64), 64, [64.0, 36.0, 8.0]),         # packed: GPT-2 cells
    ((1, 4096, 32, 192), 128, [256.0, 136.0, 16.0]),  # folded: kanana cell
])
def test_subtile_gauges_published(shape, value_dim, want):
    """flash_attention publishes the counts at trace time, as gauges, from
    the packed and the folded branch alike."""
    from distributed_tpu import obs

    reg = obs.default_registry()
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape[:3] + (value_dim,), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, v)
    assert [reg.gauge_value(f"flash.subtiles_{n}")
            for n in ("square", "computed", "masked")] == want


def _dot_operand_dtypes(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.add(tuple(str(v.aval.dtype) for v in eqn.invars))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_operand_dtypes(sub, found)
    return found


@pytest.mark.parametrize("heads,head_dim,value_dim", [
    (2, 64, 64), (1, 128, 128),  # packed
    (3, 64, 64), (2, 192, 128),  # folded: odd heads; latent attention
])
def test_kernels_feed_the_mxu_bf16(heads, head_dim, value_dim):
    """Every matrix product of the three kernels takes bf16 operands from
    bf16 inputs: the scale folded into q (head_dim 64: a power of two) must
    not promote it (a NumPy scalar is no weak type), and head_dim 128 and
    192 keep their f32 multiply on the scores."""
    q = jax.ShapeDtypeStruct((1, 512, heads, head_dim), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 512, heads, value_dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
    assert _dot_operand_dtypes(jaxpr.jaxpr, set()) == {
        ("bfloat16", "bfloat16")}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,value_dim,blocks,side", [
    ((1, 96, 2, 64), 64, (32, 32), None),   # several blocks: from the scratch
    ((1, 120, 2, 64), 64, (128, 128), 32),  # one block, walked in sub-tiles
    ((2, 100, 1, 128), 128, (64, 64), 32),  # one head a lane block, ragged T
    ((1, 96, 3, 48), 32, (32, 32), None),   # folded: several blocks
    ((2, 120, 2, 192), 128, (128, 128), 32),  # folded: 192 / 128, one block
])
def test_residual_statistic(shape, value_dim, blocks, side, causal, subtile):
    """What the forward saves for the backward beside its inputs and output
    is ONE row statistic, lse = m + log l, 4 bytes a row and head, laid out
    lane-major (b, head blocks, heads a block, t_pad), packed (b = B) or
    folded (b = B*H, one head a block): it must be the log-sum-exp of the
    dense scores on every real row."""
    fa = subtile(side)
    b, t, h, d = shape
    q, k, v = _qkv(shape, seed=5, value_dim=value_dim)
    if value_dim == d and fa._packed_supported(h, d):
        rows, heads, hpb = b, h, 128 // d
        lay = lambda x: x.reshape(b, t, -1)
    else:
        rows, heads, hpb = b * h, 1, 1
        lay = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, -1)
    out, lse = fa._fwd_pallas(
        lay(q), lay(k), lay(v), heads=heads, hpb=hpb, suffix="",
        causal=causal, block_q=blocks[0], block_k=blocks[1])
    assert out.shape == lay(v).shape
    assert lse.shape[:3] == (rows, heads // hpb, hpb)
    assert lse.dtype == jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)  # (b, h, t)
    np.testing.assert_allclose(
        np.asarray(lse[..., :t]).reshape(b, h, t), np.asarray(want),
        rtol=2e-5, atol=2e-5)
