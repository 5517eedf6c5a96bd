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


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _dot_operand_dtypes(jaxpr):
    return {tuple(str(v.aval.dtype) for v in eqn.invars)
            for eqn in _eqns(jaxpr) if eqn.primitive.name == "dot_general"}


def _dots_outside_kernels(jaxpr):
    """(operand dtypes, precision) of each matrix product of the jaxpr that
    no ``pallas_call`` holds."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append((tuple(str(v.aval.dtype) for v in eqn.invars),
                          eqn.params["precision"]))
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dots_outside_kernels(sub)
    return found


@pytest.mark.parametrize("heads,head_dim,value_dim", [
    (2, 64, 64), (1, 128, 128),  # packed
    (3, 64, 64), (2, 192, 128),  # folded: odd heads; latent attention
])
def test_kernels_feed_the_mxu_bf16(heads, head_dim, value_dim):
    """Every matrix product of the three kernels takes bf16 operands from
    bf16 inputs: the scale folded into q (head_dim 64: a power of two) must
    not promote it (a NumPy scalar is no weak type), and head_dim 128 and
    192 keep their f32 multiply on the scores. The one product outside them
    is the backward's delta = sum(dO O), f32 products summed at HIGHEST."""
    q = jax.ShapeDtypeStruct((1, 512, heads, head_dim), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 512, heads, value_dim), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v)
    kernels = [e.params["jaxpr"] for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 3
    assert set().union(*map(_dot_operand_dtypes, kernels)) == {
        ("bfloat16", "bfloat16")}
    highest = (jax.lax.Precision.HIGHEST,) * 2
    assert _dots_outside_kernels(jaxpr.jaxpr) == [
        (("float32", "float32"), highest)]


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


# (B, H, K/V heads, D, Dv, window, a selection) of each layout the backward
# forms its delta in: packed at 128-wide heads (grouped, with a selection,
# with a window), packed at 64, folded at 192 / 128.
DELTA_LAYOUTS = {
    "grouped128": (2, 4, 2, 128, 128, None, False),
    "selecting128": (2, 4, 2, 128, 128, None, True),
    "window128": (2, 4, 2, 128, 128, 64, False),
    "packed64": (2, 4, 4, 64, 64, None, False),
    "folded": (2, 2, 2, 192, 128, None, False),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("layout", list(DELTA_LAYOUTS))
def test_backward_delta_is_the_sum_of_products(layout, dtype, monkeypatch):
    """delta_i = sum_j dO_ij O_ij, a row and head, as the dq and dk/dv
    kernels receive it ((b, head blocks, heads a block, t_pad), lane-major):
    the float32 products' sum to within 1e-6 of sum_j |dO_ij O_ij| on every
    real row, and 0 on the rows that pad T = 200 to the blocks' 256."""
    from distributed_tpu.ops import flash_attention as fa

    b, h, kv, d, dv, window, selecting = DELTA_LAYOUTS[layout]
    t, blocks = 200, dict(block_q=64, block_k=128)
    rng = np.random.default_rng(3)
    arrays = [jnp.asarray(rng.standard_normal((b, t, n, w)), dtype)
              for n, w in ((h, d), (kv, d), (kv, dv))]
    if dv == d and fa._packed_supported(h, d):
        rows, heads, hpb, group = b, h, 128 // d, h // kv
        lay = lambda x: x.reshape(b, t, -1)
    else:
        rows, heads, hpb, group = b * h, 1, 1, 1
        lay = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, -1)
    q, k, v = map(lay, arrays)
    selection = None
    if selecting:
        sel = _spread_selection(t)
        selection = (fa.selection_blocks(sel, **blocks)[0].reshape(-1), sel)
    static = dict(heads=heads, hpb=hpb, causal=True, group=group,
                  window=window, suffix="_swa" if window else "", **blocks)
    out, lse = fa._fwd_pallas(q, k, v, selection, **static)
    g = jnp.asarray(rng.standard_normal(out.shape), dtype)

    seen = {}
    call = fa._call

    def spy(*args):  # a kernel's call, its name and last operand recorded
        run = call(*args)

        def record(*operands):
            seen[args[7]] = operands[-1]
            return run(*operands)
        return record

    monkeypatch.setattr(fa, "_call", spy)
    fa._bwd_pallas((q, k, v, out, lse, selection), g, **static)
    deltas = [np.asarray(x) for name, x in seen.items()
              if name.startswith(("dtpu_flash_dq", "dtpu_flash_dkv"))]
    assert len(deltas) == 2 and np.array_equal(*deltas)
    delta = deltas[0]
    assert delta.shape == (rows, heads // hpb, hpb, 256)
    assert delta.dtype == np.float32
    products = np.asarray(g.astype(jnp.float32) * out.astype(jnp.float32),
                          np.float64).reshape(rows, t, heads, -1)
    want, scale = products.sum(-1), np.abs(products).sum(-1)
    got = delta[..., :t].reshape(rows, heads, t).transpose(0, 2, 1)
    assert np.all(np.abs(got - want) <= 1e-6 * scale)
    assert not np.any(delta[..., t:])


# ---------------------------------------------------- grouped query heads --
# 128-wide heads in the lane-packed layout, ``group`` query heads a K/V head:
# the forward's and dq's grids walk a group's heads innermost, on the K, V and
# selection blocks the first of them fetched, and dk/dv's its q blocks
# head-minor. Blocks of (64, 128) at T = 256 (or 200, padded to it): four q
# blocks and two kv blocks, so the scratch a head is carried and read back.
GROUP_BLOCKS = dict(block_q=64, block_k=128)


def _grouped_qkv(group, t, b=2, kv_heads=2, seed=7):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, heads, 128)), jnp.float32)
        for heads in (group * kv_heads, kv_heads, kv_heads,
                      group * kv_heads))  # q, k, v and the output's weights


def _spread_selection(t, b=2, seed=11, density=0.3):
    """(b, t, t) int8, causal with the diagonal kept, spread over the keys."""
    rng = np.random.default_rng(seed)
    sel = np.tril((rng.random((b, t, t)) < density) | np.eye(t, dtype=bool))
    return jnp.asarray(sel, jnp.int8)


def _close(got, want, rel=2e-5):
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) < rel * scale + 1e-7


@pytest.mark.parametrize("t", [256, 200])
@pytest.mark.parametrize("selecting", [True, False])
@pytest.mark.parametrize("group", [2, 4, 8])
def test_grouped_kernels_match_dense_attention(group, selecting, t):
    """Values, the row statistic and all three gradients of the kernels that
    share a K/V head's blocks among its query heads, with a selection and
    without, at a T the blocks divide and one they pad."""
    from distributed_tpu.ops import flash_attention as fa

    q, k, v, w = _grouped_qkv(group, t)
    sel = _spread_selection(t) if selecting else None
    flash = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, selection=sel, **GROUP_BLOCKS))
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
        q, k, v, True, sel))
    _close(jax.value_and_grad(flash, (0, 1, 2))(q, k, v),
           jax.value_and_grad(dense, (0, 1, 2))(q, k, v))
    want = fa.dense_attention(q, k, v, True, sel, return_lse=True)[1]
    if selecting:
        lse = flash_attention(q, k, v, causal=True, selection=sel,
                              return_lse=True, **GROUP_BLOCKS)[1]
    else:
        flat = lambda x: x.reshape(*x.shape[:2], -1)
        lse = fa._fwd_pallas(
            flat(q), flat(k), flat(v), heads=q.shape[2], hpb=1, suffix="",
            causal=True, group=group, **GROUP_BLOCKS)[1][:, :, 0, :t]
    _close(lse, want)


def test_a_cleared_flag_skips_the_walk_for_every_head_of_the_group():
    """A grid block whose flag is 0 runs no walk in any of the three kernels,
    for each head that shares it: with the flag of a block that does hold
    selected pairs cleared by hand, the kernels agree with the dense path
    over a selection that lacks those pairs; and a block the selection
    itself leaves empty is flagged so."""
    from distributed_tpu.ops import flash_attention as fa

    group, t = 4, 256
    q, k, v, w = _grouped_qkv(group, t)
    sel = _spread_selection(t).at[0, 128:192, :128].set(0)
    flags, _ = fa.selection_blocks(sel, 64, 128)
    assert not flags[0, 2, 0] and flags[0, 3, 0] and flags[1, 3, 0]
    flags = flags.at[1, 3, 0].set(0)
    without = sel.at[1, 192:, :128].set(0)
    flash = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, selection=sel, selection_flags=flags,
        **GROUP_BLOCKS))
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
        q, k, v, True, without))
    _close(jax.value_and_grad(flash, (0, 1, 2))(q, k, v),
           jax.value_and_grad(dense, (0, 1, 2))(q, k, v))


def _kernel_specs(heads, kv_heads, selecting, t=256, d=128, value_dim=None,
                  blocks=GROUP_BLOCKS):
    """{kernel name: (grid, [(block shape, {grid step: block index})])} of
    the three ``pallas_call``s in the gradient of a causal call at B = 2,
    every operand and result in the call's order, the steps in the grid's."""
    shape = lambda h, w: jax.ShapeDtypeStruct((2, t, h, w), jnp.float32)
    sel = jax.ShapeDtypeStruct((2, t, t), jnp.int8) if selecting else None
    loss = lambda q, k, v, sel: jnp.sum(flash_attention(
        q, k, v, causal=True, selection=sel, **blocks))
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        shape(heads, d), shape(kv_heads, d),
        shape(kv_heads, value_dim or d), sel)
    out = {}
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        mapping = eqn.params["grid_mapping"]
        flags = [np.zeros(1, np.int32)] * mapping.num_index_operands
        steps = list(np.ndindex(*mapping.grid))
        out[eqn.params["name"]] = (mapping.grid, [
            (tuple(dim.block_size for dim in bm.block_shape),
             {g: tuple(int(i) for i in jax.core.eval_jaxpr(
                 bm.index_map_jaxpr.jaxpr, bm.index_map_jaxpr.consts,
                 *g, *flags)) for g in steps})
            for bm in mapping.block_mappings])
    return out


@pytest.mark.parametrize("heads,d,value_dim,selecting,suffix", [
    (2, 128, 128, True, "_sel"),    # one head a block, with a selection
    (2, 128, 128, False, "_packed"),
    (4, 64, 64, False, "_packed"),  # two heads a block
    (2, 192, 128, False, ""),       # folded: b = B * H, 256 and 128 lanes
])
def test_one_head_a_kv_head_keeps_the_grids_and_specs(
        heads, d, value_dim, selecting, suffix):
    """Without grouped queries the three calls get the (b, head block, i, j)
    grids, the one-head-block specs and the index maps they always had."""
    specs = _kernel_specs(heads, heads, selecting, d=d, value_dim=value_dim)
    b, nh = (4, 1) if suffix == "" else (2, heads * d // 128)
    w, wv = (256, 128) if suffix == "" else (128, 128)
    q_at = lambda b, h, qi, ki: (b, qi, h)
    kv_at = lambda b, h, qi, ki: (b, ki, h)
    stat_at = lambda b, h, qi, ki: (b, h, 0, qi)
    blocks = {"q": ((1, 64, w), q_at), "o": ((1, 64, wv), q_at),
              "k": ((1, 128, w), kv_at), "v": ((1, 128, wv), kv_at),
              "stat": ((1, 1, 128 // max(d, 64) if suffix else 1, 64),
                       stat_at)}
    operands = {"fwd": "q k v o stat", "dq": "q k v o stat stat q",
                "dkv": "q k v o stat stat k v"}
    for kernel, names in operands.items():
        grid, got = specs[f"dtpu_flash_{kernel}{suffix}"]
        dkv = kernel == "dkv"
        assert grid == (b, nh, *((2, 4) if dkv else (4, 2)))
        want = [blocks[n] for n in names.split()]
        if selecting:  # after the inputs; transposed for dk/dv
            want.insert(3 if kernel == "fwd" else 6, (
                (1, 128, 64) if dkv else (1, 64, 128),
                lambda b, h, qi, ki: (b, ki, qi) if dkv else (b, qi, ki)))
        assert [shape for shape, _ in got] == [shape for shape, _ in want]
        for (_, table), (_, at) in zip(got, want):
            assert table == {
                g: at(g[0], g[1], *(g[:1:-1] if dkv else g[2:]))
                for g in table}


def _changes(table):
    """How often a block's index changes over a grid walked in order: the
    copies the pipeline makes of it."""
    seen = list(table.values())
    return 1 + sum(a != b for a, b in zip(seen, seen[1:]))


@pytest.mark.parametrize("group", [1, 2, 4])
def test_kv_blocks_are_fetched_once_a_kv_head(group):
    """``kv_block_fetches`` against the calls' own index maps: walking each
    grid in order, the forward's and dq's K, V and selection blocks, and
    dk/dv's selection block, change once for all the heads of a group."""
    from distributed_tpu.ops.flash_attention import kv_block_fetches

    specs = _kernel_specs(2 * group, 2, True)
    fetches, a_head = kv_block_fetches(2, 2 * group, group, 4, 2)
    assert (fetches, a_head) == (2 * 2 * 4 * 2, 2 * 2 * group * 4 * 2)
    for kernel, sel_at in (("fwd", 3), ("dq", 6)):
        _, blocks = specs[f"dtpu_flash_{kernel}_sel"]
        assert [_changes(blocks[i][1]) for i in (1, 2, sel_at)] == [fetches] * 3
    _, blocks = specs["dtpu_flash_dkv_sel"]
    assert _changes(blocks[6][1]) == fetches  # the selection, transposed
    assert _changes(blocks[1][1]) == 2 * 2 * 2  # K: once a K/V head and block


@pytest.mark.parametrize("group", [1, 4])
def test_a_single_kv_block_is_fetched_once_a_kv_head(group):
    """One kv block and four q blocks: K and V never move under a K/V head,
    which is what ``kv_block_fetches`` counts; the selection's block moves
    with the q block, four times as often, and once for a group's heads."""
    from distributed_tpu.ops.flash_attention import kv_block_fetches

    specs = _kernel_specs(2 * group, 2, True,
                          blocks=dict(block_q=64, block_k=256))
    fetches, a_head = kv_block_fetches(2, 2 * group, group, 4, 1)
    assert (fetches, a_head) == (2 * 2, 2 * 2 * group)
    for kernel, sel_at in (("fwd", 3), ("dq", 6)):
        grid, blocks = specs[f"dtpu_flash_{kernel}_sel"]
        assert grid == ((2, 2, 4, 1, 4) if group > 1 else (2, 2, 4, 1))
        assert [_changes(blocks[i][1]) for i in (1, 2)] == [fetches] * 2
        assert _changes(blocks[sel_at][1]) == 4 * fetches


# --------------------------------------------- fewer heads a fetch than all --
@pytest.fixture
def vmem(monkeypatch):
    """Set what ``_heads_a_fetch`` takes a kernel's VMEM to be, with no
    function traced under another budget left in ``_flash_cached``."""
    from distributed_tpu.ops import flash_attention as fa

    fa._flash_cached.cache_clear()
    yield lambda n: monkeypatch.setattr(fa, "_VMEM", n)
    fa._flash_cached.cache_clear()


@pytest.mark.parametrize("group,block_q,block_k,itemsize,share", [
    (8, 512, 1024, 2, 8),    # keye-vl2-30b.train.dsa8k: the whole group
    (8, 1024, 1024, 2, 2),   # its layer at T <= 2048, bf16: block_q 1024
    (8, 512, 1024, 4, 4),    # float32 inputs
    (16, 512, 1024, 2, 8),   # half of a group of sixteen
    (32, 512, 1024, 4, 4),   # multi-query attention, float32
    (6, 512, 1024, 2, 6), (6, 1024, 1024, 2, 2), (7, 1024, 1024, 2, 1),
    (8, 512, 2048, 2, 4), (8, 256, 1024, 2, 8), (1, 1024, 1024, 4, 1),
])
def test_heads_a_fetch_follow_the_scoped_vmem(group, block_q, block_k,
                                              itemsize, share):
    """As many of a group's heads walk on one fetch as the v5e's 16 MiB hold
    of their q-side blocks and scratch: a divisor of the group, down to one
    (``test_flash_v5e_compile.py`` compiles these shapes for the chip)."""
    from distributed_tpu.ops.flash_attention import _heads_a_fetch

    assert _heads_a_fetch(group, block_q, block_k, itemsize) == share


@pytest.mark.parametrize("selecting", [True, False])
@pytest.mark.parametrize("budget,share", [(1_000_000, 2), (500_000, 1)])
def test_fewer_heads_a_fetch_match_dense_attention(budget, share, selecting,
                                                   vmem):
    """A group of four with room for two heads a fetch, and for one (the
    plain grid, each head reading K/V block h // group): the grids say so,
    and values, row statistic and gradients are the dense path's."""
    from distributed_tpu.ops import flash_attention as fa

    vmem(budget)
    group, t = 4, 200
    suffix = "_sel" if selecting else "_packed"
    specs = _kernel_specs(2 * group, 2, selecting)
    for kernel in ("fwd", "dq"):
        grid, blocks = specs[f"dtpu_flash_{kernel}{suffix}"]
        assert grid == ((2, 4, 4, 2, 2) if share == 2 else (2, 8, 4, 2))
        assert blocks[0][0] == (1, 64, share * 128)
        assert _changes(blocks[1][1]) == fa.kv_block_fetches(
            2, 8, share, 4, 2)[0]
    assert specs[f"dtpu_flash_dkv{suffix}"][0] == (2, 2, 2, 16)
    q, k, v, w = _grouped_qkv(group, t)
    sel = _spread_selection(t) if selecting else None
    flash = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, selection=sel, **GROUP_BLOCKS))
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
        q, k, v, True, sel))
    _close(jax.value_and_grad(flash, (0, 1, 2))(q, k, v),
           jax.value_and_grad(dense, (0, 1, 2))(q, k, v))
    if selecting:
        _close(flash_attention(q, k, v, causal=True, selection=sel,
                               return_lse=True, **GROUP_BLOCKS)[1],
               fa.dense_attention(q, k, v, True, sel, return_lse=True)[1])


def test_kv_block_fetches_at_the_selecting_cells_shape():
    """keye-vl2-30b.train.dsa8k: 32 query heads over 4 K/V heads, 16 x 8 grid
    blocks: 512 fetches where one a query head makes 4,096, published as
    gauges at trace time; the same number twice with no grouped queries."""
    from distributed_tpu import obs
    from distributed_tpu.ops.flash_attention import kv_block_fetches

    assert kv_block_fetches(1, 32, 8, 16, 8) == (512, 4096)
    assert kv_block_fetches(1, 32, 1, 16, 8) == (4096, 4096)
    assert kv_block_fetches(8, 8, 1, 1, 1) == (64, 64)  # one block: it stays
    shape = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((1, 8192, 8192), jnp.int8)
    attend = lambda q, k, v, sel: flash_attention(
        q, k, v, causal=True, selection=sel)
    reg = obs.default_registry()
    names = ("flash.kv_block_fetches", "flash.kv_block_fetches_a_head")
    jax.eval_shape(attend, shape(32), shape(4), shape(4), sel)
    assert [reg.gauge_value(n) for n in names] == [512.0, 4096.0]
    jax.eval_shape(attend, shape(32), shape(32), shape(32), None)
    assert [reg.gauge_value(n) for n in names] == [4096.0, 4096.0]


# ------------------------------------------- calls with no shared K/V head --
# The digest of str(make_jaxpr(grad)) at the parent of PR 37 (commit fb5b43e,
# JAX 0.9.0, the interpreter), addresses blanked: the three kernels' bodies,
# grids and index maps of the calls that share no K/V head in place, at the
# four cells that make them (both GPT-2 cells' layout, kanana's folded
# widths, LFM2's repeated 64-wide heads) and at 128-wide heads with one K/V
# head a query head, with a selection and without. Re-pinned where the
# backward's delta = sum(dO O) became a contraction on the kernels' (b, T,
# H x D) layout: against commit 7c33136's text the equations of delta alone
# differ (a reshape to (b, T, H, D) of each operand, their product, its sum
# and a transpose, for an iota, the product on (b, T, H x D), a 0/1 matrix,
# a dot_general and a reshape), equation by equation, names aside.
PARENT_JAX = "0.9.0"
PARENT_JAXPRS = {
    "packed64": ((8, 1024, 16, 16, 64, 64, False),
                 "9227552482bc9d98d9e94f41c67d444489fb6d3feecda581379760cac9a9fc42"),
    "packed64_long": ((1, 4096, 16, 16, 64, 64, False),
                      "b009002aef760ccd28eb7140772f618d47150e6197f1d9105d931f858e971d6e"),
    "folded": ((1, 4096, 32, 32, 192, 128, False),
               "5baf7d308998946400a72dbb3364b054343b9f07ba52396a1bb21c44960d4d4d"),
    "repeated64": ((1, 8192, 32, 8, 64, 64, False),
                   "aad5e3f3e289f3d1e3967ca87572d366abdb15d8acbf8f1df5de13d875b95cbc"),
    "sel_128": ((1, 2048, 4, 4, 128, 128, True),
                "2eabc9e41a52d2744e161d5b03535f5d059e95d62d25b996ce7f097425f41c80"),
    "packed_128": ((1, 2048, 4, 4, 128, 128, False),
                   "94804f86569479d6ad3b0f048f0df307acfb21170f333e69deacd931b20b0740"),
}


@pytest.mark.parametrize("case", list(PARENT_JAXPRS))
def test_calls_that_share_no_kv_head_in_place_trace_as_before(case):
    """With one K/V head a query head (or K and V repeated) the gradient's
    jaxpr, kernels' bodies and index maps included, is the parent's text."""
    import hashlib
    import re

    (b, t, h, kv, d, dv, selecting), digest = PARENT_JAXPRS[case]
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the parent's digests were taken under JAX {PARENT_JAX}")
    shape = lambda heads, width: jax.ShapeDtypeStruct(
        (b, t, heads, width), jnp.bfloat16)
    sel = jax.ShapeDtypeStruct((b, t, t), jnp.int8) if selecting else None
    loss = lambda q, k, v, sel: jnp.sum(flash_attention(
        q, k, v, causal=True, selection=sel).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        shape(h, d), shape(kv, d), shape(kv, dv), sel))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# ----------------------------------------------------------- a sliding window --
def _windowed_case(window, t, group, blocks):
    b, kv = (1, 1) if t > 256 else (2, 2)
    q, k, v, w = _grouped_qkv(group, t, b=b, kv_heads=kv)
    flash = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, window=window, **blocks))
    return (q, k, v), w, flash


@pytest.mark.parametrize("blocks", [dict(block_q=64, block_k=128),
                                    dict(block_q=128, block_k=128)])
@pytest.mark.parametrize("t,group", [
    (256, 1), (256, 6), (256, 8), (200, 1), (200, 6), (200, 8), (1024, 1)])
@pytest.mark.parametrize("window", [64, 128, 512, 4096])
def test_windowed_kernels_match_dense_attention(window, t, group, blocks):
    """Values, the row statistic and all three gradients of the windowed
    kernels (128-wide heads: one K/V head a query head, and groups of six
    and eight on one fetch) against ``dense_attention`` with the same
    window, at a T the blocks divide, one they pad and one of eight kv
    blocks, for windows under a sub-tile, of one, of several blocks and
    wider than the sequence."""
    from distributed_tpu.ops import flash_attention as fa

    (q, k, v), w, flash = _windowed_case(window, t, group, blocks)
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
        q, k, v, True, window=window))
    # the values as they are (the weighted sum of 10^5 terms cancels to a
    # number whose rounding depends on the order of the sum)
    _close(flash_attention(q, k, v, causal=True, window=window, **blocks),
           fa.dense_attention(q, k, v, True, window=window))
    _close(jax.grad(flash, (0, 1, 2))(q, k, v),
           jax.grad(dense, (0, 1, 2))(q, k, v))
    flat = lambda x: x.reshape(*x.shape[:2], -1)
    lse = fa._fwd_pallas(
        flat(q), flat(k), flat(v), heads=q.shape[2], hpb=1, suffix="_swa",
        causal=True, group=group, window=window if window < t else None,
        **blocks)[1][:, :, 0, :t]
    _close(lse, fa.dense_attention(q, k, v, True, window=window,
                                   return_lse=True)[1])


@pytest.mark.parametrize("shape,value_dim", [
    ((2, 200, 4, 64), None),     # two heads a lane block
    ((1, 160, 2, 192), 128),     # folded, 256 lanes: one sub-tile a block
    ((2, 100, 3, 32), None),     # folded, a ragged T
])
def test_windowed_kernels_in_the_other_layouts(shape, value_dim):
    """The window is the walk's, not the layout's: lane-packed 64-wide
    heads and the folded layout (latent attention's widths) take it too."""
    from distributed_tpu.ops import flash_attention as fa

    q, k, v = _qkv(shape, value_dim=value_dim)
    w = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    for window in (24, 130):
        flash = lambda q, k, v: jnp.sum(w * flash_attention(
            q, k, v, causal=True, window=window, block_q=64, block_k=128))
        dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
            q, k, v, True, window=window))
        _close(flash_attention(q, k, v, causal=True, window=window,
                               block_q=64, block_k=128),
               fa.dense_attention(q, k, v, True, window=window))
        _close(jax.grad(flash, (0, 1, 2))(q, k, v),
               jax.grad(dense, (0, 1, 2))(q, k, v))


@pytest.mark.parametrize("group", [1, 8])
def test_a_window_that_holds_the_sequence_is_the_causal_call(group):
    """To the bit, values and gradients: the call is the plain one (the
    same cached function, the plain kernels' names)."""
    (q, k, v), w, windowed = _windowed_case(256, 256, group, GROUP_BLOCKS)
    plain = lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, **GROUP_BLOCKS))
    got = jax.value_and_grad(windowed, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    text = str(jax.make_jaxpr(jax.grad(windowed, (0, 1, 2)))(q, k, v))
    assert "_swa" not in text and "dtpu_flash_fwd_packed" in text
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=64,
                        selection=_spread_selection(256))


@pytest.mark.parametrize("t,blocks,window,lanes", [
    (8192, (512, 1024), 512, 128),   # laguna-xs2.train.swa8k
    (8192, (512, 1024), 1536, 128), (4096, (512, 1024), 200, 128),
    (1000, (128, 256), 64, 128), (4096, (512, 1024), 512, 256),
    (256, (256, 256), 1, 128),
])
def test_walked_subtiles_against_a_count_from_the_shape(t, blocks, window,
                                                        lanes):
    """``subtile_counts`` with a window against a count written out from
    the shape: a sub-tile is walked if some row of it sees some column of
    it, and masked unless every row sees every column."""
    from distributed_tpu.ops import flash_attention as fa

    side_q, side_k = (fa._subtile(x, lanes) for x in blocks)
    t_pad = -(-t // max(blocks)) * max(blocks)
    computed = masked = 0
    for r0 in range(0, t_pad, side_q):
        for c0 in range(0, t_pad, side_k):
            r1, c1 = r0 + side_q - 1, c0 + side_k - 1
            # the last row reaches furthest right, the first furthest left
            if c0 > r1 or c1 <= r0 - window:
                continue
            computed += 1
            # every row sees every column: the first row's diagonal is at
            # or past the last column, the last row's window holds the first
            masked += not (c1 <= r0 and c0 > r1 - window)
    square = (t_pad // side_q) * (t_pad // side_k)
    assert fa.subtile_counts(t, *blocks, True, lanes, window) == (
        square, computed, masked)
    if (t, window, lanes) == (8192, 512, 128):
        # five sub-tiles a row of them where the window holds four
        assert (computed, masked) == (1 + 2 + 3 + 4 + 60 * 5, 64 + 60)
        assert fa.walked_pairs(t, 64, 128, 2, window) == 310 * 128 * 128
        assert fa.walked_pairs(t, 64, 128, 2, None) == 2080 * 128 * 128


def test_a_windowed_grid_neither_steps_over_nor_fetches_blocks_left_of_the_band():
    """laguna-xs2.train.swa8k's sliding layers, 64 query heads over 8 K/V
    heads at T = 8192 and a window of 512: the forward's and dq's kv axis is
    2 steps long where the plain call's is 8, dk/dv's q axis 3 x 8 heads
    where it is 16 x 8; walking the grids in order every K and V block is
    fetched once a K/V head, 8 times, where the plain call fetches 128; and
    ``kv_block_fetches`` and its gauges say the same."""
    from distributed_tpu import obs
    from distributed_tpu.ops import flash_attention as fa

    def specs(window):
        shape = lambda h: jax.ShapeDtypeStruct((1, 8192, h, 128),
                                               jnp.bfloat16)
        loss = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))
        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
            shape(64), shape(8), shape(8))
        out = {}
        for eqn in _eqns(jaxpr.jaxpr):
            if eqn.primitive.name != "pallas_call":
                continue
            mapping = eqn.params["grid_mapping"]
            # one K/V head's walk: the first row and K/V head
            steps = [g for g in np.ndindex(*mapping.grid) if g[1] == 0]
            out[eqn.params["name"]] = (mapping.grid, [
                [tuple(int(i) for i in jax.core.eval_jaxpr(
                    bm.index_map_jaxpr.jaxpr, bm.index_map_jaxpr.consts, *g))
                 for g in steps] for bm in mapping.block_mappings])
        return out

    banded, plain = specs(512), specs(None)
    changes = lambda seen: 1 + sum(a != b for a, b in zip(seen, seen[1:]))
    for kernel in ("fwd", "dq"):
        grid, maps = banded[f"dtpu_flash_{kernel}_swa"]
        assert grid == (1, 8, 16, 2, 8)
        assert plain[f"dtpu_flash_{kernel}_packed"][0] == (1, 8, 16, 8, 8)
        k_blocks = [at[1] for at in maps[1]]
        assert changes(k_blocks) == changes([at[1] for at in maps[2]]) == 8
        assert sorted(set(k_blocks)) == list(range(8))
        # q block qi sees kv blocks (qi - 1) // 2 .. qi // 2 and no other
        seen = {}
        for (g, at) in zip([g for g in np.ndindex(*grid) if g[1] == 0],
                           maps[1]):
            seen.setdefault(g[2], set()).add(at[1])
        assert seen == {qi: {max(qi - 1, 0) // 2, qi // 2}
                        for qi in range(16)}
        assert changes([at[1] for at in plain[
            f"dtpu_flash_{kernel}_packed"][1][1]]) == 128
    grid, maps = banded["dtpu_flash_dkv_swa"]
    assert grid == (1, 8, 8, 3 * 8)
    assert plain["dtpu_flash_dkv_packed"][0] == (1, 8, 8, 16 * 8)
    q_blocks = {}
    for g, at in zip([g for g in np.ndindex(*grid) if g[1] == 0], maps[0]):
        q_blocks.setdefault(g[2], set()).add(at[1])
    assert q_blocks == {ki: {min(2 * ki + j, 15) for j in range(3)}
                        for ki in range(8)}
    assert fa.kv_block_fetches(1, 64, 8, 16, 8, (512, 1024, 512)) == (
        8 * 8, 64 * 8)
    assert fa.kv_block_fetches(1, 64, 8, 16, 8) == (8 * 128, 64 * 128)
    reg = obs.default_registry()
    assert reg.gauge_value("flash.kv_block_fetches") == 1024.0  # plain, last
    specs(512)
    assert reg.gauge_value("flash.kv_block_fetches") == 64.0
    assert reg.gauge_value("flash.subtiles_computed") == 310.0
