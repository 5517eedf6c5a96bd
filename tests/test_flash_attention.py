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


def _qkv(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(3)
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


# (shape, block_q, block_k, sub-tile side): the packed kernels' sub-tile walk.
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
        fa._packed_cached.cache_clear()
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


@pytest.mark.parametrize("t,blocks,causal,want", [
    (1024, (1024, 1024), True, (64, 36, 8)),    # both benchmark cells
    (1024, (1024, 1024), False, (64, 64, 0)),
    (1000, (1024, 1024), False, (64, 64, 8)),   # the padding edge
    (700, (1024, 1024), False, (64, 48, 8)),   # two columns in the padding
    (4096, (512, 1024), True, (1024, 528, 32)),  # the bf16 clamp shape
    (600, (600, 600), True, (1, 1, 1)),         # ragged block: one sub-tile
])
def test_subtile_counts(t, blocks, causal, want):
    """The static count of the sub-tile walk, at the module's own side."""
    from distributed_tpu.ops.flash_attention import _SUBTILE, subtile_counts

    assert _SUBTILE == 128
    assert subtile_counts(t, *blocks, causal) == want


def test_subtile_gauges_published():
    """flash_attention publishes the counts at trace time, as gauges."""
    from distributed_tpu import obs

    reg = obs.default_registry()
    q = jax.ShapeDtypeStruct((1, 1024, 2, 64), jnp.bfloat16)
    jax.eval_shape(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, q, q)
    assert [reg.gauge_value(f"flash.subtiles_{n}")
            for n in ("square", "computed", "masked")] == [64.0, 36.0, 8.0]


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


@pytest.mark.parametrize("head_dim", [64, 128])
def test_packed_kernels_feed_the_mxu_bf16(head_dim):
    """Every matrix product of the three packed kernels takes bf16 operands
    from bf16 inputs: the scale folded into q (head_dim 64: a power of two)
    must not promote it (a NumPy scalar is no weak type), and head_dim 128
    keeps its f32 multiply on the scores."""
    q = jax.ShapeDtypeStruct((1, 512, 128 // head_dim, head_dim),
                             jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert _dot_operand_dtypes(jaxpr.jaxpr, set()) == {
        ("bfloat16", "bfloat16")}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape,blocks,side", [
    ((1, 96, 2, 64), (32, 32), None),     # several blocks: from the scratch
    ((1, 120, 2, 64), (128, 128), 32),    # one block, walked in sub-tiles
    ((2, 100, 1, 128), (64, 64), 32),     # one head a lane block, ragged T
])
def test_packed_residual_statistic(shape, blocks, side, causal, subtile):
    """What the forward saves for the backward beside its inputs and output
    is ONE row statistic, lse = m + log l, 4 bytes a row and head, laid out
    lane-major (B, head blocks, heads a block, t_pad): it must be the
    log-sum-exp of the dense scores on every real row."""
    fa = subtile(side)
    b, t, h, d = shape
    q, k, v = _qkv(shape, seed=5)
    flat = lambda x: x.reshape(b, t, h * d)
    _, lse = fa._fwd_pallas_packed(
        flat(q), flat(k), flat(v), h, d, 1.0 / np.sqrt(d), causal, *blocks)
    hpb = 128 // d
    assert lse.shape[:3] == (b, h // hpb, hpb) and lse.dtype == jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jax.scipy.special.logsumexp(s, axis=-1)  # (b, h, t)
    np.testing.assert_allclose(
        np.asarray(lse[..., :t]).reshape(b, h, t), np.asarray(want),
        rtol=2e-5, atol=2e-5)
