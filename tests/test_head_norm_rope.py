"""``ops/head_norm_rope.py`` in the interpreter against the lines it stands
in for: ``nn/attention.py``'s ``_rms`` then ``rope_half`` / ``rope_rotary``
on the (B, T, H, 128) view, values and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu.nn.attention import (
    _rms, rope_half, rope_rotary, yarn_inv_freq)
from distributed_tpu.ops import head_norm_rope as hn

EPS = 1e-6
# Laguna's full layers: 64 of 128 dimensions, YaRN's frequencies and factor.
YARN = (yarn_inv_freq(64, 500000.0, factor=64.0, original_max_position=4096,
                      beta_fast=64.0, beta_slow=1.0), 1.4159)
ROTATIONS = {"theta1e4": 1e4, "theta1e7": 1e7, "yarn64": YARN}


def half_freq(theta):
    """``rope_half``'s frequencies, as the layer hands them over."""
    return 1.0 / (theta ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))


def fused(rotation, **blocks):
    def f(x, scale):
        rot = (rotation if isinstance(rotation, tuple)
               else (half_freq(rotation), 1.0))
        return hn.head_norm_rope(x, scale, rot, epsilon=EPS, **blocks)
    return f


def plain(rotation):
    def f(x, scale):
        b, t, width = x.shape
        y = _rms(x.reshape(b, t, width // 128, 128), scale, EPS)
        y = (rope_rotary(y, *rotation) if isinstance(rotation, tuple)
             else rope_half(y, rotation))
        return y.reshape(b, t, width)
    return f


def operands(b, t, heads, dtype, seed=0):
    kx, ks, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (3.0 * jax.random.normal(kx, (b, t, heads * 128))).astype(dtype)
    scale = 1.0 + 0.2 * jax.random.normal(ks, (128,))
    return x, scale, jax.random.normal(kw, x.shape)


def ulp(a):
    """The spacing of bfloat16 at each value of ``a``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 7)


# (B, T, heads, the blocks' overrides): 4, 6 and 8 heads a block; a head
# count BLOCK_HEADS does not divide (12 -> 6 a block, 10 -> 5, two blocks
# each); rows the row block divides, rows it does not (the last block is
# part empty) and a sequence shorter than a block.
SHAPES = [
    (1, 64, 4, {}), (2, 40, 6, {}), (1, 96, 8, {}),
    (1, 64, 12, {}), (2, 24, 10, {}),
    (1, 80, 8, dict(block_rows=32)), (2, 72, 16, dict(block_rows=32)),
    (2, 100, 4, dict(block_rows=64, block_heads=2)),
]


def test_blocks_follow_the_heads_and_the_dtype():
    assert hn.blocks(8192, 64, 2) == (512, 8)
    assert hn.blocks(8192, 48, 2) == (512, 8)
    assert hn.blocks(8192, 4, 2) == (512, 4)
    assert hn.blocks(8192, 12, 2) == (512, 6)
    assert hn.blocks(8192, 10, 4) == (256, 5)
    assert hn.blocks(40, 6, 2) == (40, 6)
    x, scale, _ = operands(1, 16, 6, jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        hn.head_norm_rope(x, scale, YARN, epsilon=EPS, block_heads=4)
    with pytest.raises(ValueError, match="projection"):
        hn.head_norm_rope(x[..., :100], scale, YARN, epsilon=EPS)
    with pytest.raises(ValueError, match="frequencies"):
        hn.head_norm_rope(x, scale, (np.ones(65, np.float32), 1.0),
                          epsilon=EPS)


@pytest.mark.parametrize("rotation", list(ROTATIONS))
def test_tables_turn_as_the_plain_rotation_does(rotation):
    """cos is 1 and the sines 0 past the rotated dimensions; against a one
    in every lane the tables give what ``rope_half`` / ``rope_rotary`` give."""
    rot = ROTATIONS[rotation]
    rot = rot if isinstance(rot, tuple) else (half_freq(rot), 1.0)
    cos, *sines = hn.rotation_tables(40, *rot)
    r = 2 * len(rot[0])
    assert len(sines) == (1 if r == 128 else 2)
    np.testing.assert_array_equal(cos[:, r:], 1.0)
    for s in sines:
        np.testing.assert_array_equal(s[:, r:], 0.0)
    ones = jnp.ones((1, 40, 1, 128))
    want = plain(ROTATIONS[rotation])(
        ones.reshape(1, 40, 128), jnp.ones((128,)) * np.sqrt(1.0 + EPS))
    np.testing.assert_allclose(cos + sum(sines), want[0], atol=2e-6)


@pytest.mark.parametrize("rotation", list(ROTATIONS))
@pytest.mark.parametrize("b,t,heads,blocks", SHAPES)
def test_values_float32(b, t, heads, blocks, rotation):
    x, scale, _ = operands(b, t, heads, jnp.float32)
    got = jax.jit(fused(ROTATIONS[rotation], **blocks))(x, scale)
    want = jax.jit(plain(ROTATIONS[rotation]))(x, scale)
    assert got.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_allclose(got, want, atol=1e-6 * float(
        jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("rotation", list(ROTATIONS))
@pytest.mark.parametrize("b,t,heads,blocks", SHAPES)
def test_values_bfloat16_to_a_unit_in_the_last_place(b, t, heads, blocks,
                                                     rotation):
    """Rounded after the norm and after the rotation, as the plain lines
    round: what the flash kernels receive is what they received, but for
    the few entries (under one in 10,000) where float32's order of summing
    makes a rounding fall the other way. One that falls so after the norm
    moves the rotation's sum by a unit of its operands, which the sum may
    be smaller than: the bound is a unit at the head's largest entry."""
    x, scale, _ = operands(b, t, heads, jnp.bfloat16)
    got = jax.jit(fused(ROTATIONS[rotation], **blocks))(x, scale)
    want = jax.jit(plain(ROTATIONS[rotation]))(x, scale)
    assert got.shape == x.shape and got.dtype == jnp.bfloat16
    got, want = (np.asarray(a.astype(jnp.float32)).reshape(b, t, heads, 128)
                 for a in (got, want))
    assert np.mean(got == want) > 0.9999
    assert np.all(np.abs(got - want) <= ulp(
        np.max(np.abs(want), axis=-1, keepdims=True)))


@pytest.mark.parametrize("rotation", list(ROTATIONS))
@pytest.mark.parametrize("b,t,heads,blocks", SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradients_match_autodiff_of_the_plain_lines(dtype, b, t, heads,
                                                     blocks, rotation):
    """d x and d scale under ``jit``, at batch 1 and 2: float32 to 1e-5 of
    the leaf's largest entry; bfloat16 against autodiff in float32 on the
    same rounded operands at the flash tests' tolerance. The frequencies
    take no gradient."""
    x, scale, w = operands(b, t, heads, jnp.dtype(dtype), seed=1)
    loss = lambda f: lambda x, s: jnp.sum(w * f(x, s).astype(jnp.float32))
    rot = ROTATIONS[rotation]
    got = jax.jit(jax.grad(loss(fused(rot, **blocks)), (0, 1)))(x, scale)
    want = jax.jit(jax.grad(loss(plain(rot)), (0, 1)))(
        x.astype(jnp.float32), scale)
    assert got[0].dtype == x.dtype and got[1].dtype == scale.dtype
    for a, e in zip(got, want):
        a, e = np.asarray(a.astype(jnp.float32)), np.asarray(e)
        if dtype == "float32":
            np.testing.assert_allclose(a, e, atol=1e-5 * np.max(np.abs(e)))
        else:
            np.testing.assert_allclose(a, e, rtol=2e-2, atol=2e-2 * np.sqrt(
                np.mean(np.square(e))))


def test_the_frequencies_take_no_gradient():
    x, scale, w = operands(1, 32, 4, jnp.float32)
    freq = jnp.asarray(YARN[0])
    g = jax.grad(lambda f: jnp.sum(w * hn.head_norm_rope(
        x, scale, (f, YARN[1]), epsilon=EPS)))(freq)
    np.testing.assert_array_equal(g, 0.0)


def test_each_pass_is_traced_once_a_shape(monkeypatch):
    """A model's layers call the passes at one shape each: the kernels'
    bodies are traced once a pass, however many layers call them."""
    traced = []

    def counting(name, body):
        def kernel(*refs, **static):
            traced.append(name)
            return body(*refs, **static)
        return kernel

    monkeypatch.setattr(hn, "_fwd_kernel", counting("fwd", hn._fwd_kernel))
    monkeypatch.setattr(hn, "_bwd_kernel", counting("bwd", hn._bwd_kernel))
    x, scale, w = operands(1, 48, 4, jnp.bfloat16, seed=2)
    f = fused(YARN)
    loss = lambda x, s: sum(
        jnp.sum(w * f(x * c, s).astype(jnp.float32)) for c in (1.0, 2.0, 3.0))
    from qk_prep import kernel_calls

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, scale)
    assert kernel_calls(jaxpr) == ["dtpu_head_norm_rope"] * 3 + [
        "dtpu_head_norm_rope_bwd"] * 3
    assert sorted(traced) == ["bwd", "fwd"]
