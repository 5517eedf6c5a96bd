"""``ops.grouped_matmul``: the three Pallas kernels in interpret mode against
a plain loop over the groups, forward and both gradients, with empty, uneven
and overfull groups, and the tile-aligned layout they run on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu.ops import grouped_matmul as gm

TILE = 16
PAIRS, GROUPS, K, N = 96, 4, 32, 48


def layout(sizes):
    rows = gm.buffer_rows(PAIRS, GROUPS, TILE)
    starts, tile_group, used = gm.group_layout(
        jnp.asarray(sizes), rows // TILE, TILE)
    mask = np.zeros((rows,), bool)
    for s, n in zip(np.asarray(starts), sizes):
        mask[s:s + n] = True
    return rows, np.asarray(starts), tile_group, used, mask


def plain(lhs, rhs, sizes, starts):
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g, (s, n) in enumerate(zip(starts, sizes)):
        out = out.at[s:s + n].set(lhs[s:s + n] @ rhs[g])
    return out


SIZES = {
    "uneven": [5, 40, 17, 1],
    "empty_groups": [0, 33, 0, 0],
    "all_empty": [0, 0, 0, 0],
    "one_takes_all": [0, 0, PAIRS, 0],
    "full_tiles": [16, 32, 16, 32],
}


@pytest.mark.parametrize("case", sorted(SIZES))
def test_layout_is_tile_aligned_and_every_group_owns_a_tile(case):
    sizes = SIZES[case]
    rows, starts, tile_group, used, _ = layout(sizes)
    tiles = [max(1, -(-n // TILE)) for n in sizes]
    assert int(used[0]) == sum(tiles) <= rows // TILE
    assert list(starts) == [TILE * sum(tiles[:g]) for g in range(GROUPS)]
    want = [g for g, t in enumerate(tiles) for _ in range(t)]
    assert list(np.asarray(tile_group[:sum(tiles)])) == want
    # tiles not in use name the last group: no block index changes
    assert set(np.asarray(tile_group[sum(tiles):])) <= {GROUPS - 1}


@pytest.mark.parametrize("case", sorted(SIZES))
def test_forward_and_both_gradients_match_a_loop_over_groups(case):
    sizes = SIZES[case]
    rows, starts, tile_group, used, mask = layout(sizes)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    lhs = jnp.where(mask[:, None], jax.random.normal(k1, (rows, K)), 0.0)
    rhs = jax.random.normal(k2, (GROUPS, K, N))
    w = jnp.where(mask[:, None], jax.random.normal(k3, (rows, N)), 0.0)

    def system(l, r):
        return gm.grouped_matmul(l, r, tile_group, used, tile_m=TILE)

    out = system(lhs, rhs)
    want = plain(lhs, rhs, sizes, starts)
    np.testing.assert_allclose(out, want, atol=1e-5)
    assert not np.asarray(out)[~mask].any()  # padding rows stay zero
    got = jax.grad(lambda l, r: jnp.sum(system(l, r) * w), (0, 1))(lhs, rhs)
    ref = jax.grad(lambda l, r: jnp.sum(plain(l, r, sizes, starts) * w),
                   (0, 1))(lhs, rhs)
    np.testing.assert_allclose(np.asarray(got[0])[mask],
                               np.asarray(ref[0])[mask], atol=1e-4)
    np.testing.assert_allclose(got[1], ref[1], atol=1e-4)
    for g, n in enumerate(sizes):  # an empty group's gradient is written
        if n == 0:
            assert not np.asarray(got[1][g]).any()


def test_tiles_beyond_those_in_use_are_zeros_and_add_nothing():
    """The kernels cover exactly the tiles in use: whatever the buffer holds
    past them, the products there are zeros and no gradient reads it."""
    sizes = SIZES["uneven"]
    rows, starts, tile_group, used, mask = layout(sizes)
    assert int(used[0]) == 7 and rows // TILE > 7
    past = np.arange(rows) >= int(used[0]) * TILE
    clean = jnp.where(mask[:, None], jax.random.normal(
        jax.random.PRNGKey(0), (rows, K)), 0.0)
    dirty = jnp.where(past[:, None], 7.0, clean)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (GROUPS, K, N))

    def loss(l, r):
        out = gm.grouped_matmul(l, r, tile_group, used, tile_m=TILE)
        return jnp.sum(jnp.sin(out) + out), out

    (want, _), d_want = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        clean, rhs)
    (got, out), d_got = jax.value_and_grad(loss, (0, 1), has_aux=True)(
        dirty, rhs)
    assert not np.asarray(out)[past].any()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert not np.asarray(d_got[0])[past].any()
    np.testing.assert_allclose(np.asarray(d_got[0])[mask],
                               np.asarray(d_want[0])[mask], atol=1e-5)
    np.testing.assert_allclose(d_got[1], d_want[1], atol=1e-5)


def test_bfloat16_operands_accumulate_in_float32():
    sizes = SIZES["uneven"]
    rows, starts, tile_group, used, mask = layout(sizes)
    lhs = jnp.where(mask[:, None], jax.random.normal(
        jax.random.PRNGKey(0), (rows, K)), 0.0).astype(jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(1), (GROUPS, K, N)).astype(
        jnp.bfloat16)
    out = gm.grouped_matmul(lhs, rhs, tile_group, used, tile_m=TILE)
    assert out.dtype == jnp.bfloat16
    want = plain(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes,
                 starts)
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)
    d_rhs = jax.grad(lambda r: jnp.sum(gm.grouped_matmul(
        lhs, r, tile_group, used, tile_m=TILE).astype(jnp.float32)))(rhs)
    assert d_rhs.dtype == jnp.bfloat16


def test_rows_must_be_whole_tiles():
    with pytest.raises(ValueError, match="multiple of the tile"):
        gm.grouped_matmul(jnp.zeros((TILE + 1, K)), jnp.zeros((1, K, N)),
                          jnp.zeros((2,), jnp.int32),
                          jnp.ones((1,), jnp.int32), tile_m=TILE)


def test_wide_matrices_are_tiled():
    """Blocks over the caps: the weight block is cut along N, the d rhs
    block along K and N; the result does not change."""
    assert gm._pick_tile(768, 1024) == 768
    assert gm._pick_tile(2048, 1024) == 1024
    assert gm._pick_tile(768, 512) == 384
    assert gm._pick_tile(100, 64) == 100  # no lane-multiple divisor: whole
