"""``ops.grouped_matmul``: the experts' gated MLP over their buffer (three
Pallas kernels, the activation and its backward in their epilogues) in
interpret mode against the formulation it replaced, written out here as
three grouped products with XLA's activation between them: values and all
four gradients, with even, uneven, empty and overfull groups; the buffer's
contract (nothing past the tiles in use is read or written); and the
tile-aligned layout the kernels run on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu.ops import grouped_matmul as gm

TILE = 16
PAIRS, GROUPS, K, N = 96, 4, 32, 48
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


def layout(sizes):
    rows = gm.buffer_rows(PAIRS, GROUPS, TILE)
    starts, tile_group, used = gm.group_layout(
        jnp.asarray(sizes), rows // TILE, TILE)
    mask = np.zeros((rows,), bool)
    for s, n in zip(np.asarray(starts), sizes):
        mask[s:s + n] = True
    return rows, np.asarray(starts), tile_group, used, mask


def product(lhs, rhs, sizes, starts):
    """One grouped matmul as the kernels compute it: float32 accumulation,
    the result in the operands' dtype; zeros where no group has a row."""
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), lhs.dtype)
    for g, (s, n) in enumerate(zip(starts, sizes)):
        out = out.at[s:s + n].set(jnp.dot(
            lhs[s:s + n], rhs[g],
            preferred_element_type=jnp.float32).astype(lhs.dtype))
    return out


def parents(buf, w_gate, w_up, w_down, sizes, starts):
    """The formulation ``grouped_gated_mlp`` replaced (PR 34's ``experts``
    scope): three grouped products, the activation between them XLA's."""
    hidden = jax.nn.silu(product(buf, w_gate, sizes, starts)) * product(
        buf, w_up, sizes, starts)
    return product(hidden, w_down, sizes, starts)


def operands(sizes, dtype, seed=0):
    rows, starts, tile_group, used, mask = layout(sizes)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    rows_of = lambda key: jnp.where(
        mask[:, None], jax.random.normal(key, (rows, K)), 0.0).astype(dtype)
    weights = tuple(
        (jax.random.normal(key, shape) * shape[1] ** -0.5).astype(dtype)
        for key, shape in zip(keys[2:], ((GROUPS, K, N), (GROUPS, K, N),
                                         (GROUPS, N, K))))
    return rows_of(keys[0]), weights, rows_of(keys[1])


def value_and_gradients(mlp, buf, weights, d_out):
    out, vjp = jax.vjp(mlp, buf, *weights)
    return (out,) + vjp(d_out)


def f32(x):
    return np.asarray(x, np.float32)


SIZES = {
    "even": [24, 24, 24, 24],
    "uneven": [5, 40, 17, 1],
    "empty_groups": [0, 33, 0, 0],
    "all_empty": [0, 0, 0, 0],
    "one_takes_all": [0, 0, PAIRS, 0],
    "full_tiles": [16, 32, 16, 32],  # every pair held: the buffer's worst
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


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("case", sorted(SIZES))
def test_value_and_four_gradients_match_the_three_products_it_replaced(
        case, dtype):
    """On the tiles in use: float32 to 1e-5; bfloat16 to a rounding of the
    result's dtype at the result's scale (the kernels round ``silu(g) u``,
    dg and du once from float32 where XLA rounded each factor, and add d
    buf's two terms before rounding)."""
    sizes, dtype = SIZES[case], DTYPES[dtype]
    rows, starts, tile_group, used, mask = layout(sizes)
    buf, weights, d_out = operands(sizes, dtype, seed=len(case))
    got = value_and_gradients(
        lambda *a: gm.grouped_gated_mlp(*a, tile_group, used, tile_m=TILE),
        buf, weights, d_out)
    want = value_and_gradients(
        lambda *a: parents(*a, sizes, starts), buf, weights, d_out)
    in_use = np.arange(rows) < int(used[0]) * TILE
    for name, a, b in zip(("out", "d_buf", "d_w_gate", "d_w_up", "d_w_down"),
                          got, want):
        assert a.dtype == dtype and a.shape == b.shape, name
        a, b = f32(a), f32(b)
        if a.shape[0] == rows:
            # a padded row of a tile in use is zero in every product
            assert not a[in_use & ~mask].any(), name
            a, b = a[in_use], b[in_use]
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
        else:
            step = 2.0 ** -7  # bfloat16's spacing over a value
            assert np.all(np.abs(a - b) <= step * (
                np.abs(b) + np.abs(b).max())), name
    for g, n in enumerate(sizes):  # an empty group's gradients are written
        if n == 0:
            for d_w in got[2:]:
                assert not f32(d_w[g]).any()


def test_nothing_past_the_tiles_in_use_reaches_a_result_or_a_gradient():
    """The kernels cover exactly the tiles in use: NaN below them in the
    buffer and in d out changes no result on a tile in use and no gradient.
    What the results hold below is nobody's to read."""
    sizes = SIZES["uneven"]
    rows, starts, tile_group, used, mask = layout(sizes)
    assert int(used[0]) == 7 and rows // TILE > 7
    past = (np.arange(rows) >= int(used[0]) * TILE)[:, None]
    buf, weights, d_out = operands(sizes, jnp.float32)
    mlp = lambda *a: gm.grouped_gated_mlp(*a, tile_group, used, tile_m=TILE)
    want = value_and_gradients(mlp, buf, weights, d_out)
    got = value_and_gradients(mlp, jnp.where(past, jnp.nan, buf), weights,
                              jnp.where(past, jnp.nan, d_out))
    for a, b in zip(got, want):
        a, b = f32(a), f32(b)
        if a.shape[0] == rows:
            a, b = a[~past[:, 0]], b[~past[:, 0]]
        np.testing.assert_array_equal(a, b)


def test_bfloat16_operands_accumulate_in_float32():
    sizes = SIZES["uneven"]
    rows, starts, tile_group, used, mask = layout(sizes)
    buf, weights, d_out = operands(sizes, jnp.bfloat16)
    got = value_and_gradients(
        lambda *a: gm.grouped_gated_mlp(*a, tile_group, used, tile_m=TILE),
        buf, weights, d_out)
    wide = lambda x: x.astype(jnp.float32)
    want = value_and_gradients(
        lambda *a: parents(*a, sizes, starts), wide(buf),
        tuple(map(wide, weights)), wide(d_out))
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        a, b = f32(a), f32(b)
        if a.shape[0] == rows:
            a, b = a[mask], b[mask]
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2 * np.abs(
            b).max())


def test_rows_must_be_whole_tiles():
    with pytest.raises(ValueError, match="multiple of the tile"):
        gm.grouped_gated_mlp(
            jnp.zeros((TILE + 1, K)), jnp.zeros((1, K, N)),
            jnp.zeros((1, K, N)), jnp.zeros((1, N, K)),
            jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32),
            tile_m=TILE)


def test_wide_matrices_are_tiled():
    """Blocks over the caps: the weight block is cut along N, the d rhs
    block along K and N; the result does not change."""
    assert gm._pick_tile(768, 1024) == 768
    assert gm._pick_tile(2048, 1024) == 1024
    assert gm._pick_tile(768, 512) == 384
    assert gm._pick_tile(100, 64) == 100  # no lane-multiple divisor: whole
