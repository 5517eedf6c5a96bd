"""``ops/moe_rows.py``'s two walks (Pallas interpreter on the CPU) against the
formulation they replaced, written out here with ``jnp.take`` over the
buffer's static worst case: dispatch and combine, forward and all three
gradients (``flat``, ``out_buf``, ``gates``), through ``nn.moe``'s own
``custom_vjp``s; the buffer's contract (padded rows of a tile in use are
exactly zero, tiles not in use are never read)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu.nn import moe
from distributed_tpu.ops import grouped_matmul as gmm, moe_rows

TILE = gmm.TILE_M
# name: (tokens, top_k, experts routed over, experts held, how they choose)
CASES = {
    "a_share_held": (160, 3, 128, 16, "random"),
    "all_held": (96, 2, 8, 8, "random"),
    "every_pair_on_one_expert": (100, 3, 8, 4, "one"),
    "an_empty_held_group": (150, 2, 8, 4, "skip_2"),
    "no_pair_held": (64, 2, 8, 3, "none"),
    "tokens_not_a_multiple_of_128": (200, 3, 16, 4, "random"),
}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
D = 192  # a row of one full chunk of 128 lanes and a part of one


def choices(case, seed=0):
    n, k, experts, held, how = CASES[case]
    rng = np.random.default_rng(seed)
    if how == "one":  # the walks take any pair table: k pairs, one expert
        return np.full((n, k), 1)
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
    if how == "none":
        idx = held + idx % (experts - held)
    if how.startswith("skip"):
        idx = np.where(idx == int(how[-1]), experts - 1, idx)
    return idx


def layout(case):
    """``DroplessMoE.apply``'s sort, with what the reference needs beside
    it: ``dest`` (k, n), ``held`` (k, n), ``row_valid`` (M,)."""
    n, k, _, g, _ = CASES[case]
    local = jnp.asarray(choices(case).T, jnp.int32)
    held = local < g
    group = jnp.where(held, local, g).reshape(-1)
    onehot = (group[:, None] == jnp.arange(g)[None]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    sizes = jnp.sum(onehot, axis=0)
    rows = gmm.buffer_rows(n * k, g)
    row_starts, tile_group, tiles_used = gmm.group_layout(sizes, rows // TILE)
    start = jnp.take(row_starts, jnp.minimum(group, g - 1))
    dest = jnp.where(held.reshape(-1), start + rank, rows)
    row_pair = jnp.full((rows,), n * k, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop")
    row_valid = np.asarray(row_pair < n * k)
    used = int(tiles_used[0]) * TILE
    return dict(
        n=n, k=k, rows=rows, held=held, row_valid=jnp.asarray(row_valid),
        dest=jnp.where(held, dest.reshape(k, n), 0), sizes=np.asarray(sizes),
        src_token=jnp.minimum(row_pair, n * k - 1) % n, used=used,
        in_use=jnp.arange(rows) < used,
        walk=(row_pair, moe_rows.tile_rows(sizes, row_starts, tile_group,
                                           tiles_used), tiles_used))


# ------------------------------------- the formulation that was replaced --
def take_dispatch(flat, lay):
    rows = jnp.take(flat, lay["src_token"], axis=0, mode="clip")
    return jnp.where(lay["row_valid"][:, None], rows,
                     jnp.zeros((), flat.dtype))


def take_combine(out_buf, gates, lay):
    rows = jnp.take(out_buf, lay["dest"].reshape(-1), axis=0,
                    mode="clip").reshape(lay["k"], lay["n"], -1)
    y = jnp.sum(gates[:, :, None] * rows.astype(jnp.float32), axis=0)
    return y.astype(out_buf.dtype)


def operands(lay, dtype, seed=1):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    flat = normal(lay["n"], D).astype(dtype)
    # As the grouped matmuls leave it: zeros on padded rows and below.
    out_buf = jnp.where(lay["row_valid"][:, None], normal(lay["rows"], D),
                        0.0).astype(dtype)
    gates = jnp.where(lay["held"], jnp.asarray(
        rng.uniform(0.2, 1.0, (lay["k"], lay["n"])), jnp.float32), 0.0)
    return flat, out_buf, gates, normal(lay["rows"], D), normal(lay["n"], D)


def f32(x):
    return np.asarray(x, np.float32)


def tolerance(dtype):
    # A sum of up to k rows in another order, rounded once to the dtype.
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module", params=sorted(CASES))
def lay(request):
    return layout(request.param)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_the_walks_give_the_take_formulations_values(lay, dtype):
    dtype = DTYPES[dtype]
    flat, out_buf, gates, _, _ = operands(lay, dtype)
    buf = moe._dispatch(flat, lay["walk"])
    assert buf.shape == (lay["rows"], D) and buf.dtype == dtype
    np.testing.assert_array_equal(  # rows are copied: exact
        f32(buf)[:lay["used"]], f32(take_dispatch(flat, lay))[:lay["used"]])
    y = moe._combine(out_buf, gates, lay["walk"])
    assert y.shape == (lay["n"], D) and y.dtype == dtype
    np.testing.assert_allclose(f32(y), f32(take_combine(out_buf, gates, lay)),
                               **tolerance(dtype))
    # A token none of whose pairs is held reads exactly zero.
    lonely = ~np.asarray(lay["held"]).any(axis=0)
    assert not f32(y)[lonely].any()


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_the_walks_give_the_take_formulations_gradients(lay, dtype):
    dtype = DTYPES[dtype]
    flat, out_buf, gates, w_buf, w_y = operands(lay, dtype)
    in_use = lay["in_use"][:, None]

    def loss(dispatch, combine):
        def fn(flat, out_buf, gates):
            # Only the tiles in use may be read: a walk leaves the others
            # as it found them.
            buf = jnp.where(in_use, dispatch(flat), 0).astype(jnp.float32)
            y = combine(out_buf, gates).astype(jnp.float32)
            return jnp.sum(buf * w_buf) + jnp.sum(y * w_y)
        return jax.grad(fn, argnums=(0, 1, 2))

    got = loss(lambda f: moe._dispatch(f, lay["walk"]),
               lambda b, g: moe._combine(b, g, lay["walk"]))(
                   flat, out_buf, gates)
    want = loss(lambda f: take_dispatch(f, lay),
                lambda b, g: take_combine(b, g, lay))(flat, out_buf, gates)
    tol = tolerance(dtype)
    np.testing.assert_allclose(f32(got[0]), f32(want[0]), **tol)
    np.testing.assert_allclose(f32(got[1])[:lay["used"]],
                               f32(want[1])[:lay["used"]], **tol)
    held = np.asarray(lay["held"])
    np.testing.assert_allclose(f32(got[2]) * held, f32(want[2]) * held,
                               rtol=tol["rtol"], atol=20 * tol["atol"])
    assert not (f32(got[2]) * ~held).any()  # no row, no gradient


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_padded_rows_of_a_tile_in_use_are_exactly_zero(lay, dtype):
    """``grouped_matmul``'s contract on its lhs, kept by both row-major
    movements: the rows of a group's last tile beyond the group's size
    (row 0 too where the first held group is empty) are zeros."""
    dtype = DTYPES[dtype]
    flat, out_buf, gates, _, w_y = operands(lay, dtype)
    padded = np.asarray(~lay["row_valid"])[:lay["used"]]
    assert padded.any()
    if lay["sizes"][0] == 0:
        assert padded[0]
    buf = moe._dispatch(jnp.abs(flat) + 1, lay["walk"])
    assert not f32(buf)[:lay["used"]][padded].any()
    assert f32(buf)[:lay["used"]][~padded].all()
    d_out = jax.grad(lambda b: jnp.sum(moe._combine(
        b, gates, lay["walk"]).astype(jnp.float32) * w_y))(out_buf)
    assert not f32(d_out)[:lay["used"]][padded].any()


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_what_lies_in_the_tiles_not_in_use_is_never_read(lay, dtype):
    """The buffers' tiles not in use filled with NaN beforehand: ``y`` and
    every gradient are finite and the same."""
    dtype = DTYPES[dtype]
    flat, out_buf, gates, w_buf, w_y = operands(lay, dtype)
    if lay["used"] == lay["rows"]:
        pytest.skip("every tile of this buffer is in use")
    in_use = lay["in_use"][:, None]
    poisoned = jnp.where(in_use, out_buf, jnp.nan)
    y = moe._combine(poisoned, gates, lay["walk"])
    np.testing.assert_array_equal(
        f32(y), f32(moe._combine(out_buf, gates, lay["walk"])))

    def grads(out_buf, d_buf):
        _, back = jax.vjp(lambda b, g: moe._combine(b, g, lay["walk"]),
                          out_buf, gates)
        d_out, d_gates = back(w_y.astype(dtype))
        d_flat, = jax.vjp(lambda f: moe._dispatch(f, lay["walk"]),
                          flat)[1](d_buf)
        return d_out[:lay["used"]], d_gates, d_flat

    clean = grads(out_buf, w_buf.astype(dtype))
    dirty = grads(poisoned, jnp.where(in_use, w_buf, jnp.nan).astype(dtype))
    for a, b in zip(dirty, clean):
        assert np.isfinite(f32(a)).all()
        np.testing.assert_array_equal(f32(a), f32(b))


def test_a_token_block_smaller_than_the_batch_sums_the_same(monkeypatch):
    """``sum_rows`` holds its accumulator in VMEM, a block of tokens a grid
    step; several blocks over the same tiles give one block's result."""
    lay = layout("tokens_not_a_multiple_of_128")
    _, out_buf, gates, _, _ = operands(lay, jnp.float32)
    one = moe_rows.sum_rows(out_buf, *lay["walk"], lay["n"],
                            pair_scale=gates)
    monkeypatch.setattr(moe_rows, "_ACC_BYTES", TILE * 16 * 128 * 4)
    two = moe_rows.sum_rows(out_buf, *lay["walk"], lay["n"],
                            pair_scale=gates)
    np.testing.assert_array_equal(f32(one), f32(two))


@pytest.mark.parametrize("width", [16, 128, 320])
def test_rows_of_any_width_make_the_round_trip(width):
    """Narrower than one chunk of 128 lanes, one chunk exactly, two and a
    part: every buffer row back to its token with a gate of one is the
    token's row times the pairs it holds."""
    lay = layout("an_empty_held_group")
    flat = jnp.asarray(np.random.default_rng(2).standard_normal(
        (lay["n"], width)), jnp.float32)
    buf = moe._dispatch(flat, lay["walk"])
    back = moe._combine(buf, lay["held"].astype(jnp.float32), lay["walk"])
    np.testing.assert_allclose(
        f32(back), f32(flat) * np.asarray(lay["held"]).sum(0)[:, None],
        rtol=1e-6)


def test_tile_rows_counts_each_groups_rows_tile_by_tile():
    sizes = jnp.asarray([0, 300, 128, 1], jnp.int32)
    starts, group, used = gmm.group_layout(sizes, 10)
    np.testing.assert_array_equal(
        moe_rows.tile_rows(sizes, starts, group, used),
        [0, 128, 128, 44, 128, 1, 0, 0, 0, 0])
