"""Grouped matrix multiplication over the row groups of held experts.

``lhs`` is one (M, K) buffer whose rows are sorted by group (an expert's
tokens are one group); ``rhs`` is the (G, K, N) stack of the groups'
matrices; ``out[r] = lhs[r] @ rhs[group of r]``. Three Pallas kernels: the
product (``dtpu_gmm``), the product against the transposed matrices
(``dtpu_gmm_nt``: the backward's d lhs) and the per-group lhs^T dout
(``dtpu_gmm_tn``: the backward's d rhs). ``grouped_matmul`` ties them into
one ``custom_vjp``.

The layout keeps the kernels simple (``group_layout``): every group starts
on a tile boundary of ``TILE_M`` rows and owns at least one tile, so a row
tile belongs to exactly one group and an empty group's gradient is still
written (as zeros). The buffer has a static number of tiles, sized for the
worst case; which group a tile belongs to and how many tiles are in use
arrive as scalar-prefetch operands. A tile beyond those in use costs no
product and no DMA: its index maps point at the last tile in use, whose
blocks are already resident, and the product kernels write zeros for it.
Rows of a group's last tile beyond the group's size must be zero in
``lhs``; they are then zero in every product and add nothing to d rhs.

MXU operands in the inputs' dtype (bf16 in the benchmark), f32 accumulation
(d rhs over a group's tiles in f32 scratch), results in the inputs' dtypes.
Mosaic on TPU, the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import interpret as _interpret, round_up as _round_up

# Rows a grid step multiplies: the MXU's side on the v5e. An expert of the
# benchmark's cell sees about 192 rows a step, so a larger tile would
# mostly multiply padding.
TILE_M = 128
# Caps on a weight block (MXU operand dtype) and on a d rhs block (f32):
# each is double-buffered in VMEM beside the row tiles, under the 16 MB a
# kernel may use on the v5e.
_RHS_BLOCK_ELEMENTS = 2 * 1024 * 1024
_DRHS_BLOCK_ELEMENTS = 512 * 1024


def buffer_rows(pairs: int, groups: int, tile_m: int = TILE_M) -> int:
    """Rows of the static buffer that holds up to ``pairs`` rows in
    ``groups`` tile-aligned groups, whatever their sizes."""
    return _round_up(pairs, tile_m) + groups * tile_m


def group_layout(group_sizes, num_tiles: int, tile_m: int = TILE_M):
    """``(row_starts, tile_group, tiles_used)`` of ``group_sizes`` (G,):
    the first buffer row of each group, the group of each of the buffer's
    ``num_tiles`` tiles (tiles not in use name the last group, so they
    change no block index) and the number of tiles in use, shape (1,)."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = jnp.maximum((sizes + tile_m - 1) // tile_m, 1)
    ends = jnp.cumsum(tiles)
    row_starts = (ends - tiles) * tile_m
    tile_group = jnp.searchsorted(
        ends, jnp.arange(num_tiles, dtype=jnp.int32), side="right")
    tile_group = jnp.minimum(tile_group, sizes.shape[0] - 1).astype(jnp.int32)
    return row_starts, tile_group, ends[-1:].astype(jnp.int32)


def _pick_tile(n: int, cap: int) -> int:
    """``n`` if it fits ``cap``, else its largest divisor that is a
    multiple of 128 and fits; ``n`` where there is none."""
    if n <= cap:
        return n
    for d in range(cap - cap % 128, 0, -128):
        if n % d == 0:
            return d
    return n


# ---------------------------------------------------------------- kernels --
def _gmm_kernel(tile_group_ref, used_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs):
    i = pl.program_id(1)

    @pl.when(i < used_ref[0])
    def _():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(i >= used_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm_tn_kernel(tile_group_ref, used_ref, lhs_ref, dout_ref, out_ref,
                   acc_ref, *, num_tiles):
    """d rhs of one group accumulates in f32 scratch over the group's
    consecutive tiles and is written, in the output's dtype, on its last."""
    i = pl.program_id(2)

    @pl.when(i < used_ref[0])
    def _():
        prod = jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        group = tile_group_ref[i]
        first = jnp.logical_or(
            i == 0, group != tile_group_ref[jnp.maximum(i - 1, 0)])
        last = jnp.logical_or(
            i == used_ref[0] - 1,
            group != tile_group_ref[jnp.minimum(i + 1, num_tiles - 1)])

        @pl.when(first)
        def _():
            acc_ref[...] = prod

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += prod

        @pl.when(last)
        def _():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _used_tile(i, used_ref):
    return jnp.minimum(i, used_ref[0] - 1)


def _gmm_call(lhs, rhs, tile_group, tiles_used, *, transpose_rhs, tile_m):
    m, k = lhs.shape
    g, r1, r2 = rhs.shape
    n = r1 if transpose_rhs else r2
    if (r2 if transpose_rhs else r1) != k:
        raise ValueError(f"lhs {lhs.shape} does not contract with rhs "
                         f"{rhs.shape} (transpose_rhs={transpose_rhs})")
    if m % tile_m:
        raise ValueError(f"{m} rows are no multiple of the tile {tile_m}")
    tn = _pick_tile(n, max(128, _RHS_BLOCK_ELEMENTS // k))
    if transpose_rhs:
        rhs_spec = pl.BlockSpec(
            (1, tn, k), lambda j, i, tg, used: (tg[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (1, k, tn), lambda j, i, tg, used: (tg[i], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tile_m),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, i, tg, used: (_used_tile(i, used), 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tile_m, tn),
                                   lambda j, i, tg, used: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        name="dtpu_gmm_nt" if transpose_rhs else "dtpu_gmm",
        interpret=_interpret(),
    )(tile_group, tiles_used, lhs, rhs)


def _gmm_tn_call(lhs, dout, tile_group, tiles_used, groups, dtype, *,
                 tile_m):
    m, k = lhs.shape
    n = dout.shape[1]
    tn = _pick_tile(n, 1024)
    tk = _pick_tile(k, max(128, _DRHS_BLOCK_ELEMENTS // tn))
    return pl.pallas_call(
        functools.partial(_gmm_tn_kernel, num_tiles=m // tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, m // tile_m),
            in_specs=[
                pl.BlockSpec(
                    (tile_m, tk),
                    lambda a, b, i, tg, used: (_used_tile(i, used), a)),
                pl.BlockSpec(
                    (tile_m, tn),
                    lambda a, b, i, tg, used: (_used_tile(i, used), b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, tg, used: (tg[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        name="dtpu_gmm_tn",
        interpret=_interpret(),
    )(tile_group, tiles_used, lhs, dout)


# ----------------------------------------------------------------- public --
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(lhs, rhs, tile_group, tiles_used, tile_m):
    return _gmm_call(lhs, rhs, tile_group, tiles_used, transpose_rhs=False,
                     tile_m=tile_m)


def _gmm_fwd(lhs, rhs, tile_group, tiles_used, tile_m):
    out = _gmm_call(lhs, rhs, tile_group, tiles_used, transpose_rhs=False,
                    tile_m=tile_m)
    return out, (lhs, rhs, tile_group, tiles_used)


def _gmm_bwd(tile_m, res, dout):
    lhs, rhs, tile_group, tiles_used = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm_call(dout, rhs, tile_group, tiles_used, transpose_rhs=True,
                     tile_m=tile_m)
    drhs = _gmm_tn_call(lhs, dout, tile_group, tiles_used, rhs.shape[0],
                        rhs.dtype, tile_m=tile_m)
    return dlhs, drhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, tile_group, tiles_used, *, tile_m: int = TILE_M):
    """``out[r] = lhs[r] @ rhs[tile_group[r // tile_m]]`` for the rows of
    the ``tiles_used`` first tiles of ``lhs`` (M, K), zeros below them;
    ``rhs`` is (G, K, N), the layout ``group_layout``'s. Differentiable in
    ``lhs`` and ``rhs`` (module docstring)."""
    return _gmm(lhs, rhs, tile_group, tiles_used, tile_m)
