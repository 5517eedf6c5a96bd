"""Row movements of the dropless expert layer, over the tiles in use.

``nn.DroplessMoE`` sorts its (token, choice) pairs by expert into one buffer
of static shape, sized for every pair landing on this chip
(``grouped_matmul.group_layout``: each group starts on a tile of ``TILE_M``
rows, the rows a group does not fill are padding). The grouped matmuls
follow the tiles in use; so do the two walks here, which carry rows into the
buffer and back:

``gather_rows`` (row-major: dispatch forward, combine backward). For each
tile in use, a buffer row takes its token's row of a (n, d) source, padded
rows take zeros; optionally each row is scaled by its pair's gate (f32) and
dotted with the same row of a second buffer, the dots going back to the
pairs. Tiles not in use are not written at all: whoever reads the result
follows ``tiles_used`` too.

``sum_rows`` (token-major: combine forward, dispatch backward). Token i
takes the f32 sum of the valid buffer rows that name it, each times its
pair's gate: a walk over the tiles in use that adds each row into an
accumulator held in VMEM, so a pair that is not held costs nothing and adds
exactly zero.

Both are handed ``row_pair`` (M,), the pair j * n + i of each buffer row
(token i's j-th choice), ``rows_in_tile``, the valid rows of each tile
(``tile_rows``) and ``tiles_used``, as scalar-prefetch operands; a pair's
gate and a row's token are looked up in scalar memory, so no index vector is
gathered or scattered by XLA for them.

Both kernels keep a row as a block of (d / 128, 128): Mosaic moves no slice
of fewer than 8 rows of a tiled operand, so a single row of a (rows, d)
array cannot be fetched, and a row spread over one sublane of d / 128
registers costs a register operation per 128 values. ``gather_rows`` is
handed its source in that form (one relayout of n rows by XLA) and fetches a
row with one contiguous copy; both change between the two forms in VMEM with
sublane-strided loads and stores. The loops over tiles and rows are
``fori_loop``s to ``tiles_used`` and to the tile's count of valid rows:
nothing is traced per tile or per row, and an unused tile costs nothing.

Mosaic on TPU, the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import (
    LANES, interpret as _interpret, round_up as _round_up)
from .grouped_matmul import TILE_M

# Rows of a (d / 128, 128) row block are sliced out of tiled operands, bf16
# ones too: their count is padded to a bf16 tile's 16 sublanes.
_ROW_BLOCK_ALIGN = 16
# Cap on sum_rows' accumulator (f32, all the tokens of a grid step).
_ACC_BYTES = 32 * 1024 * 1024


def _for_chunks(d: int, body, carry=None):
    """``carry = body(i, lanes, width, carry)`` over the 128-lane chunks of a
    row of ``d``: the whole ones in a loop (traced once), a narrower last
    one after it."""
    whole = d // LANES

    def step(i, carry):
        return body(i, pl.ds(pl.multiple_of(i * LANES, LANES), LANES), LANES,
                    carry)

    if whole:
        carry = jax.lax.fori_loop(0, whole, step, carry)
    if d % LANES:
        carry = body(whole, pl.ds(whole * LANES, d % LANES), d % LANES, carry)
    return carry


def tile_rows(group_sizes, row_starts, tile_group, tiles_used):
    """Valid rows of each tile of ``group_layout``'s buffer (tiles of
    ``TILE_M``): a group's rows fill its tiles in order; 0 for a tile not in
    use."""
    t = jnp.arange(tile_group.shape[0], dtype=jnp.int32)
    first = jnp.take(row_starts, tile_group) // TILE_M
    left = jnp.take(group_sizes.astype(jnp.int32), tile_group) - (
        t - first) * TILE_M
    return jnp.where(t < tiles_used[0], jnp.clip(left, 0, TILE_M), 0)


# ------------------------------------------------------------ gather_rows --
def _gather_kernel(pair_ref, rows_ref, used_ref, *refs, n, d, cp, scaled):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None  # the pairs' gates, SMEM
    src_ref = refs.pop(0)
    other_ref = refs.pop(0) if scaled else None
    out_ref = refs.pop(0)
    dots_ref = refs.pop(0) if scaled else None
    fetched, fetched_f32, staged = refs[:3]
    other_buf, gate_rows, dot_rows = refs[3:6] if scaled else (None,) * 3
    row_sem, out_sem, other_sem = refs[-3:]
    used = used_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def row_copy(token, r, slot):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(pl.multiple_of(token * cp, cp), cp)],
            fetched.at[slot, pl.ds(pl.multiple_of(r * cp, cp), cp)],
            row_sem.at[slot])

    def other_copy(t, slot):
        return pltpu.make_async_copy(
            other_ref.at[pl.ds(pl.multiple_of(t * TILE_M, TILE_M), TILE_M)],
            other_buf.at[slot], other_sem.at[slot])

    def out_copy(t, slot):
        return pltpu.make_async_copy(
            staged.at[slot],
            out_ref.at[pl.ds(pl.multiple_of(t * TILE_M, TILE_M), TILE_M)],
            out_sem.at[slot])

    def start(t, slot):
        def body(r, carry):
            token = jax.lax.rem(pair_ref[t * TILE_M + r], n)
            row_copy(token, r, slot).start()
            return carry

        jax.lax.fori_loop(0, rows_ref[t], body, 0)
        if scaled:
            other_copy(t, slot).start()

    def wait(t, slot):
        def body(r, carry):
            row_copy(0, r, slot).wait()  # any row: a wait counts its bytes
            if scaled:  # row r of gate_rows: the row's gate in every lane
                gate_rows[pl.ds(r, 1), :] = jnp.full(
                    (1, LANES), scale_ref[pair_ref[t * TILE_M + r]])
            return carry

        jax.lax.fori_loop(0, rows_ref[t], body, 0)
        if scaled:
            other_copy(t, slot).wait()

    if scaled:
        dots_ref[...] = jnp.zeros_like(dots_ref)

    @pl.when(used > 0)
    def _():
        start(0, 0)

    def tile(t, carry):
        slot = t % 2

        @pl.when(t + 1 < used)
        def _():
            start(t + 1, 1 - slot)

        wait(t, slot)

        @pl.when(t >= 2)  # the tile staged here two steps ago has left
        def _():
            out_copy(t - 2, slot).wait()

        fetched_f32[...] = fetched[slot].astype(jnp.float32)
        # A padded row was not fetched and has no gate: what lies there is
        # dropped, never multiplied.
        valid = jax.lax.broadcasted_iota(
            jnp.int32, (TILE_M, 1), 0) < rows_ref[t]

        def chunk(i, lanes, w, dots):
            x = fetched_f32[pl.ds(i, TILE_M, stride=cp), :]
            if scaled:
                other = other_buf[slot, :, lanes].astype(jnp.float32)
                if w < LANES:
                    other = jnp.pad(other, ((0, 0), (0, LANES - w)))
                    other = jnp.where(lane < w, other, 0.0)
                    x = jnp.where(lane < w, x, 0.0)
                dots = dots + jnp.where(valid, x * other, 0.0)
                x = x * gate_rows[...]
            x = jnp.where(valid, x, 0.0)
            staged[slot, :, lanes] = x[:, :w].astype(staged.dtype)
            return dots

        dots = _for_chunks(d, chunk, jnp.zeros((TILE_M, LANES), jnp.float32))
        out_copy(t, slot).start()
        if scaled:
            # Each row's dot goes to its pair: element pair of (k * n,),
            # held as rows of 128 lanes.
            dot_rows[...] = jnp.broadcast_to(
                jnp.sum(dots, axis=1, keepdims=True), (TILE_M, LANES))

            def put(r, carry):
                pair = pair_ref[t * TILE_M + r]
                at = pl.ds(pair // LANES, 1)
                dots_ref[at, :] = jnp.where(
                    lane == pair % LANES, dot_rows[pl.ds(r, 1), :],
                    dots_ref[at, :])
                return carry

            jax.lax.fori_loop(0, rows_ref[t], put, 0)
        return carry

    jax.lax.fori_loop(0, used, tile, 0)
    for back in (1, 2):
        @pl.when(used >= back)
        def _():
            out_copy(used - back, (used - back) % 2).wait()


# Jitted: the expert layers of a model call each walk at one shape, which is
# then traced and lowered once a program and not once a layer.
@jax.jit
def gather_rows(src, row_pair, rows_in_tile, tiles_used, *,
                pair_scale: Optional[jax.Array] = None,
                dot_with: Optional[jax.Array] = None):
    """``out[r] = src[row_pair[r] % n]`` for the ``rows_in_tile[t]`` first
    rows of each of the ``tiles_used`` first tiles of a buffer of
    ``len(row_pair)`` rows; zeros on a tile's other rows; the tiles not in
    use are left as they were found. With ``pair_scale`` (k, n) float32 and
    ``dot_with`` (M, d), combine's backward: a row is multiplied, in
    float32, by ``pair_scale`` of its pair, and a second result (k, n)
    float32 holds, for the pair of each valid row, the dot of the row as
    fetched with the same row of ``dot_with``; zero for a pair with no
    row."""
    n, d = src.shape
    m = row_pair.shape[0]
    scaled = pair_scale is not None
    if scaled != (dot_with is not None):
        raise ValueError("pair_scale and dot_with come together")
    cp = _round_up(-(-d // LANES), _ROW_BLOCK_ALIGN)
    # A row as one contiguous (cp, 128) block: see the module docstring.
    dense = jnp.pad(src, ((0, 0), (0, cp * LANES - d))).reshape(
        n * cp, LANES)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    prefetch = [row_pair, rows_in_tile, tiles_used]
    operands, in_specs = [dense], [any_space]
    out_shape = [jax.ShapeDtypeStruct((m, d), src.dtype)]
    out_specs = [any_space]
    scratch = [pltpu.VMEM((2, TILE_M * cp, LANES), src.dtype),
               pltpu.VMEM((TILE_M * cp, LANES), jnp.float32),
               pltpu.VMEM((2, TILE_M, d), src.dtype)]
    if scaled:
        pairs = pair_scale.size
        pair_rows = -(-pairs // LANES)
        prefetch.append(pair_scale.astype(jnp.float32).reshape(-1))
        operands.append(dot_with)
        in_specs.append(any_space)
        out_shape.append(
            jax.ShapeDtypeStruct((pair_rows, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((pair_rows, LANES),
                                      lambda i, *_: (0, 0)))
        scratch += [pltpu.VMEM((2, TILE_M, d), dot_with.dtype),
                    pltpu.VMEM((TILE_M, LANES), jnp.float32),
                    pltpu.VMEM((TILE_M, LANES), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2,))] * 3
    out = pl.pallas_call(
        functools.partial(_gather_kernel, n=n, d=d, cp=cp, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(1,), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        name="dtpu_moe_rows_gather",
        interpret=_interpret(),
    )(*prefetch, *operands)
    if not scaled:
        return out[0]
    return out[0], out[1].reshape(-1)[:pairs].reshape(pair_scale.shape)


# --------------------------------------------------------------- sum_rows --
def _sum_kernel(pair_ref, rows_ref, used_ref, *refs, n, d, cp, block, blocks,
                scaled):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    buf_ref, out_ref, tile_buf, dense, acc, staged, tile_sem, out_sem = refs
    used = used_ref[0]
    low = pl.program_id(0) * block

    def tile_copy(t, slot):
        return pltpu.make_async_copy(
            buf_ref.at[pl.ds(pl.multiple_of(t * TILE_M, TILE_M), TILE_M)],
            tile_buf.at[slot], tile_sem.at[slot])

    def out_copy(j, slot):
        return pltpu.make_async_copy(
            staged.at[slot],
            out_ref.at[pl.ds(pl.multiple_of(low + j * TILE_M, TILE_M),
                             TILE_M)],
            out_sem.at[slot])

    acc[...] = jnp.zeros_like(acc)

    @pl.when(used > 0)
    def _():
        tile_copy(0, 0).start()

    def tile(t, carry):
        slot = t % 2

        @pl.when(t + 1 < used)
        def _():
            tile_copy(t + 1, 1 - slot).start()

        tile_copy(t, slot).wait()

        def chunk(i, lanes, w, carry):
            dense[pl.ds(i, TILE_M, stride=cp), 0:w] = tile_buf[
                slot, :, lanes].astype(jnp.float32)

        _for_chunks(d, chunk)

        def row(r, carry):
            pair = pair_ref[t * TILE_M + r]
            token = jax.lax.rem(pair, n) - low

            def add():
                z = dense[pl.ds(pl.multiple_of(r * cp, cp), cp), :]
                if scaled:
                    z = z * scale_ref[pair]
                at = pl.ds(pl.multiple_of(token * cp, cp), cp)
                acc[at, :] = acc[at, :] + z

            if blocks == 1:
                add()
            else:
                pl.when(jnp.logical_and(token >= 0, token < block))(add)
            return carry

        jax.lax.fori_loop(0, rows_ref[t], row, 0)
        return carry

    jax.lax.fori_loop(0, used, tile, 0)

    def write(j, carry):
        slot = j % 2

        @pl.when(j >= 2)
        def _():
            out_copy(j - 2, slot).wait()

        def chunk(i, lanes, w, carry):
            staged[slot, :, lanes] = acc[
                pl.ds(pl.multiple_of(j * TILE_M * cp, cp) + i, TILE_M,
                      stride=cp), 0:w].astype(staged.dtype)

        _for_chunks(d, chunk)
        out_copy(j, slot).start()
        return carry

    steps = block // TILE_M
    jax.lax.fori_loop(0, steps, write, 0)
    for back in range(1, min(steps, 2) + 1):
        out_copy(steps - back, (steps - back) % 2).wait()


@functools.partial(jax.jit, static_argnames="num_tokens")
def sum_rows(buf, row_pair, rows_in_tile, tiles_used, num_tokens: int, *,
             pair_scale: Optional[jax.Array] = None):
    """``y[i]``: the float32 sum of ``buf[r]``, times ``pair_scale`` (k, n)
    float32 of the row's pair where given, over the valid rows r
    (``gather_rows``'s) of token i (``row_pair[r] % num_tokens == i``);
    (num_tokens, d) in ``buf``'s dtype; a token no row names reads zeros."""
    m, d = buf.shape
    scaled = pair_scale is not None
    cp = _round_up(-(-d // LANES), _ROW_BLOCK_ALIGN)
    padded = _round_up(num_tokens, TILE_M)
    block = min(padded, max(
        TILE_M, _ACC_BYTES // (cp * LANES * 4) // TILE_M * TILE_M))
    padded = _round_up(padded, block)
    blocks = padded // block
    item = jnp.dtype(buf.dtype).itemsize
    vmem = ((block + TILE_M) * cp * LANES * 4 + 4 * TILE_M * d * item
            + 4 * 1024 * 1024)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    prefetch = [row_pair, rows_in_tile, tiles_used]
    if scaled:
        prefetch.append(pair_scale.astype(jnp.float32).reshape(-1))
    y = pl.pallas_call(
        functools.partial(_sum_kernel, n=num_tokens, d=d, cp=cp, block=block,
                          blocks=blocks, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(blocks,),
            in_specs=[any_space], out_specs=any_space,
            scratch_shapes=[
                pltpu.VMEM((2, TILE_M, d), buf.dtype),
                pltpu.VMEM((TILE_M * cp, LANES), jnp.float32),
                pltpu.VMEM((block * cp, LANES), jnp.float32),
                pltpu.VMEM((2, TILE_M, d), buf.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((padded, d), buf.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        name="dtpu_moe_rows_sum",
        interpret=_interpret(),
    )(*prefetch, buf)
    return y if padded == num_tokens else y[:num_tokens]
