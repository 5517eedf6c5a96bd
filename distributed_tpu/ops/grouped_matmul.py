"""The held experts' gated MLP over their buffer, as grouped matrix
multiplications that follow the tiles in use.

``buf`` is one (M, K) buffer whose rows are sorted by group (an expert's
tokens are one group); a weight is the (G, K, N) stack of the groups'
matrices, and a grouped product is ``out[r] = lhs[r] @ rhs[group of r]``.
Three Pallas kernels: the product (``dtpu_gmm``), the product against the
transposed matrices (``dtpu_gmm_nt``: the backward's d lhs) and the
per-group lhs^T dout (``dtpu_gmm_tn``: the backward's d rhs).
``grouped_gated_mlp`` ties nine calls of them, each one product, into one
``custom_vjp``: ``(silu(buf w_gate) * (buf w_up)) w_down`` and its four
gradients. What lies between the products rides in their epilogues, on the
tile the product has just made: the call that makes ``u = buf w_up`` reads
the finished tile of ``g = buf w_gate`` and also writes ``h = silu(g) u``;
the backward's product against ``w_down`` holds its tile of dh in VMEM,
reads g and u and writes dg and du in its place (dh is never stored); and
the second of the two products that make d buf adds the first's result to
its accumulator before the store. g and u are rounded to the buffer's dtype
before the activation, so the forward is what the backward differentiates;
kept for the backward are ``buf``, g, u, h and the weights.

The layout keeps the kernels simple (``group_layout``): every group starts
on a tile boundary of ``TILE_M`` rows and owns at least one tile, so a row
tile belongs to exactly one group and an empty group's gradient is still
written (as zeros). The buffer has a static number of tiles, sized for the
worst case; which group a tile belongs to arrives as a scalar-prefetch
operand, and how many tiles are in use is the bound of every grid's
row-tile axis, read at run time. A tile beyond those in use is no grid
step: nothing is fetched, computed or written for it, and that part of
every result is left as it was found. Whoever reads a result follows
``tiles_used`` too, as ``ops.moe_rows``'s walks do. Rows of a group's last
tile beyond the group's size must be zero in ``buf`` and in d out; they are
then zero in every product and add nothing to d rhs.

MXU operands in the inputs' dtype (bf16 in the benchmark), f32 accumulation
(d rhs over a group's tiles in f32 scratch), the epilogues in f32 on the
accumulator and on g and u as stored, results in the inputs' dtypes.
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
def _f32(ref):
    return ref[...].astype(jnp.float32)


# What a product does with its f32 accumulator and the row tiles beside it.
def _store(acc, out_ref):
    out_ref[...] = acc.astype(out_ref.dtype)


def _add(acc, add_ref, out_ref):
    out_ref[...] = (acc + _f32(add_ref)).astype(out_ref.dtype)


def _gate(u, g_ref, u_ref, h_ref):
    """``h = silu(g) u`` of g and u as stored, so that the backward
    differentiates what the forward computed."""
    g, u = _f32(g_ref), u.astype(u_ref.dtype)
    u_ref[...] = u
    h_ref[...] = (g * jax.nn.sigmoid(g) * u.astype(jnp.float32)).astype(
        h_ref.dtype)


def _gate_bwd(dh, g_ref, u_ref, dg_ref, du_ref):
    """``dg = dh u silu'(g)`` and ``du = dh silu(g)``; dh is never stored."""
    g = _f32(g_ref)
    s = jax.nn.sigmoid(g)
    silu = g * s
    dg_ref[...] = (dh * _f32(u_ref) * (s + silu * (1.0 - s))).astype(
        dg_ref.dtype)
    du_ref[...] = (dh * silu).astype(du_ref.dtype)


# epilogue: (row tiles it reads beside the accumulator, results)
_EPILOGUES = {_store: (0, 1), _add: (1, 1), _gate: (1, 2), _gate_bwd: (2, 2)}


def _gmm_kernel(tile_group_ref, lhs_ref, rhs_ref, *refs, transpose_rhs,
                epilogue):
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
        ((1,), (0,)), ((), ()))
    epilogue(jax.lax.dot_general(lhs_ref[...], rhs_ref[0], dims,
                                 preferred_element_type=jnp.float32), *refs)


def _gmm_tn_kernel(tile_group_ref, lhs_ref, dout_ref, out_ref, acc_ref):
    """d rhs of one group accumulates in f32 scratch over the group's
    consecutive tiles and is written, in the output's dtype, on its last."""
    i, used = pl.program_id(2), pl.num_programs(2)
    prod = jax.lax.dot_general(
        lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    group = tile_group_ref[i]
    first = jnp.logical_or(
        i == 0, group != tile_group_ref[jnp.maximum(i - 1, 0)])
    last = jnp.logical_or(
        i == used - 1, group != tile_group_ref[jnp.minimum(i + 1, used - 1)])

    @pl.when(first)
    def _():
        acc_ref[...] = prod

    @pl.when(jnp.logical_not(first))
    def _():
        acc_ref[...] += prod

    @pl.when(last)
    def _():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _gmm_call(lhs, rhs, tile_group, tiles_used, *tiles, transpose_rhs=False,
              epilogue=_store, tile_m):
    """One product over the tiles in use, ``lhs @ rhs[group]`` or, with
    ``transpose_rhs``, ``lhs @ rhs[group]^T``; ``tiles`` are the (M, N)
    operands its epilogue reads a tile at a time. ``_add``'s result takes
    the place of the operand added."""
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
        rhs_spec = pl.BlockSpec((1, tn, k), lambda j, i, tg: (tg[i], j, 0))
    else:
        rhs_spec = pl.BlockSpec((1, k, tn), lambda j, i, tg: (tg[i], 0, j))
    tile_spec = pl.BlockSpec((tile_m, tn), lambda j, i, tg: (i, j))
    operands, results = _EPILOGUES[epilogue]
    assert len(tiles) == operands, (epilogue.__name__, len(tiles))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs,
                          epilogue=epilogue),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tn, tiles_used[0]),
            in_specs=[pl.BlockSpec((tile_m, k), lambda j, i, tg: (i, 0)),
                      rhs_spec] + [tile_spec] * operands,
            out_specs=[tile_spec] * results,
        ),
        out_shape=[jax.ShapeDtypeStruct((m, n), lhs.dtype)] * results,
        input_output_aliases={3: 0} if epilogue is _add else {},
        name="dtpu_gmm_nt" if transpose_rhs else "dtpu_gmm",
        interpret=_interpret(),
    )(tile_group, lhs, rhs, *tiles)
    return out[0] if results == 1 else out


def _gmm_tn_call(lhs, dout, tile_group, tiles_used, groups, dtype, *,
                 tile_m):
    m, k = lhs.shape
    n = dout.shape[1]
    tn = _pick_tile(n, 1024)
    tk = _pick_tile(k, max(128, _DRHS_BLOCK_ELEMENTS // tn))
    return pl.pallas_call(
        _gmm_tn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // tk, n // tn, tiles_used[0]),
            in_specs=[
                pl.BlockSpec((tile_m, tk), lambda a, b, i, tg: (i, a)),
                pl.BlockSpec((tile_m, tn), lambda a, b, i, tg: (i, b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, tg: (tg[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        name="dtpu_gmm_tn",
        interpret=_interpret(),
    )(tile_group, lhs, dout)


# ----------------------------------------------------------------- public --
# Jitted: a model's expert layers call each pass at one shape, which is then
# traced and lowered once a program and not once a layer.
@functools.partial(jax.jit, static_argnames="tile_m")
def _forward(buf, w_gate, w_up, w_down, tile_group, tiles_used, tile_m):
    call = functools.partial(_gmm_call, tile_m=tile_m)
    g = call(buf, w_gate, tile_group, tiles_used)
    u, h = call(buf, w_up, tile_group, tiles_used, g, epilogue=_gate)
    return call(h, w_down, tile_group, tiles_used), (g, u, h)


@functools.partial(jax.jit, static_argnames="tile_m")
def _backward(res, d_out, tile_m):
    buf, w_gate, w_up, w_down, tile_group, tiles_used, g, u, h = res
    layout = (tile_group, tiles_used)
    nt = functools.partial(_gmm_call, transpose_rhs=True, tile_m=tile_m)
    tn = lambda lhs, dout, w: _gmm_tn_call(
        lhs, dout, *layout, w.shape[0], w.dtype, tile_m=tile_m)
    d_out = d_out.astype(buf.dtype)
    dg, du = nt(d_out, w_down, *layout, g, u, epilogue=_gate_bwd)
    d_buf = nt(du, w_up, *layout, nt(dg, w_gate, *layout), epilogue=_add)
    return (d_buf, tn(buf, dg, w_gate), tn(buf, du, w_up),
            tn(h, d_out, w_down))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _mlp(buf, w_gate, w_up, w_down, tile_group, tiles_used, tile_m):
    return _forward(buf, w_gate, w_up, w_down, tile_group, tiles_used,
                    tile_m)[0]


def _mlp_fwd(buf, w_gate, w_up, w_down, tile_group, tiles_used, tile_m):
    out, kept = _forward(buf, w_gate, w_up, w_down, tile_group, tiles_used,
                         tile_m)
    return out, (buf, w_gate, w_up, w_down, tile_group, tiles_used, *kept)


def _mlp_bwd(tile_m, res, d_out):
    return (*_backward(res, d_out, tile_m), None, None)


_mlp.defvjp(_mlp_fwd, _mlp_bwd)


def grouped_gated_mlp(buf, w_gate, w_up, w_down, tile_group, tiles_used, *,
                      tile_m: int = TILE_M):
    """``(silu(buf @ w_gate[e]) * (buf @ w_up[e])) @ w_down[e]`` for the
    rows of the ``tiles_used`` first tiles of ``buf`` (M, K), ``e`` the
    group ``tile_group`` gives the row's tile (``group_layout``'s layout);
    the other tiles of the result are left as they were found. ``w_gate``
    and ``w_up`` are (G, K, H), ``w_down`` (G, H, K). Differentiable in
    ``buf`` and the three weights (module docstring)."""
    return _mlp(buf, w_gate, w_up, w_down, tile_group, tiles_used, tile_m)
