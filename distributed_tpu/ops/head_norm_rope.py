"""Per-head RMSNorm and the half-split rotation of a projection, in one pass
on the projection's own layout.

A grouped-query layer norms each head of ``x Wq`` and ``x Wk`` and then
rotates it (``nn/attention.py:_rms``, ``rope_half`` / ``rope_rotary``). Written
on a (B, T, H, 128) view, XLA:TPU holds that view with T on the lanes and
copies the projection into and out of it in float32, several times a layer
(root PERF.md, PR 39: 12.4 ms a step of ``copy`` in the Keye cell, forward
alone). At 128-wide heads a head is exactly one lane tile of the projection's
(B, T, H x 128), so neither step needs another layout:

    n   = x * rsqrt(mean(x^2 over the head's 128 lanes) + eps)    float32
    y   = round(n * scale)                  to x's dtype, as ``_rms`` rounds
    out = round(y cos + roll(y, r/2) sin_hi + roll(y, 128 - r/2) sin_lo)

with r the rotated dimensions, ``sin_hi`` holding sin on lanes r/2..r,
``sin_lo`` minus sin on lanes 0..r/2, cos 1 and both sines 0 past r, and the
rotation's factor (YaRN's) in all three: lane rolls against (T, 128) float32
tables, no slice and no concatenate. At r = 128 the two rolls are one and so
are the two sines. ``head_norm_rope`` is one ``custom_vjp`` over two kernels,
``dtpu_head_norm_rope`` and ``dtpu_head_norm_rope_bwd``. The backward keeps
the projection alone (no float32 residual): it recomputes ``n``, turns the
cotangent back,

    dy = g cos + roll(g sin_hi, -r/2) + roll(g sin_lo, r/2)
    dx = rsqrt(..) * (dy scale - n * mean(dy scale n))
    d_scale = sum over rows and heads of dy n

and leaves the scale's gradient as one (128,) float32 partial sum a row
block, which one small XLA sum finishes.

Grid (batch, row blocks, head blocks), the head blocks innermost: the tables'
block index is constant along them, so a row block's tables are fetched
once. A block is (rows, heads x 128); inside, a static loop over the
block's heads, each a 128-lane slice of all the block's rows. Rows past T
in the last row block are computed on whatever the block holds and never
written back; the scale's gradient masks them.

Mosaic on TPU, the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANES, interpret as _interpret

# Rows and heads a block: (512, 8 x 128) is 1 MiB in bfloat16, so the
# backward's three such operands, double-buffered, and a row block's tables
# stay under half of the 16 MiB a kernel may use. Half the rows in float32.
# A head's whole (rows, 128) slice is one value of the body: walked in
# chunks of 32 to 256 rows the same block took up to 2.4 times as long on
# the chip (root PERF.md section 6, PR 39).
BLOCK_ROWS = 512
BLOCK_HEADS = 8


def blocks(t: int, heads: int, itemsize: int):
    """(rows, heads) of a block for T rows of ``heads`` heads: as many heads
    as divide ``heads`` up to ``BLOCK_HEADS``; ``BLOCK_ROWS`` rows (half of
    them under float32), or the whole sequence where that is shorter."""
    rows = BLOCK_ROWS if itemsize <= 2 else BLOCK_ROWS // 2
    hb = max(n for n in range(1, BLOCK_HEADS + 1) if heads % n == 0)
    return min(rows, t), hb


def rotation_tables(t: int, inv_freq, factor: float = 1.0):
    """The rotation's float32 tables over ``LANES`` lanes for positions 0 to
    T - 1: (cos, sin_hi, sin_lo), or (cos, sin) where all ``LANES`` dimensions
    turn (``sin`` then is ``sin_hi + sin_lo``). ``inv_freq`` (r / 2,) are the
    frequencies of the r rotated dimensions; the angle is computed as
    ``rope_half`` and ``rope_rotary`` compute it."""
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    half = inv_freq.shape[0]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    zero = jnp.zeros_like(sin)
    rest = LANES - 2 * half
    pad = lambda a, fill: jnp.pad(a, ((0, 0), (0, rest)),
                                  constant_values=fill)
    cos = pad(jnp.concatenate([cos, cos], axis=1), 1.0)
    if not rest:
        return cos, jnp.concatenate([-sin, sin], axis=1)
    return (cos, pad(jnp.concatenate([zero, sin], axis=1), 0.0),
            pad(jnp.concatenate([-sin, zero], axis=1), 0.0))


def _lanes(j):
    """Head j's lanes of a (rows, heads x 128) block."""
    return slice(j * LANES, (j + 1) * LANES)


def _turn(y, tables, half, back=False):
    """The rotation of y (rows, 128) float32 against its rows of the
    tables, or, ``back``, its transpose applied to a cotangent."""
    roll = lambda a, shift: pltpu.roll(a, shift % LANES, 1)
    if len(tables) == 2:
        cos, sin = tables
        if back:
            return y * cos + roll(y * sin, LANES // 2)
        return y * cos + roll(y, LANES // 2) * sin
    cos, sin_hi, sin_lo = tables
    if back:
        return y * cos + roll(y * sin_hi, -half) + roll(y * sin_lo, half)
    return y * cos + roll(y, half) * sin_hi + roll(y, -half) * sin_lo


def _normalised(x, epsilon):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(
        jnp.mean(xf * xf, axis=-1, keepdims=True) + epsilon)
    return xf * inv, inv


def _fwd_kernel(x_ref, scale_ref, *refs, epsilon, half, heads):
    *table_refs, out_ref = refs
    scale = scale_ref[...]
    tables = [r[...] for r in table_refs]
    for j in range(heads):
        x = x_ref[:, _lanes(j)]
        n, _ = _normalised(x, epsilon)
        y = (n * scale).astype(x.dtype).astype(jnp.float32)
        out_ref[:, _lanes(j)] = _turn(y, tables, half).astype(out_ref.dtype)


def _bwd_kernel(x_ref, g_ref, scale_ref, *refs, epsilon, half, heads, t):
    *table_refs, dx_ref, dscale_ref = refs
    rows = x_ref.shape[0]
    scale = scale_ref[...]
    tables = [r[...] for r in table_refs]
    total = jnp.zeros((rows, LANES), jnp.float32)
    for j in range(heads):
        n, inv = _normalised(x_ref[:, _lanes(j)], epsilon)
        dy = _turn(g_ref[:, _lanes(j)].astype(jnp.float32), tables, half,
                   back=True)
        dn = dy * scale
        dx = inv * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[:, _lanes(j)] = dx.astype(dx_ref.dtype)
        total = total + dy * n
    if t % rows:  # the last row block holds rows past T
        row = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
            jnp.int32, total.shape, 0)
        total = jnp.where(row < t, total, 0.0)
    total = jnp.sum(total, axis=0, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dscale_ref[...] = total

    @pl.when(pl.program_id(2) > 0)
    def _():
        dscale_ref[...] += total


def _call(x, scale, inv_freq, factor, block_rows, block_heads):
    """What both passes hand ``pallas_call``: the grid, the heads a block,
    the block spec of a (B, T, H x 128) operand, the specs and values of the
    small operands (the scale and the tables), and the compiler's
    parameters."""
    b, t, width = x.shape
    h = width // LANES
    rows, hb = blocks(t, h, jnp.dtype(x.dtype).itemsize)
    rows, hb = block_rows or rows, block_heads or hb
    if h % hb:
        raise ValueError(f"{hb} heads a block do not divide {h} heads")
    tables = rotation_tables(t, inv_freq, factor)
    wide = pl.BlockSpec((None, rows, hb * LANES), lambda b, r, c: (b, r, c))
    one = pl.BlockSpec((1, LANES), lambda b, r, c: (0, 0))
    table = pl.BlockSpec((rows, LANES), lambda b, r, c: (r, 0))
    return ((b, pl.cdiv(t, rows), h // hb), hb, wide,
            [one] + [table] * len(tables),
            [scale.astype(jnp.float32).reshape(1, LANES), *tables],
            pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "arbitrary")))


_STATIC = ("factor", "epsilon", "block_rows", "block_heads")


# Jitted: a model's layers call each pass at one shape, which is then traced
# and lowered once a program and not once a layer.
@functools.partial(jax.jit, static_argnames=_STATIC)
def _forward(x, scale, inv_freq, factor, epsilon, block_rows, block_heads):
    grid, hb, wide, small_specs, small, params = _call(
        x, scale, inv_freq, factor, block_rows, block_heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, epsilon=epsilon,
                          half=inv_freq.shape[0], heads=hb),
        grid=grid, in_specs=[wide] + small_specs, out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=params, name="dtpu_head_norm_rope",
        interpret=_interpret(),
    )(x, *small)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _backward(x, scale, inv_freq, g, factor, epsilon, block_rows,
              block_heads):
    grid, hb, wide, small_specs, small, params = _call(
        x, scale, inv_freq, factor, block_rows, block_heads)
    dx, partial = pl.pallas_call(
        functools.partial(_bwd_kernel, epsilon=epsilon,
                          half=inv_freq.shape[0], heads=hb, t=x.shape[1]),
        grid=grid, in_specs=[wide, wide] + small_specs,
        out_specs=[wide, pl.BlockSpec((None, None, 1, LANES),
                                      lambda b, r, c: (b, r, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((*grid[:2], 1, LANES), jnp.float32)],
        compiler_params=params, name="dtpu_head_norm_rope_bwd",
        interpret=_interpret(),
    )(x, g.astype(x.dtype), *small)
    return dx, jnp.sum(partial, axis=(0, 1, 2)).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _prep(x, scale, inv_freq, factor, epsilon, block_rows, block_heads):
    return _forward(x, scale, inv_freq, factor, epsilon, block_rows,
                    block_heads)


def _prep_fwd(x, scale, inv_freq, factor, epsilon, block_rows, block_heads):
    return _forward(x, scale, inv_freq, factor, epsilon, block_rows,
                    block_heads), (x, scale, inv_freq)


def _prep_bwd(factor, epsilon, block_rows, block_heads, res, g):
    x, scale, inv_freq = res
    dx, dscale = _backward(x, scale, inv_freq, g, factor, epsilon,
                           block_rows, block_heads)
    return dx, dscale, None


_prep.defvjp(_prep_fwd, _prep_bwd)


def head_norm_rope(x, scale, rotation, *, epsilon: float,
                   block_rows: int | None = None,
                   block_heads: int | None = None):
    """Per-head RMSNorm, then the half-split rotation, of a projection ``x``
    (B, T, H x 128), bfloat16 or float32, as it leaves its product; the same
    shape and dtype out. ``scale`` (128,) is the norm's; ``rotation`` is
    ``(inv_freq, factor)``: the frequencies of the rotated dimensions, (r /
    2,) float32 for the first r of a head's 128 (``rope_half``'s own at r =
    128, ``yarn_inv_freq``'s for a partial YaRN rotation), and the factor on
    cos and sin. What ``_rms`` then ``rope_half`` / ``rope_rotary`` return on
    the (B, T, H, 128) view, up to float32 reassociation: float32 inside,
    rounded to ``x``'s dtype after the norm and after the rotation.
    Differentiable in ``x`` and ``scale``; the frequencies take no gradient.
    ``block_rows`` and ``block_heads`` override ``blocks``."""
    inv_freq, factor = rotation
    if x.ndim != 3 or x.shape[-1] % LANES or scale.shape != (LANES,):
        raise ValueError(
            f"a (B, T, H x {LANES}) projection and a ({LANES},) scale; got "
            f"{x.shape} and {scale.shape}")
    inv_freq = jnp.asarray(inv_freq, jnp.float32)
    if not 0 < 2 * inv_freq.shape[0] <= LANES:
        raise ValueError(
            f"{inv_freq.shape[0]} frequencies turn no even width within "
            f"{LANES} dimensions")
    return _prep(x, scale, inv_freq, float(factor), float(epsilon),
                 block_rows, block_heads)
