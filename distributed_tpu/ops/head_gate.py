"""A head-wise output gate on the flash kernels' own (B, T, H x 128) layout.

A gated attention layer scales each head's output by a sigmoid of the
layer's input before ``Wo`` (``nn/attention.py:GroupedQueryAttention(gate=
True)``):

    s   = sigmoid(z)                      z = x Wg (B, T, H), float32 inside
    out = round(ctx * s[..., None])       ctx (B, T, H, head_dim)

Written on a float32 (B, T, H, 128) view, XLA:TPU copies the heads' outputs
into another layout and back, in both passes (root PERF.md section 5: 16
ms a step of the Laguna cell's). At 128-wide heads a head is exactly one lane
tile of the (B, T, H x 128) that the flash kernels return and ``Wo`` reads,
and the gate is one scalar a row and tile:

    forward    out = round(c s)
    backward   dc  = round(g s)
               dz  = round((sum over the head's 128 lanes of g c) s (1 - s))

with every product in float32 and each result rounded once to its
operand's dtype: what autodiff of the plain lines gives. ``head_gate`` is
one ``custom_vjp`` over two kernels, ``dtpu_head_gate`` and
``dtpu_head_gate_bwd``. The backward keeps ``ctx`` and ``z`` (the flash
kernels keep ``ctx`` anyway) and recomputes ``s``: no float32 residual.

Grid (batch, row blocks, head blocks), the head blocks innermost, blocks
as ``head_norm_rope.blocks`` sizes them: (rows, heads x 128) of ``ctx``;
inside, a static loop over the block's heads, each a 128-lane slice of all
the block's rows. ``z`` comes padded with zeros to a whole number of lane
tiles, and a row block's (rows, lanes) of it stay in VMEM along the head
blocks: a lane roll by the head block's first head brings its gates to
lanes 0, 1, ... In the backward the block's sums go back by the opposite
roll into a float32 scratch, which the last head block scales by
s (1 - s) and writes as ``dz``, padded like ``z``. A body is unrolled
over a block's heads, not a layer's: at 64 heads the whole layer in one
body took most of a second of the trace and lowering a process start pays
(root PERF.md section 6). Rows past T in the last row block are
computed on whatever the block holds and never written back.

Mosaic on TPU, the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANES, interpret as _interpret, round_up
from .head_norm_rope import _lanes, blocks


def _gates(z_ref, hb):
    """sigmoid of the row block's ``z``, float32, rolled so that the head
    block's first head sits in lane 0."""
    width = z_ref.shape[-1]
    first = pl.program_id(2) * hb
    return pltpu.roll(jax.nn.sigmoid(z_ref[...].astype(jnp.float32)),
                      (width - first) % width, 1)


def _fwd_kernel(z_ref, c_ref, out_ref, *, hb):
    s = _gates(z_ref, hb)
    for j in range(hb):
        c = c_ref[:, _lanes(j)].astype(jnp.float32)
        out_ref[:, _lanes(j)] = (c * s[:, j:j + 1]).astype(out_ref.dtype)


def _bwd_kernel(z_ref, c_ref, g_ref, dc_ref, dz_ref, acc_ref, *, hb):
    s = _gates(z_ref, hb)
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ds = jnp.zeros_like(s)
    for j in range(hb):
        g = g_ref[:, _lanes(j)].astype(jnp.float32)
        dc_ref[:, _lanes(j)] = (g * s[:, j:j + 1]).astype(dc_ref.dtype)
        dot = jnp.sum(g * c_ref[:, _lanes(j)].astype(jnp.float32), axis=-1,
                      keepdims=True)
        ds = jnp.where(lane == j, dot, ds)
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(ds)

    acc_ref[...] += pltpu.roll(ds, block * hb, 1)

    @pl.when(block == pl.num_programs(2) - 1)
    def _():
        s = jax.nn.sigmoid(z_ref[...].astype(jnp.float32))
        dz_ref[...] = (acc_ref[...] * (s * (1.0 - s))).astype(dz_ref.dtype)


def _specs(ctx, width):
    """The grid and the block specs of a (B, T, H x 128) operand and of a
    (B, T, ``width``) one, and the block's rows and heads."""
    b, t, h = *ctx.shape[:2], ctx.shape[-1] // LANES
    rows, hb = blocks(t, h, jnp.dtype(ctx.dtype).itemsize)
    wide = pl.BlockSpec((None, rows, hb * LANES), lambda b, r, j: (b, r, j))
    gate = pl.BlockSpec((None, rows, width), lambda b, r, j: (b, r, 0))
    return (b, pl.cdiv(t, rows), h // hb), wide, gate, rows, hb


# Jitted: a model's layers call each pass at one shape, which is then traced
# and lowered once a program and not once a layer.
@jax.jit
def _forward(ctx, z):
    grid, wide, gate, _, hb = _specs(ctx, z.shape[-1])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb),
        grid=grid, in_specs=[gate, wide], out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(ctx.shape, ctx.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="dtpu_head_gate", interpret=_interpret(),
    )(z, ctx)


@jax.jit
def _backward(ctx, z, g):
    grid, wide, gate, rows, hb = _specs(ctx, z.shape[-1])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb),
        grid=grid, in_specs=[gate, wide, wide], out_specs=[wide, gate],
        out_shape=[jax.ShapeDtypeStruct(ctx.shape, ctx.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        scratch_shapes=[pltpu.VMEM((rows, z.shape[-1]), jnp.float32)],
        # dz's block stays along the head blocks, which add to it in turn.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dtpu_head_gate_bwd", interpret=_interpret(),
    )(z, ctx, g.astype(ctx.dtype))


def _padded(z):
    """``z`` with zeros to a whole number of lane tiles, what a lane roll
    takes."""
    pad = round_up(z.shape[-1], LANES) - z.shape[-1]
    return jnp.pad(z, ((0, 0), (0, 0), (0, pad)))


@jax.custom_vjp
def _gate(ctx, z):
    return _forward(ctx, _padded(z))


def _gate_fwd(ctx, z):
    return _forward(ctx, _padded(z)), (ctx, z)


def _gate_bwd(res, g):
    ctx, z = res
    dc, dz = _backward(ctx, _padded(z), g)
    return dc, dz[..., :z.shape[-1]]


_gate.defvjp(_gate_fwd, _gate_bwd)


def head_gate(ctx, z):
    """``ctx`` (B, T, H x 128), bfloat16 or float32, the heads' outputs as
    the flash kernels return them, each scaled by ``sigmoid(z)`` of its head
    and row, ``z`` (B, T, H) the gate's pre-activation; the same shape and
    dtype out. What ``round(ctx * sigmoid(z)[..., None])`` gives on the (B,
    T, H, 128) view, float32 inside. Differentiable in both; the gradients
    in their operands' dtypes."""
    if (ctx.ndim != 3 or z.shape != (*ctx.shape[:2], ctx.shape[-1] // LANES)
            or ctx.shape[-1] % LANES):
        raise ValueError(
            f"a (B, T, H x {LANES}) output and a (B, T, H) gate; got "
            f"{ctx.shape} and {z.shape}")
    return _gate(ctx, z)
