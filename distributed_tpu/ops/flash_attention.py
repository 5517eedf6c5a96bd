"""Flash attention: Pallas TPU forward kernel + blockwise backward.

The dense attention path materializes the (B, H, T, T) score tensor in HBM —
at T=8k and 12 heads that is the whole memory budget. This kernel computes
softmax(QK^T)V with the online-softmax recurrence entirely in VMEM: the
grid walks (batch, head blocks, q_blocks, kv_blocks) with the kv dimension
innermost and sequential, carrying the running max/sum/accumulator in
scratch, so HBM traffic is O(T*D) instead of O(T^2).

The backward pass is a pair of Pallas kernels (dq with the kv dimension
innermost; dk/dv with the q dimension innermost) that recompute the
probabilities in VMEM from the saved per-row statistic — flash-style
rematerialization; HBM traffic stays O(T*D) and no (T, T) matrix ever
exists. (The first implementation was a plain-JAX blockwise scan; on the
TPU it ran at ~12% MFU per layer because XLA serialized the kv-block loop
as a while op — the kernels keep the MXU busy instead.)

One set of kernels, two layouts, told apart by what the kernels read from
their refs' shapes (lanes a head block, heads that share it, a value width
of its own). *Lane-packed* (64- and 128-wide heads: the GPT-2 cells):
(B, T, H*D) as projected, a (block_q, block_k) tile of 128//D heads in
VMEM a grid step. *Folded*, (B*H, T, D): every other shape, one head a
block, q and k padded to whole lanes and v to its own (latent attention:
192-wide keys in 256 lanes, 128-wide values), behind a transpose. A grid
block is as large as VMEM allows (at T <= 1024 the whole sequence, at 4096
8 x 4 blocks a head); the kernels walk it in square sub-tiles (``_subtile``:
side ``_SUBTILE`` a 128 lanes of head block) and treat each by where it
lies (``_tile_class``), statically for a shape:

- above the diagonal (or wholly in the padding, when not causal): not
  computed, no product, no exp, no mask;
- wholly at or below the diagonal and real: computed with no mask, and
  merged with its neighbours of the same kind into one product a head,
  along a row of sub-tiles and, where rows come out alike, across them
  (``_passes``): a grid block the diagonal does not touch is one product;
- crossed by the diagonal (or the padding edge): the only ones that build
  ``_valid_mask``.

At T = 1024 and side 128 that is 36 of 64 sub-tiles computed, 8 of them
masked; at T = 4096, 528 of 1024 and 32, or 136 of 256 and 16 where the
head block is 256 lanes and the side with it (``_subtile``,
``subtile_counts``; published as the gauges ``flash.subtiles_square`` /
``_computed`` / ``_masked``). How a grid block lies against the diagonal is
one of a few static *views* (``_block_views``), each an unrolled walk under
its own ``pl.when``; blocks above the diagonal run none. The walk is
set-up time as well as kernel time: what it unrolls is traced and lowered. ``causal=False`` runs the same walk with every
sub-tile of the second kind. What else the walk rests on, the mathematics
in one precision throughout (f32 scores, statistics and accumulators, bf16
MXU operands, exact divide, once a row):

- one row statistic, lse = m + log l, is saved for the backward, lane-major
  (4 bytes a row and head), whose kernels compute p = exp(s - lse) with no
  per-score divide;
- 1/sqrt(D) is folded into q where that is exact in q's dtype (a power of
  two: D = 64), and applied to every f32 score where not (D = 128, 192);
- a head's operand is not sliced out of a shared lane block (a rotate per
  use) but has the other heads' lanes zeroed, contracts over the whole block
  and produces the whole block, of which the head's own span is kept: the
  same MXU passes, no XLU work, dense stores; a head that has the block to
  itself is taken as it stands;
- dk/dv compute the score tile column-major (k q^T), so p^T and ds^T are
  left operands as they stand, and read their row statistics lane-major.

Two things a caller may add, both for 128-wide heads in the lane-packed
layout, neither touching a call that does not ask: *shared K/V heads*
(grouped queries: the forward's and dq's grids, (b, K/V head, q block, kv
block, head of the group), walk a group's query heads innermost on the K
and V blocks, and the selection's, that the first of them fetched, since a
block whose index did not change is not copied again: each is read once a
K/V head, not once a query head (``kv_block_fetches``; the gauges
``flash.kv_block_fetches`` / ``_a_head``). The q-side blocks hold the whole
group's lanes and the body takes its head's as a view; where that is more
than VMEM has room for, at a large group or float32 inputs, fewer heads
share a fetch, down to one (``_heads_a_fetch``). dk/dv's grid walks,
q block by q block, a group's query heads into one accumulator, the
selection's block staying put under them), and a *selection* (learned
sparse attention: an int8 (T, T) operand, one for all heads, and-ed with
``_valid_mask`` in every segment's mask, with a flag a grid block in scalar
memory so that a block with no selected pair runs no walk; the kernels are
then named ``dtpu_flash_*_sel`` and hand back their row statistic). A
selection masks dense sub-tiles: it saves work only where whole grid blocks
go unselected.

The walk is unrolled at trace time (the LLO scheduler packs straight-line
code best; an in-kernel ``fori_loop`` over sub-tiles cannot merge runs),
so the custom_vjp's two halves are jitted: a model's layers share one
trace and one lowering of each kernel. Chip numbers: root PERF.md.

The reference has no attention anywhere (SURVEY.md §2c); this is part of the
long-context tier the framework adds (with ops.ring_attention for the
sequence-parallel case — ring attention distributes *across chips*, flash
attention blocks *within* a chip; MultiHeadAttention composes them).

CPU/tests run the same kernel via Pallas interpret mode; on TPU it compiles
to Mosaic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from ..obs.registry import default_registry
from ._pallas_common import (
    LANES as _LANES,
    NEG as _NEG,
    interpret as _interpret,
    packed_supported as _packed_supported,
    round_up as _round_up,
)


# -------------------------------------------------- shared kernel helpers --
def _valid_mask(row0, col0, rows, cols, t_actual, causal, kv_major=False,
                window=None):
    """(rows, cols) mask of the score tile whose first row and column are
    ``row0`` and ``col0``: real columns, and under causality the lower-
    triangular band, ``window`` columns wide where one is given (row r sees
    r - window < c <= r). ``kv_major``: of the transposed tile, (cols,
    rows). The single source of truth for masking: the three kernels build
    it over a run of sub-tiles the diagonal, the window's edge or the
    padding edge crosses."""
    shape, r_ax, c_ax = ((cols, rows), 1, 0) if kv_major else (
        (rows, cols), 0, 1)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, c_ax)
    valid = col < t_actual
    if causal:
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, r_ax)
        valid = jnp.logical_and(valid, col <= row)
        if window is not None:
            valid = jnp.logical_and(valid, col > row - window)
    return valid


def _lane_pad(d: int) -> int:
    return _round_up(max(d, _LANES), _LANES)


def _pad(x, t_pad: int, width: int):
    """(b, T, n) zero-padded to (b, t_pad, width)."""
    return jnp.pad(
        x, ((0, 0), (0, t_pad - x.shape[1]), (0, width - x.shape[2])))


# ------------------------------------------------------ the sub-tile walk --
# One set of kernels serves both layouts: they read how many lanes a head
# block has from their refs, and how many heads share it from ``heads``.
# Lane-packed, (B, T, H*D) as projected: each 128-lane block holds 128//D
# whole heads side by side and the per-head math masks lanes in VMEM, so no
# HBM transpose exists in either direction (the fold's ran at ~150 GB/s,
# 25-30% of a GPT-2-small step). Requires 128 % D == 0 and H % (128//D) == 0
# (head_dim 64/128). Folded, (B*H, T, D): any other shape; one head a block,
# q and k padded to whole lanes and v to its own, behind a (B,T,H,D) ->
# (B,H,T,D) transpose around the call. The grid walks (batch, head block,
# q block, kv block) in both.
#
# Inside a grid step the (block_q, block_k) tile is walked in sub-tiles
# (module docstring): block_q/block_k size the DMA, _SUBTILE the compute.

# Side of the square sub-tile the kernels class and compute by, under a head
# block of 128 lanes; 128, a lane tile, is the floor. Chosen on the v5e from
# the three kernels' device time a call at the cells' shapes, bf16 causal
# (scripts/flash_kernel_times.py; PR 26), fwd + dq + dkv in ms:
#   (8, 1024, 16, 64): whole tile 1.994, 512: 1.302, 256: 1.094, 128: 1.028
#   (4, 1024, 20, 64):                             256: 0.697, 128: 0.655
# A smaller side skips more of the triangle (3/4, 10/16, 36/64 computed)
# and pays more, smaller products; dq and dkv run at 90% MXU occupancy at
# 128 and 256 alike (LLO bundle count), so there the smaller area wins.
# At (1, 4096, 16, 64), block_q 512 (no cell), 256 is 3% ahead: 2.041, 2.114.
# Folded at (1, 4096, 32, 192) with 128-wide values, q and k in 256 lanes
# (PR 29; parent's whole (512, 1024) blocks 2.264 + 3.372 + 3.623 = 9.259):
#   every row of sub-tiles a pass:  128: 7.831, 256: 7.587, 512: 7.701
#   like rows merged (_passes):     128: 7.540, 256: 7.503
# The forward alone read 2.453 / 2.186 / 2.062: a pass of 128 rows pays the
# products' set-up and the statistics' round trip four times a block. With
# merged passes the side matters on the blocks the diagonal crosses only;
# at 256 lanes, side 256 runs as fast on 544/528 of the area, unrolls half
# the passes (the cell's warm set-up: 24.8-25.4 s against 27.0, the parent's
# 23.7-24.0: side 128 is past setup_s's bound) and measured 0.1% ahead in the
# cell: hence _subtile's rule.
_SUBTILE = 128


def _subtile(block: int, lanes: int = _LANES) -> int:
    """Sub-tile side along a block of ``block`` rows or columns, under a
    head block of ``lanes`` lanes: ``_SUBTILE`` a lane tile of the head block
    (a contraction twice as deep takes a tile twice the side) where that
    divides the block, else the whole block (a block that is smaller, or
    ragged against it, is one sub-tile)."""
    side = _SUBTILE * (lanes // _LANES)
    return side if block % side == 0 else block


# How a sub-tile lies against what its rows may see.
_SKIPPED, _MASKED, _UNMASKED = range(3)


def _tile_class(r0, rows, c0, cols, diag, edge, window=None):
    """Class of the score sub-tile of ``rows`` x ``cols`` at (r0, c0). Row r
    sees the columns below lim(r): r + diag + 1 under causality (``diag``
    is the first row's distance below the first column's diagonal), else
    ``edge``, the number of real columns (None: all); with a ``window``
    (causal) it sees the last ``window`` of them only, from lim(r) - window
    on. A sub-tile no row sees any of is skipped; one every row sees all of
    needs no mask."""
    if diag is not None:
        lo, hi = r0 + diag + 1, r0 + rows + diag
    elif edge is not None:
        lo = hi = edge
    else:
        return _UNMASKED
    if c0 >= hi:
        return _SKIPPED
    if window is not None and diag is not None:
        if c0 + cols <= lo - window:  # left of the first row's window
            return _SKIPPED
        if c0 < hi - window:  # the last row's window starts inside it
            return _MASKED
    return _UNMASKED if c0 + cols <= lo else _MASKED


def _segments(classes, size):
    """[(start, stop, masked)] over a row of sub-tile classes: neighbours of
    one class are merged, so that each run is one matrix product."""
    segs = []
    for i, c in enumerate(classes):
        if c == _SKIPPED:
            continue
        masked = c == _MASKED
        if segs and segs[-1][1] == i * size and segs[-1][2] == masked:
            segs[-1] = (segs[-1][0], (i + 1) * size, masked)
        else:
            segs.append((i * size, (i + 1) * size, masked))
    return segs


# Scores one pass of a kernel may hold, the heads alive together counted: a
# (1024, 1024) block of one head, the largest the default blocks make. Twice
# that (two 64-wide heads) does not fit the v5e's 16 MB of scoped VMEM (v5e
# compile, PR 29: dkv at (8, 1024, 16, 64), not causal).
_PASS_SCORES = 1024 * 1024


def _passes(blocks, subs, heads, diag, edge, kv_major=False, window=None):
    """[(start, stop, segs)] down a grid block of ``blocks`` = (block_q,
    block_k) in sub-tiles of ``subs`` = (sub_q, sub_k): for each row of
    sub-tiles its ``_segments`` along the columns (``kv_major``: for each
    column, along the rows), with neighbouring rows whose segments are alike
    merged into one pass while ``_PASS_SCORES`` holds it with ``heads`` heads
    a block: a grid block the diagonal does not touch is one product as tall
    as it is wide. ``segs`` is empty where nothing is to compute: above the
    diagonal, in the padding, or left of the ``window``."""
    (block, lim), (sub, sub_lim) = (
        (blocks[::-1], subs[::-1]) if kv_major else (blocks, subs))
    most = _PASS_SCORES // (lim * min(heads, 2))  # alive: _head_groups
    passes = []
    for i in range(0, block, sub):
        segs = _segments([
            _tile_class(*((j, subs[0], i) if kv_major else (i, subs[0], j)),
                        subs[1], diag, edge, window)
            for j in range(0, lim, sub_lim)], sub_lim)
        if passes and passes[-1][1:] == (i, segs) and (
                i + sub - passes[-1][0] <= most):
            passes[-1] = (passes[-1][0], i + sub, segs)
        else:
            passes.append((i, i + sub, segs))
    return passes


@functools.lru_cache(maxsize=64)
def subtile_counts(t: int, block_q: int, block_k: int, causal: bool,
                   lanes: int = _LANES, window: Optional[int] = None):
    """(square, computed, masked): the sub-tiles of the padded T x T score
    square for these blocks and a head block of ``lanes`` lanes (q's and
    k's), those the kernels compute (the rest lie
    wholly above the diagonal, wholly left of the ``window``, or wholly in
    the padding when not causal) and, of the computed, those that build a
    mask (the diagonal or the window's edge crosses them, or the padding
    edge when not causal). Static for a shape."""
    sub_q, sub_k = _subtile(block_q, lanes), _subtile(block_k, lanes)
    t_pad = _round_up(t, max(block_q, block_k))
    diag, edge = (0, None) if causal else (None, t)
    classes = [
        _tile_class(r0, sub_q, c0, sub_k, diag, edge, window)
        for r0 in range(0, t_pad, sub_q) for c0 in range(0, t_pad, sub_k)
    ]
    return (len(classes), len(classes) - classes.count(_SKIPPED),
            classes.count(_MASKED))


class _Static:
    """``maximum`` / ``minimum`` of Python ints, for ``_band`` at trace time
    (``jnp``'s would stage an operation inside a jitted function)."""
    maximum, minimum = staticmethod(max), staticmethod(min)


def _band(i, dkv, block_q, block_k, nq, nk, window, lib=jnp):
    """(first, last) block along the inner grid axis that the outer block
    ``i`` of a windowed call sees any of: for q block ``i`` (the forward's
    and dq's grids) the kv blocks from its first row's window start to its
    last row's diagonal; for kv block ``i`` (``dkv``) the q blocks from its
    first column's diagonal to the last row whose window reaches its last
    column. ``i`` may be a grid index (``lib`` = jnp, in an index map or a
    kernel) or a Python int (``lib`` = ``_Static``)."""
    if dkv:
        return i * block_k // block_q, lib.minimum(
            (i * block_k + block_k + window - 2) // block_q, nq - 1)
    return (lib.maximum(i * block_q - (window - 1), 0) // block_k,
            lib.minimum((i * block_q + block_q - 1) // block_k, nk - 1))


def _band_steps(dkv, block_q, block_k, nq, nk, window):
    """Steps of a windowed call's inner grid axis: the most blocks any outer
    block's band holds (at T = 8192, window 512 and (512, 1024) blocks 2 of
    the 8 kv blocks, and 3 of the 16 q blocks under dk/dv)."""
    return max(hi - lo + 1 for lo, hi in (
        _band(i, dkv, block_q, block_k, nq, nk, window, _Static)
        for i in range(nk if dkv else nq)))


def _band_block(i, j, dkv, **band):
    """(block, whether it lies in the band) of step ``j`` of a windowed
    call's inner grid axis under outer block ``i``. Past the band's end the
    block is the band's last (an index map that does not move copies
    nothing) and the kernels run no walk."""
    lo, hi = _band(i, dkv, **band)
    return jnp.minimum(lo + j, hi), lo + j <= hi


def _inner_step(i, j, dkv, block_q, block_k, nq, nk, window):
    """(steps, block, in_band) of a kernel's step ``j`` along its grid's
    inner axis under outer block ``i``: with no window every block of the
    axis, step ``j`` on block ``j`` (``in_band`` None); with one, the band's
    (``_band_steps``, ``_band_block``)."""
    if window is None:
        return (nq if dkv else nk), j, None
    band = dict(block_q=block_q, block_k=block_k, nq=nq, nk=nk, window=window)
    return (_band_steps(dkv, **band), *_band_block(i, j, dkv, **band))


def kv_block_fetches(b: int, head_blocks: int, share: int, nq: int, nk: int,
                     band=None):
    """(fetches, a_head): the K block fetches (V's are as many) of one
    forward call over the grid ``_specs`` lays out for ``b`` rows,
    ``head_blocks`` query head blocks, ``share`` of them walked on one fetch
    (``_heads_a_fetch``; 1 with one K/V head a query head), and nq x nk grid
    blocks, that is the grid steps at which the K/V index map's value is
    not the step's before, and what one fetch a query head block would
    make: the same number where ``share`` is 1. The pipeline copies no
    block whose index did not change, so with the sharing heads the
    innermost axis a block is fetched once for all of them. A selection's
    block is fetched as often, but for a single kv block (nk 1), which
    never moves while the selection's moves with the q block, nq times as
    often. ``band`` ((block_q, block_k, window) of a windowed call): the
    grid's kv axis holds a q block's band only (``_band_steps``), and a kv
    block two q blocks' bands share stays put between them. Static for a
    shape."""
    runs = nq * nk if nk > 1 else 1  # one kv block: it never moves
    if band is not None:
        block_q, block_k, window = band
        steps = _band_steps(False, block_q, block_k, nq, nk, window)
        seen = [min(lo + j, hi) for lo, hi in (
            _band(qi, False, block_q, block_k, nq, nk, window, _Static)
            for qi in range(nq)) for j in range(steps)]
        runs = 1 + sum(a != c for a, c in zip(seen, seen[1:]))
    return b * (head_blocks // share) * runs, b * head_blocks * runs


_VMEM = 16 << 20  # what a kernel may hold of VMEM: the v5e's scoped limit


def _heads_a_fetch(group, block_q, block_k, itemsize):
    """How many of the ``group`` query heads of a K/V head (128 wide, one a
    head block) the forward and dq walk on one fetch of its K and V blocks
    and the selection's: the most, of ``group``'s divisors, that ``_VMEM``
    has room for. The q-side blocks hold the lanes of all the sharing
    heads, double-buffered (q and o, or q, dO and dq), beside a float32
    scratch slab a head (m, l and acc, or dq's accumulator) and their rows
    of the statistics, so what a step holds grows with the heads times
    block_q; beside them stand K, V and the selection, double-buffered, and
    a pass's scores and probabilities. One head a fetch is the layout of a
    call with no shared head, and holds what that holds. dk/dv's blocks are
    one head's under any sharing. (Held against what the v5e's compiler
    asks for: PERF.md section 6 and CHANGES.md, PR 37.)"""
    per_row = _LANES * max(4 * itemsize + 12, 6 * itemsize + 4) + 128
    others = (4 * block_k * _LANES * itemsize + 2 * block_q * block_k
              + (4 + itemsize) * min(block_q * block_k, _PASS_SCORES))
    return next(s for s in range(group, 0, -1) if group % s == 0 and (
        s == 1 or s * block_q * per_row + others <= _VMEM))


def _block_views(nq, nk, block_q, block_k, t_actual, causal, window=None):
    """The distinct ways a grid block (qi, ki) lies against the diagonal
    (causal) or the padding edge (not causal), static for a shape:
    [((diag, edge), in_view)] with ``_tile_class``'s ``diag`` / ``edge`` in
    the block's own coordinates and ``in_view(qi, ki)`` true in exactly
    those blocks. Blocks nothing is seen of appear in no view. A block
    wholly below the diagonal, or wholly real, is (None, None). With a
    ``window`` a block wholly left of it appears in no view either, one the
    window's edge crosses is a view by its distance from the diagonal as
    one the diagonal crosses is, and (None, None) is a block wholly inside
    the band."""
    views = {}
    for qi in range(nq):
        for ki in range(nk):
            if causal and window is not None:
                d = qi * block_q - ki * block_k
                if d <= -block_q or d >= block_k - 1 + window:
                    continue
                if block_k - 1 <= d <= window - block_q:
                    views[None, None] = lambda qi, ki: jnp.logical_and(
                        qi * block_q - ki * block_k >= block_k - 1,
                        qi * block_q - ki * block_k <= window - block_q)
                else:
                    views[d, None] = functools.partial(
                        lambda d, qi, ki:
                        qi * block_q - ki * block_k == d, d)
            elif causal:
                d = qi * block_q - ki * block_k
                if d <= -block_q:
                    continue
                if d >= block_k - 1:
                    views[None, None] = (
                        lambda qi, ki:
                        qi * block_q - ki * block_k >= block_k - 1)
                else:
                    views[d, None] = functools.partial(
                        lambda d, qi, ki:
                        qi * block_q - ki * block_k == d, d)
            else:
                e = min(t_actual - ki * block_k, block_k)
                if e <= 0:
                    continue
                if e == block_k:
                    views[None, None] = (
                        lambda qi, ki: (ki + 1) * block_k <= t_actual)
                else:
                    views[None, e] = (
                        lambda qi, ki: ki == t_actual // block_k)
    return list(views.items())


def _scale_folds(scale: float) -> bool:
    """True when multiplying by ``scale`` is exact in every float dtype (a
    power of two: 1/sqrt(64)), so it can be applied once to a (rows, hd)
    operand or result instead of to every score."""
    return math.frexp(scale)[0] == 0.5


def _head_lanes(ref, heads):
    """Each head's lane span in a block of ``heads`` heads side by side."""
    hd = ref.shape[-1] // heads
    return [slice(hx * hd, (hx + 1) * hd) for hx in range(heads)]


def _head_groups(lanes):
    """Heads whose score tiles are alive together: two (every head of a
    block there is), so that narrower heads would not multiply the VMEM the
    walk needs."""
    return [lanes[i:i + 2] for i in range(0, len(lanes), 2)]


def _own_lanes(x, sl):
    """``x`` (rows, lanes) with the lanes of every head but ``sl`` zeroed: a
    contraction over all the lanes is then that head's alone, at the MXU
    cost of the 64-wide one and without the lane slice's rotate to lane 0.
    A head that has the block to itself is ``x``."""
    if sl.stop - sl.start == x.shape[-1]:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    own = jnp.logical_and(lane >= sl.start, lane < sl.stop)
    return jnp.where(own, x, jnp.zeros_like(x))


def _by_head(lanes, per_head):
    """One (rows, lanes) array that holds, in each head's lane span, that
    head's entry of ``per_head`` ((rows, lanes) or (rows, 1) each). A product
    with a whole lane block on its right is right in the lanes of the head
    whose probabilities were on its left, and only there."""
    width = lanes[-1].stop
    out = per_head[0]
    for sl, x in zip(lanes[1:], per_head[1:]):
        shape = (x.shape[0], width)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        out = jnp.where(lane >= sl.start, jnp.broadcast_to(x, shape),
                        jnp.broadcast_to(out, shape))
    return jnp.broadcast_to(out, (out.shape[0], width))


def _stat_columns(stat, lanes):
    """A row statistic as the q-major kernels use it, (rows, lanes) with each
    head's value across its lane span, from how it is stored, lane-major
    (heads, rows): one XLU transpose, no HBM traffic."""
    rows = stat.shape[-1]
    return jnp.concatenate([
        jnp.broadcast_to(stat[hx:hx + 1, :], (sl.stop - sl.start, rows))
        for hx, sl in enumerate(lanes)], axis=0).T


def _nt(a, b):
    """a @ b.T on the MXU, f32 out."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a.astype(b.dtype), b, preferred_element_type=jnp.float32)


def _p_ds_lse(a, b, c, d, lse, delta, valid, scale):
    """The backward kernels' segment math: p = exp(a b^T - lse) from
    the one saved row statistic, ds = p * (c d^T - delta). Row-major (dq):
    a, b, c, d = q, k, dO, v and lse, delta are columns; column-major (dkv):
    k, q, v, dO and rows. ``valid`` is None on a segment that needs no
    mask; ``scale`` is None when it is already folded into q."""
    s = _nt(a, b)
    if scale is not None:
        s = s * scale
    if valid is not None:
        s = jnp.where(valid, s, _NEG)
    p = jnp.exp(s - lse)
    ds = p * (_nt(c, d) - delta)
    if scale is not None:
        ds = ds * scale
    return p, ds


def _walk_blocks(kernel_walk, one_block, views, qi, ki, live=None):
    """Run ``kernel_walk(diag, edge)`` for the view this grid block lies in:
    statically in a grid of one block, else under one ``pl.when`` a view.
    ``live`` (a selection was handed in): the block holds a selected pair;
    one that holds none runs no walk, as one above the diagonal runs none."""
    if one_block:
        return kernel_walk(*views[0][0])
    for view, in_view in views:
        here = in_view(qi, ki)
        if live is not None:
            here = jnp.logical_and(here, live)
        pl.when(here)(functools.partial(kernel_walk, *view))


def _seg_valid(sel, row_at, col_at, span, segs, t_actual, causal,
               kv_major=False, window=None):
    """One mask (or None: every pair counts) for each segment of a pass.
    ``span`` is the pass's own (start, stop) inside the grid block, whose
    row r and column c are ``row_at(r)`` and ``col_at(c)`` of the sequence:
    rows, with ``segs`` runs of columns, or, ``kv_major``, columns with runs
    of rows and masks transposed. A segment the diagonal, the ``window``'s
    edge or the padding edge crosses builds ``_valid_mask``; with a selection (``sel``: its flags and
    its block, laid out as the masks are) each mask is and-ed with the pairs
    selected, and a segment below the diagonal is masked by them alone."""
    out = []
    for s0, s1, masked in segs:
        (r0, r1), (c0, c1) = ((s0, s1), span) if kv_major else (
            span, (s0, s1))
        ok = _valid_mask(row_at(r0), col_at(c0), r1 - r0, c1 - c0,
                         t_actual, causal, kv_major, window
                         ) if masked else None
        if sel is not None:
            block = (sel[1][0, c0:c1, r0:r1] if kv_major
                     else sel[1][0, r0:r1, c0:c1])
            picked = block.astype(jnp.int32) != 0
            ok = picked if ok is None else jnp.logical_and(ok, picked)
        out.append(ok)
    return out


def _live(sel, nq, nk, qi, ki, in_band=None):
    """Whether grid block (qi, ki) of this batch row holds a selected pair
    (the selection's flags, in scalar memory); with no selection
    ``in_band``: a windowed call's word on the step (``_inner_step``), None
    where every block runs its walk."""
    if sel is None:
        return in_band
    return sel[0][(pl.program_id(0) * nq + qi) * nk + ki] != 0


def _head_of_group(group, lanes=(), stats=(), scratch=()):
    """The refs of a forward or dq grid step as one query head's, the body's
    own terms. Where ``group`` > 1 query heads walk on one fetch
    (``_heads_a_fetch``) the grid's last axis is theirs, over the K/V
    head's blocks (and the selection's) that the step before left in VMEM:
    the ``lanes`` refs (q, o, dO, dq) hold all their lanes a q block and
    the ``stats`` refs their rows of the statistics, fetched and written
    back once for all, and the ``scratch`` refs carry a slab a head. Each
    comes back as the view of this step's head; with one head a fetch, as
    it stands."""
    if group == 1:
        return (*lanes, *stats, *scratch)
    hd = pl.program_id(4)

    def own(ref):
        w = ref.shape[-1] // group
        return ref.at[:, :, pl.ds(pl.multiple_of(hd * w, _LANES), w)]
    return (*(own(r) for r in lanes), *(r.at[:, pl.ds(hd, 1)] for r in stats),
            *(r.at[hd] for r in scratch))


def _selecting(kernel, n_in):
    """``kernel`` as a selection-taking ``pallas_call`` hands over its refs:
    the flags first (scalar prefetch), the selection's block after the
    ``n_in`` inputs the kernel has anyway."""
    def body(flags_ref, *refs, **kw):
        return kernel(*refs[:n_in], *refs[n_in + 1:],
                      sel=(flags_ref, refs[n_in]), **kw)
    return body


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_out_ref, m_ref, l_ref,
                acc_ref, *, scale, heads, block_q, block_k, t_actual,
                causal, nq, nk, group=1, sel=None, window=None):
    """One (b, hblk, qi, ki) grid step on (1, block, lanes) tiles of
    ``heads`` heads side by side: q and k as wide as each other, v, and with
    it the output and the statistics, as wide as itself. Each q sub-tile
    takes the kv columns its rows see in at most two matrix products a
    head, an unmasked run and a masked one, under one row maximum. Scratch
    carries m, l and acc, each head's replicated across its span of v's
    lanes, between the sequential ki steps; a grid of one block needs
    neither scratch nor ``pl.when``. Where ``group`` query heads walk on one
    fetch of their K/V head's blocks the grid has them as its last axis and
    the step is one head's (``_head_of_group``): k, v and the selection stay
    put for their steps. With a ``window`` the kv axis holds a q block's
    band only (``_band_steps``) and step kj is on kv block ``_band_block``."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    steps, ki, in_band = _inner_step(qi, kj, False, block_q, block_k, nq, nk,
                                     window)
    q_ref, o_ref, lse_out_ref, m_ref, l_ref, acc_ref = _head_of_group(
        group, (q_ref, o_ref), (lse_out_ref,), (m_ref, l_ref, acc_ref))
    subs = [_subtile(x, q_ref.shape[-1]) for x in (block_q, block_k)]
    v_lanes = _head_lanes(v_ref, heads)
    lanes = list(zip(_head_lanes(q_ref, heads), v_lanes))
    fold = _scale_folds(scale)
    one_block = nq == 1 and nk == 1 and group == 1 and sel is None

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse = (m + jnp.log(l)).T  # (lanes, rows): a head's value, lane-major
        for hx, sl in enumerate(v_lanes):
            lse_out_ref[0, 0, hx:hx + 1, rows] = lse[sl.start:sl.start + 1]

    def walk(diag, edge):
        for r0, r1, segs in reversed(_passes(
                (block_q, block_k), subs, heads, diag, edge, window=window)):
            if not segs:
                continue
            rows = slice(r0, r1)
            q = q_ref[0, rows, :]
            if fold:
                q = q * scale
            ks = [k_ref[0, c0:c1, :] for c0, c1, _ in segs]
            vs = [v_ref[0, c0:c1, :] for c0, c1, _ in segs]
            valid = _seg_valid(sel, lambda r: qi * block_q + r,
                               lambda c: ki * block_k + c, (r0, r1), segs,
                               t_actual, causal, window=window)
            # Both heads' scores, then both softmaxes, then both P V: the
            # order the bundle scheduler packs best.
            done = []
            for group in _head_groups(lanes):
                scores = []
                for slq, _ in group:
                    qh = _own_lanes(q, slq)
                    ss = [_nt(qh, k) for k in ks]  # (sub_q, c1 - c0) f32
                    if not fold:
                        ss = [s * scale for s in ss]
                    scores.append([
                        s if ok is None else jnp.where(ok, s, _NEG)
                        for s, ok in zip(ss, valid)])
                stats = []
                for (_, slv), ss in zip(group, scores):
                    m = functools.reduce(jnp.maximum, [
                        jnp.max(s, axis=-1, keepdims=True) for s in ss])
                    if not one_block:
                        m = jnp.maximum(
                            m_ref[rows, slv.start:slv.start + 1], m)
                    ps = [jnp.exp(s - m) for s in ss]
                    l = sum(jnp.sum(p, axis=-1, keepdims=True) for p in ps)
                    stats.append((m, l, ps))
                done += [(m, l, sum(_dot(p, v) for p, v in zip(ps, vs)))
                         for m, l, ps in stats]
            m, l, acc = (_by_head(v_lanes, x) for x in zip(*done))
            if one_block:
                finish(rows, m, l, acc)
                continue
            alpha = jnp.exp(m_ref[rows, :] - m)
            m_ref[rows, :] = m
            l_ref[rows, :] = l + alpha * l_ref[rows, :]
            acc_ref[rows, :] = acc + alpha * acc_ref[rows, :]

    if not one_block:
        @pl.when(kj == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    _walk_blocks(
        walk, one_block,
        _block_views(nq, nk, block_q, block_k, t_actual, causal, window),
        qi, ki, _live(sel, nq, nk, qi, ki, in_band))

    if not one_block:
        @pl.when(kj == steps - 1)
        def _finish():
            finish(slice(None), m_ref[...], l_ref[...], acc_ref[...])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_ref, *, scale, heads, block_q, block_k, t_actual, causal,
               nq, nk, group=1, sel=None, window=None):
    """dq for one (b, hblk, qi, ki) grid step, walked like the forward (and
    over its grid, the ``group`` heads of one fetch innermost, a ``window``'s
    band alone on the kv axis): per q sub-tile, dq += ds K over the kv
    columns its rows see."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    steps, ki, in_band = _inner_step(qi, kj, False, block_q, block_k, nq, nk,
                                     window)
    q_ref, do_ref, dq_ref, lse_ref, dl_ref, acc_ref = _head_of_group(
        group, (q_ref, do_ref, dq_ref), (lse_ref, dl_ref), (acc_ref,))
    subs = [_subtile(x, q_ref.shape[-1]) for x in (block_q, block_k)]
    q_lanes, v_lanes = _head_lanes(q_ref, heads), _head_lanes(v_ref, heads)
    lanes = list(zip(q_lanes, v_lanes))
    fold = _scale_folds(scale)
    one_block = nq == 1 and nk == 1 and group == 1 and sel is None

    def finish(rows, acc):
        if fold:
            acc = acc * scale
        dq_ref[0, rows, :] = acc.astype(dq_ref.dtype)

    def walk(diag, edge):
        lses = _stat_columns(lse_ref[0, 0], v_lanes)
        deltas = _stat_columns(dl_ref[0, 0], v_lanes)
        for r0, r1, segs in _passes(
                (block_q, block_k), subs, heads, diag, edge, window=window):
            if not segs:
                continue
            rows = slice(r0, r1)
            q = q_ref[0, rows, :]
            if fold:
                q = q * scale
            do = do_ref[0, rows, :]
            ks = [k_ref[0, c0:c1, :] for c0, c1, _ in segs]
            vs = [v_ref[0, c0:c1, :] for c0, c1, _ in segs]
            valid = _seg_valid(sel, lambda r: qi * block_q + r,
                               lambda c: ki * block_k + c, (r0, r1), segs,
                               t_actual, causal, window=window)
            accs = []
            for group in _head_groups(lanes):
                dss = []
                for slq, slv in group:
                    qh, doh = _own_lanes(q, slq), _own_lanes(do, slv)
                    lse = lses[rows, slv.start:slv.start + 1]
                    delta = deltas[rows, slv.start:slv.start + 1]
                    dss.append([
                        _p_ds_lse(qh, k, doh, v, lse, delta, ok,
                                  None if fold else scale)[1]
                        for k, v, ok in zip(ks, vs, valid)])
                accs += [sum(_dot(ds, k) for ds, k in zip(ds_h, ks))
                         for ds_h in dss]
            acc = _by_head(q_lanes, accs)
            if one_block:
                finish(rows, acc)
            else:
                acc_ref[rows, :] += acc

    if not one_block:
        @pl.when(kj == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

    _walk_blocks(
        walk, one_block,
        _block_views(nq, nk, block_q, block_k, t_actual, causal, window),
        qi, ki, _live(sel, nq, nk, qi, ki, in_band))

    if not one_block:
        @pl.when(kj == steps - 1)
        def _finish():
            finish(slice(None), acc_ref[...])


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, acc_dk, acc_dv, *, scale, heads, block_q, block_k,
                t_actual, causal, nq, nk, group=1, sel=None, window=None):
    """dk/dv for one (b, hblk, ki, qi) grid step, the transpose of the dq
    walk: per kv sub-tile, dv += p^T dO and dk += ds^T q over the q rows
    that see its columns. The score tile is computed column-major (k q^T),
    so that p^T and ds^T are the products' left operands as they stand;
    the row statistics come lane-major, (heads, rows). Where ``group`` query
    heads share a K/V head, the head block is the K/V head's and the last
    grid axis walks, q block by q block, each of its query heads in turn
    (``nq * group`` steps, the head minor: the selection's block stays put
    for a group's steps), all summed into the one dk and dv. With a
    ``window`` the last axis holds the q blocks of a kv block's band only."""
    ki = pl.program_id(2)
    step = qi = pl.program_id(3)
    if group > 1:
        qi = step // group
    steps, qi, in_band = _inner_step(ki, qi, True, block_q, block_k, nq, nk,
                                     window)
    last = group * steps - 1
    subs = [_subtile(x, q_ref.shape[-1]) for x in (block_q, block_k)]
    q_lanes, v_lanes = _head_lanes(q_ref, heads), _head_lanes(v_ref, heads)
    lanes = list(enumerate(zip(q_lanes, v_lanes)))
    fold = _scale_folds(scale)
    one_block = nq == 1 and nk == 1 and group == 1 and sel is None

    def walk(diag, edge):
        for c0, c1, segs in _passes(
                (block_q, block_k), subs, heads, diag, edge, kv_major=True,
                window=window):
            cols = slice(c0, c1)
            if not segs:
                if one_block:  # padding columns: nothing sees them
                    dk_ref[0, cols, :] = jnp.zeros_like(dk_ref[0, cols, :])
                    dv_ref[0, cols, :] = jnp.zeros_like(dv_ref[0, cols, :])
                continue
            k = k_ref[0, cols, :]
            v = v_ref[0, cols, :]
            qs = [q_ref[0, r0:r1, :] for r0, r1, _ in segs]
            if fold:
                qs = [q * scale for q in qs]
            dos = [do_ref[0, r0:r1, :] for r0, r1, _ in segs]
            valid = _seg_valid(sel, lambda r: qi * block_q + r,
                               lambda c: ki * block_k + c, (c0, c1), segs,
                               t_actual, causal, kv_major=True,
                               window=window)
            dvs, dks = [], []
            for group in _head_groups(lanes):
                p_ds = []
                for hx, (slq, slv) in group:
                    kh, vh = _own_lanes(k, slq), _own_lanes(v, slv)
                    p_ds.append([
                        _p_ds_lse(
                            kh, q, vh, do, lse_ref[0, 0, hx:hx + 1, r0:r1],
                            dl_ref[0, 0, hx:hx + 1, r0:r1], ok,
                            None if fold else scale)
                        for (r0, r1, _), q, do, ok
                        in zip(segs, qs, dos, valid)])
                dvs += [sum(_dot(p, do) for (p, _), do in zip(h, dos))
                        for h in p_ds]
                dks += [sum(_dot(ds, q) for (_, ds), q in zip(h, qs))
                        for h in p_ds]
            dv, dk = _by_head(v_lanes, dvs), _by_head(q_lanes, dks)
            if one_block:
                dk_ref[0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, cols, :] = dv.astype(dv_ref.dtype)
            else:
                acc_dk[cols, :] += dk
                acc_dv[cols, :] += dv

    if not one_block:
        @pl.when(step == 0)
        def _init():
            acc_dk[...] = jnp.zeros_like(acc_dk)
            acc_dv[...] = jnp.zeros_like(acc_dv)

    _walk_blocks(
        walk, one_block,
        _block_views(nq, nk, block_q, block_k, t_actual, causal, window),
        qi, ki, _live(sel, nq, nk, qi, ki, in_band))

    if not one_block:
        @pl.when(step == last)
        def _finish():
            dk_ref[0] = acc_dk[...].astype(dk_ref.dtype)
            dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


def _specs(q, v, heads, hpb, causal, block_q, block_k, group=1, window=None):
    """What the three pallas_calls share, for q (b, T, heads * D) and v
    (b, T, heads // group * Dv) with ``hpb`` heads a block: (t_pad, nh, w,
    wv), the kernels' static arguments, and ``specs``: the grid and the
    block specs, by which of the grid's axes 2 and 3 walks the q blocks. A
    head block is ``hpb`` heads padded to whole lanes, w for q and k, wv for
    v; a row statistic is (b, nh, hpb, t_pad), lane-major. The grid is (b,
    head block, i, j). ``group`` query heads share a K/V head (one head a
    block), and ``share`` of them (``_heads_a_fetch``: all, where VMEM has
    the room) fetch its K and V blocks, and the selection's, once for all:
    the forward's and dq's grid is then (b, query heads by ``share``, q
    block, kv block, head of the ``share``), the q-side blocks all the
    sharing heads' (``_head_of_group``); with no room for two, query head
    block h reads K/V block h // group on the plain grid. dk/dv's grid,
    ``specs(3, dkv=True)``, is (b, K/V head, kv block, q block x head of
    the group). The last spec is the selection's block, (block_q, block_k),
    or transposed for dk/dv. With a ``window`` grid axis 3 is as long as the
    longest band (``_band_steps``) and its step j is on the j-th block of
    the band of axis 2's block (``_band_block``): blocks left of the band
    are neither stepped over nor fetched."""
    t = q.shape[1]
    if max(block_q, block_k) % min(block_q, block_k):
        raise ValueError(
            f"block_q={block_q} and block_k={block_k} must divide each "
            "other, or trailing rows would fall outside the grid")
    t_pad = _round_up(t, max(block_q, block_k))
    w = _lane_pad(q.shape[-1] // heads * hpb)
    wv = _lane_pad(v.shape[-1] // (heads // group) * hpb)
    b, nh = q.shape[0], heads // hpb
    nq, nk = t_pad // block_q, t_pad // block_k
    if not _interpret() and nq > 1 and block_q % _LANES:
        raise ValueError(
            f"block_q={block_q}: the kernels keep their row statistics "
            "lane-major, so a q block that is not the whole sequence has "
            f"to be a multiple of {_LANES} rows")
    share = _heads_a_fetch(group, block_q, block_k, q.dtype.itemsize)

    def specs(q_axis, dkv=False):
        kv_axis = 5 - q_axis  # grid axes 2 and 3
        q_heads = 1  # heads a q-side block holds
        outer, inner = (nk, nq) if dkv else (nq, nk)
        on = lambda g, j: j  # the block step j of grid axis 3 is on
        if window is not None:
            band = dict(block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                        window=window)
            inner = _band_steps(dkv, **band)
            on = lambda g, j: _band_block(g[2], j, dkv, **band)[0]
        if group == 1 or (share == 1 and not dkv):
            grid = (b, nh, outer, inner)
            at = lambda axis: lambda *g: (
                g[0], g[2] if axis == 2 else on(g, g[3]), g[1])
            q_at, kv_at = at(q_axis), at(kv_axis)
            if group > 1:
                kv_at = lambda *g: (g[0], on(g, g[3]), g[1] // group)
            stat_at = lambda *g: (g[0], g[1], 0, at(q_axis)(*g)[1])
            sel_at = lambda *g: (g[0], g[q_axis], g[kv_axis])
        elif dkv:
            grid = (b, nh // group, outer, inner * group)
            q_at = lambda *g: (g[0], on(g, g[3] // group),
                               g[1] * group + g[3] % group)
            kv_at = lambda *g: (g[0], g[2], g[1])
            stat_at = lambda *g: (g[0], g[1] * group + g[3] % group, 0,
                                  on(g, g[3] // group))
            sel_at = lambda *g: (g[0], g[3] // group, g[2])
        else:
            grid = (b, nh // share, outer, inner, share)
            q_heads = share
            q_at = lambda *g: (g[0], g[2], g[1])
            kv_at = lambda *g: (g[0], on(g, g[3]), g[1])
            if share < group:
                kv_at = lambda *g: (g[0], on(g, g[3]),
                                    g[1] // (group // share))
            stat_at = lambda *g: (g[0], g[1], 0, g[2])
            sel_at = lambda *g: (g[0], g[2], g[3])
        if dkv:  # the selection transposed, as dk/dv's masks are
            sel_spec = pl.BlockSpec(
                (1, block_k, block_q),
                lambda *g: (lambda b, i, j: (b, j, i))(*sel_at(*g)))
        else:
            sel_spec = pl.BlockSpec((1, block_q, block_k), sel_at)
        return grid, (
            *(pl.BlockSpec((1, block_q, q_heads * x), q_at)
              for x in (w, wv)),
            *(pl.BlockSpec((1, block_k, x), kv_at) for x in (w, wv)),
            pl.BlockSpec((1, q_heads, hpb, block_q), stat_at), sel_spec)

    kernel_args = dict(
        # A Python float: a NumPy scalar is no weak type, and q * scale
        # would promote the MXU's bf16 operand to f32.
        scale=1.0 / math.sqrt(q.shape[-1] // heads), heads=hpb,
        block_q=block_q, block_k=block_k, t_actual=t, causal=causal,
        nq=nq, nk=nk, group=share)  # dk/dv's ``group`` is the group itself
    if window is not None:
        kernel_args["window"] = window
    return t_pad, nh, w, wv, kernel_args, specs


def _call(kernel, n_in, grid, in_specs, out_specs, out_shape, scratch,
          name, sel_spec, selection):
    """The ``pallas_call`` of one of the three kernels: as it always was
    with no selection; with one (``selection``: its flags and its padded
    pairs), the flags are prefetched into scalar memory and the pairs'
    block joins the inputs, under the kernel's name plus ``_sel``."""
    if selection is None:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch, name=name,
            interpret=_interpret())
    call = pl.pallas_call(
        _selecting(kernel, n_in),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[*in_specs, sel_spec], out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape, name=name + "_sel", interpret=_interpret())
    return lambda *operands: call(selection[0], *operands, selection[1])


def _pad_selection(selection, t_pad):
    """(flags, pairs padded to the grid's square) of a selection's (flags,
    pairs (b, T, T)); None stays None."""
    if selection is None:
        return None
    flags, pairs = selection
    extra = t_pad - pairs.shape[1]
    return flags, jnp.pad(pairs, ((0, 0), (0, extra), (0, extra)))


def _fwd_pallas(q, k, v, selection=None, *, heads, hpb, suffix, causal,
                block_q, block_k, group=1, window=None):
    """q: (b, T, heads * D); k: (b, T, heads // group * D); v: (b, T, heads
    // group * Dv), Dv its own width (latent attention: 192-wide scores,
    128-wide values); lane-packed (heads = H, hpb = 128 // D) or folded (b =
    B*H, heads = hpb = 1). ``selection``: (flags (b * nq * nk,) int32, pairs
    (b, T, T) int8), see ``flash_attention``. Returns (out, lse) with out as
    wide as the query heads' values and the one row statistic the backward
    needs, lse = m + log l, as (b, heads // hpb, hpb, t_pad): 4 bytes a row
    and head."""
    b, t, _ = q.shape
    t_pad, nh, w, wv, kernel_args, specs = _specs(
        q, v, heads, hpb, causal, block_q, block_k, group, window)
    grid, (q_spec, o_spec, k_spec, v_spec, stat, sel_spec) = specs(q_axis=2)
    # m, l, acc between the sequential ki steps, of each head of a group.
    carried = pltpu.VMEM((*grid[4:], block_q, wv), jnp.float32)
    out, lse = _call(
        functools.partial(_fwd_kernel, **kernel_args), 3,
        grid, [q_spec, k_spec, v_spec], [o_spec, stat],
        [
            jax.ShapeDtypeStruct((b, t_pad, nh * wv), q.dtype),
            jax.ShapeDtypeStruct((b, nh, hpb, t_pad), jnp.float32),
        ],
        [carried, carried, carried],
        "dtpu_flash_fwd" + suffix, sel_spec,
        _pad_selection(selection, t_pad),
    )(_pad(q, t_pad, nh * w), _pad(k, t_pad, nh // group * w),
      _pad(v, t_pad, nh // group * wv))
    return out[:, :t, :v.shape[-1] * group], lse


def _bwd_pallas(res, g, *, heads, hpb, suffix, causal, block_q, block_k,
                group=1, window=None):
    """dq, dk, dv from the saved row statistic: two kernels (dq with kv
    innermost; dk/dv with q innermost), each O(T*D) HBM traffic."""
    q, k, v, out, lse, selection = res
    b, t, _ = q.shape
    t_pad, nh, w, wv, kernel_args, specs = _specs(
        q, v, heads, hpb, causal, block_q, block_k, group, window)
    nkv = nh // group
    qp, kp = _pad(q, t_pad, nh * w), _pad(k, t_pad, nkv * w)
    vp, dop = _pad(v, t_pad, nkv * wv), _pad(g.astype(q.dtype), t_pad,
                                             nh * wv)
    selection = _pad_selection(selection, t_pad)

    # delta_i = sum_j dO_ij O_ij per row and head, laid out like lse. The
    # product stays on the (b, T, heads x D) layout the kernels read and a
    # 0/1 (heads x D, heads) matrix sums each head's lanes: XLA:TPU fuses
    # the product into the contraction's operand, reading dO and O once,
    # where a sum over a (b, T, H, D) view makes it write that view in f32
    # and relayout it, five times the bytes (scripts/flash_delta_bytes.py).
    # The product is f32 (out is promoted), exact for bf16 inputs; HIGHEST
    # has the MXU take it whole, where a lower precision rounds it to bf16.
    lanes = jnp.arange(out.shape[-1]) // (out.shape[-1] // heads)
    delta = jnp.einsum("btw,wh->bht", g.astype(jnp.float32) * out, (
        lanes[:, None] == jnp.arange(heads)).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).reshape(b, nh, hpb, t)
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, 0), (0, t_pad - t)))

    grid, (q_spec, do_spec, k_spec, v_spec, stat, sel_spec) = specs(q_axis=2)
    dq = _call(
        functools.partial(_dq_kernel, **kernel_args), 6,
        grid, [q_spec, k_spec, v_spec, do_spec, stat, stat],
        q_spec, jax.ShapeDtypeStruct((b, t_pad, nh * w), q.dtype),
        [pltpu.VMEM((*grid[4:], block_q, w), jnp.float32)],
        "dtpu_flash_dq" + suffix, sel_spec, selection,
    )(qp, kp, vp, dop, lse, delta)

    grid, (q_spec, do_spec, k_spec, v_spec, stat, sel_spec) = specs(
        q_axis=3, dkv=True)
    dk, dv = _call(
        functools.partial(_dkv_kernel, **dict(kernel_args, group=group)), 6,
        grid, [q_spec, k_spec, v_spec, do_spec, stat, stat],
        [k_spec, v_spec],
        [
            jax.ShapeDtypeStruct((b, t_pad, nkv * w), k.dtype),
            jax.ShapeDtypeStruct((b, t_pad, nkv * wv), v.dtype),
        ],
        [
            pltpu.VMEM((block_k, w), jnp.float32),
            pltpu.VMEM((block_k, wv), jnp.float32),
        ],
        "dtpu_flash_dkv" + suffix, sel_spec,
        # dk/dv's masks are transposed, and so is what they are and-ed with.
        selection and (selection[0], jnp.swapaxes(selection[1], 1, 2)),
    )(qp, kp, vp, dop, lse, delta)
    return (dq[:, :t, :q.shape[-1]], dk[:, :t, :k.shape[-1]],
            dv[:, :t, :v.shape[-1]])


@functools.lru_cache(maxsize=64)
def _flash_cached(heads, hpb, suffix, causal, block_q, block_k, group=1,
                  selecting=False, window=None):
    """custom_vjp fn over (b, T, heads * D) arrays for this static config.
    Its two halves are jitted: every layer of a model calls this one
    function at one shape, so the kernels are traced and lowered once a
    program and not once a layer (the walk is unrolled at trace time, and
    tracing is set-up time). ``selecting``: the function takes a fourth
    argument, the selection (flags, pairs), which has no gradient, and
    returns the row statistic beside the output."""
    static = dict(heads=heads, hpb=hpb, suffix=suffix, causal=causal,
                  block_q=block_q, block_k=block_k)
    if group > 1:
        static["group"] = group
    if window is not None:
        static["window"] = window

    @jax.jit
    def flash_fwd(q, k, v, selection=None):
        return _fwd_pallas(q, k, v, selection, **static)

    @jax.jit
    def flash_bwd(res, g):
        return _bwd_pallas(res, g, **static)

    if selecting:
        @jax.custom_vjp
        def flash(q, k, v, selection):
            return flash_fwd(q, k, v, selection)

        def fwd(q, k, v, selection):
            out, lse = flash_fwd(q, k, v, selection)
            return (out, lse), (q, k, v, out, lse, selection)

        # lse goes out as a constant (flash_attention stops its gradient).
        flash.defvjp(fwd, lambda res, g: (*flash_bwd(res, g[0]), None))
        return flash

    @jax.custom_vjp
    def flash(q, k, v):
        return flash_fwd(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = flash_fwd(q, k, v)
        return out, (q, k, v, out, lse, None)

    flash.defvjp(fwd, flash_bwd)
    return flash


# -------------------------------------------------------------------- public
def dense_attention(q, k, v, causal: bool, selection=None,
                    return_lse: bool = False, window: Optional[int] = None):
    """Stock-XLA attention over (B, T, H, D) tensors — THE dense softmax
    path, shared by MultiHeadAttention's short-T branch and the Ulysses
    non-flash branch, so mask/scale/dtype policy lives in exactly one
    place. k and v may have fewer heads than q (grouped queries: query head
    h reads K/V head h // (H // Hkv)). ``selection`` (B, T, T), non-zero
    where a query may see a key, is and-ed with the causal mask, and so is
    a ``window``: query t sees the keys t - window < s <= t.
    ``return_lse``: also each row's log-sum-exp of its scaled, masked
    scores, (B, H, T) float32, as a constant (``flash_attention``'s)."""
    hd = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(hd))
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        if window is not None:
            mask = jnp.logical_and(mask, jnp.triu(mask, 1 - window))
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
    if selection is not None:
        s = jnp.where((selection != 0)[:, None], s, jnp.float32(-1e30))
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", a, v)
    if return_lse:
        return out, jax.lax.stop_gradient(
            jax.scipy.special.logsumexp(s, axis=-1))
    return out


def resolve_blocks(t: int, itemsize: int, block_q: Optional[int] = None,
                   block_k: int = 1024):
    """The (block_q, block_k) ``flash_attention`` runs a sequence of ``t``
    rows with: its swept defaults, clamped to the sequence and to each
    other."""
    rt = _round_up(t, 8)
    if block_q is None:
        # Swept default with scoped-VMEM clamps (16MB limit on v5e). The
        # sweeps (docs/PERF.md rounds 3-5) moved these grid blocks, and a
        # smaller one lost: it skips more of the causal triangle but pays
        # a grid step, a DMA and a scratch round trip a tile. The skipping
        # now happens inside the block at no such cost (_SUBTILE), so the
        # block stays as large as VMEM allows:
        # - float32 inputs double every resident block (measured compile
        #   failure at T>=2048 with 1024);
        # - bf16 at long sequence: the full-model BACKWARD kernel's stack
        #   (dq/dk/dv blocks + f32 stat rows spanning T) measured over the
        #   limit at T=4096 with bq=1024. T=2048 compiles in-model and is
        #   ~25% faster with 1024 (confirmed twice), so the bf16 clamp
        #   starts strictly above it; (2048, 4096) is clamped — bq=512
        #   still beats the old 256 default by ~11% at T=4096
        #   (docs/PERF.md round-4 sweep).
        block_q = 1024
        if itemsize >= 4 or rt > 2048:
            block_q = 512
    bq = min(block_q, rt)
    # Clamp block_k to the q-rounded sequence length: t_pad is a multiple of
    # max(bq, bk), so an unclamped default (1024) would pad mid-size
    # sequences (e.g. T=600) up to 2x. With bk <= round_up(t, bq) the padded
    # work is bounded by one q-block: t_pad <= t + bq.
    bk = min(block_k, _round_up(t, bq))
    if max(bq, bk) % min(bq, bk):  # clamping broke divisibility
        bq = bk = min(bq, bk)
    return bq, bk


def walked_pairs(t: int, heads: int, head_dim: int, itemsize: int,
                 window: Optional[int]) -> int:
    """(query, key) pairs of the sub-tiles ``flash_attention`` computes for
    one head of one sequence of ``t`` rows under causality and this
    ``window``, at the blocks and the layout it resolves to: the area the
    kernels' walk covers, to set beside the pairs the window holds."""
    bq, bk = resolve_blocks(t, itemsize)
    lanes = (_LANES if _packed_supported(heads, head_dim)
             else _lane_pad(head_dim))
    computed = subtile_counts(t, bq, bk, True, lanes,
                              window if window and window < t else None)[1]
    return computed * _subtile(bq, lanes) * _subtile(bk, lanes)


def selection_blocks(selection, block_q: int, block_k: int):
    """``(flags, total)`` of a causal selection (B, T, T) under these grid
    blocks: ``flags`` (B, nq, nk) int32, 1 where the block holds a selected
    pair (the kernels run no walk in the others), and ``total``, the blocks
    at or below the diagonal: what a plain causal call would walk."""
    b, t, _ = selection.shape
    t_pad = _round_up(t, max(block_q, block_k))
    nq, nk = t_pad // block_q, t_pad // block_k
    pairs = jnp.pad(selection, ((0, 0), (0, t_pad - t), (0, t_pad - t)))
    flags = jnp.any(pairs.reshape(b, nq, block_q, nk, block_k) != 0,
                    axis=(2, 4)).astype(jnp.int32)
    total = sum(qi * block_q - ki * block_k > -block_q
                for qi in range(nq) for ki in range(nk))
    return flags, total


def flash_attention(
    q, k, v, *, causal: bool = False,
    block_q: Optional[int] = None, block_k: int = 1024,
    selection=None, selection_flags=None, return_lse: bool = False,
    window: Optional[int] = None,
):
    """softmax(Q K^T / sqrt(d)) V without materializing the (T, T) scores.

    q: (B, T, H, D); k: (B, T, Hkv, D); v: (B, T, Hkv, Dv) — the layout
    MultiHeadAttention produces. Dv is D everywhere but under latent
    attention, whose keys carry the rope part and are wider than its values
    (192 and 128): the folded layout takes that as it is, each width padded
    to whole lanes. Hkv is H, or divides it (grouped queries: query head h
    reads K/V head h // (H // Hkv)); with 128-wide heads the kernels read
    the shared head in place, in the backward too, where dk/dv sum over a
    group's query heads; any other shape repeats K and V.
    Returns (B, T, H, Dv) in q's dtype. Scores/softmax compute in float32.
    Mosaic on TPU, the Pallas interpreter on CPU (the test configuration);
    any other backend is an error (``_pallas_common.interpret``).

    ``selection`` (B, T, T) int8: non-zero where a query may see a key, one
    selection for all heads, and-ed with the causal mask in all three
    kernels (``dtpu_flash_*_sel``; 128-wide heads only). A grid block that
    holds no selected pair runs no walk (``selection_blocks``, whose flags
    may be handed in as ``selection_flags`` by a caller that counts them).
    A selection has no gradient. Without one the kernels are the plain ones:
    no operand and no branch is added. ``return_lse`` (with a selection):
    also the kernels' row statistic, each row's log-sum-exp of its scaled
    scores over the keys it sees, (B, H, T) float32, as a constant: what the
    probabilities are recomputed from, exp(score - lse), by whoever needs
    them after the call.

    ``window`` (causal, no selection): query t sees the keys t - window < s
    <= t (sliding-window attention). The kernels, then named
    ``dtpu_flash_*_swa``, skip the sub-tiles left of the band as they skip
    those above the diagonal and mask the ones its edge crosses, and the
    grids hold a block's band alone: a kv block left of it is neither
    stepped over nor fetched (``_band_steps``, ``kv_block_fetches``). A
    window that holds the whole sequence is the plain causal call.

    ``block_q`` / ``block_k`` are the DMA blocks: what one grid step holds
    in VMEM. ``block_q=None`` (default) resolves to the swept 1024, scoped-
    VMEM-clamped to 512 for float32 inputs (any length) and for bf16 above
    T=2048 (``resolve_blocks``). An EXPLICIT block_q is honored
    as passed — sweeps on chips with different VMEM budgets must measure
    what they ask for. Inside a block the kernels compute by sub-tiles
    (``_subtile``: side ``_SUBTILE`` under a 128-lane head block), which is
    no argument:
    what is skipped and what is masked follows from ``causal``, T and the
    blocks.
    """
    b, t, h, d = q.shape
    bq, bk = resolve_blocks(t, jnp.dtype(q.dtype).itemsize, block_q, block_k)
    if window is not None:
        if not causal or selection is not None or window < 1:
            raise ValueError(
                "a window is a positive number of keys under causal "
                f"attention with no selection; got window={window}, "
                f"causal={causal}")
        window = int(window) if window < t else None
    dv = v.shape[-1]
    packed = dv == d and _packed_supported(h, d)
    group = h // k.shape[2]
    if group > 1 and not (packed and d == _LANES):
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
        group = 1
    # How far the sub-tile walk engages is static for a shape, so it is
    # published here, at trace time, as counts.
    gauge = default_registry().gauge
    for name, n in zip(("square", "computed", "masked"), subtile_counts(
            t, bq, bk, causal, _LANES if packed else _lane_pad(d), window)):
        gauge(f"flash.subtiles_{name}", n)
    # So is how often the kernels fetch a K/V block, beside once a head.
    t_pad = _round_up(t, max(bq, bk))
    rows, head_blocks = (b, h * d // _LANES) if packed else (b * h, 1)
    share = _heads_a_fetch(group, bq, bk, jnp.dtype(q.dtype).itemsize)
    for name, n in zip(("", "_a_head"), kv_block_fetches(
            rows, head_blocks, share, t_pad // bq, t_pad // bk,
            window and (bq, bk, window))):
        gauge(f"flash.kv_block_fetches{name}", n)
    if selection is not None:
        if not (packed and d == _LANES and causal):
            raise ValueError(
                "a selection is taken by the causal lane-packed kernels at "
                f"128-wide heads; got heads of {d} and {dv}, causal={causal}")
        if selection_flags is None:
            selection_flags = selection_blocks(selection, bq, bk)[0]
        flash = _flash_cached(h, 1, "", causal, bq, bk, group, True)
        out, lse = flash(
            q.reshape(b, t, h * d), k.reshape(b, t, -1), v.reshape(b, t, -1),
            (selection_flags.reshape(-1), selection.astype(jnp.int8)))
        out = out.reshape(b, t, h, d)
        if return_lse:
            return out, jax.lax.stop_gradient(lse[:, :, 0, :t])
        return out
    if return_lse:
        raise ValueError("return_lse is the selection-taking kernels'")
    if packed:
        # Lane-packed path: kernels read heads straight from the (B, T,
        # H*D) projection layout — the reshape is free, no transposes.
        flash = _flash_cached(h, _LANES // d, "_swa" if window else "_packed",
                              causal, bq, bk, group, False, window)
        return flash(
            q.reshape(b, t, h * d), k.reshape(b, t, -1),
            v.reshape(b, t, -1),
        ).reshape(b, t, h, d)
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, x.shape[-1])
    out = _flash_cached(1, 1, "_swa" if window else "", causal, bq, bk, 1,
                        False, window)(fold(q), fold(k), fold(v))
    return jnp.moveaxis(out.reshape(b, h, t, dv), 1, 2)
