"""Helpers shared by every Pallas kernel module in ``ops``.

``flash_attention``, ``pallas_kernels`` and ``fused_update`` all need the
same two decisions — *where* a kernel runs (Mosaic on real TPUs,
interpreter everywhere else) and *how* shapes are padded to tile
boundaries. Both used to be copy-pasted per module; this is the single
definition (ring_attention builds on shard_map/ppermute, not pallas_call,
so it has nothing to consolidate here).
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True when pallas_call must run in interpreter mode: Mosaic compiles
    the kernels on TPU; the CPU backend (tier-1, the 8-device sim) runs the
    same kernel semantics in the interpreter. Any other backend is an
    error — no kernel here has run on one, and an interpreted kernel on an
    accelerator would hide that."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"distributed_tpu's Pallas kernels run on TPU (Mosaic) or CPU "
        f"(interpreter); backend {backend!r} is not supported"
    )


def round_up(v: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``v``."""
    return -(-v // m) * m


# Column/score padding value shared by the attention-family kernels:
# exp(NEG - max) == 0, and NEG is large enough to never be the row max.
NEG = -1e30

# TPU vector-lane width: the last-dim tile size every kernel in this
# package pads or packs to (flash_attention's head packing, fused_update's
# flat segments, paged_attention's head-flattened pools).
LANES = 128


def packed_supported(num_heads: int, head_dim: int) -> bool:
    """True when ``num_heads`` heads of ``head_dim`` columns tile the
    128-lane vector exactly — the precondition for the lane-packed
    attention kernels (several heads share one lane vector, so a python
    per-head loop over lane slices stays a static unrolled body)."""
    return (
        head_dim <= LANES
        and LANES % head_dim == 0
        and num_heads % (LANES // head_dim) == 0
    )
