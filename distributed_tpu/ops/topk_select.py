"""Row-wise selection of the ``k`` largest scores, as a mask.

Learned sparse attention (DeepSeek-V3.2-Exp's lightning indexer) keeps, for
each query, the ``k`` keys with the largest index score: 2048 of up to 8192
in each of 8192 rows, in every layer and step. What the attention kernels
need of it is a mask, not the indices, so the work is finding each row's
``k``-th largest value: ``topk_mask`` returns ``scores >= that value`` among
the valid entries (all of them in a row with at most ``k``; entries that tie
with the ``k``-th are all kept, which on float32 scores happens by accident
only).

The threshold is found by bisection: the float32 scores are mapped to
unsigned integers of the same order, and the threshold is built a bit at a
time from the top, keeping a bit where at least ``k`` keys still reach the
candidate: 32 fused compare-and-count passes over the row block, no sort, no
index. Exact. (``jax.lax.top_k`` and its last value give the same mask nine
times slower on the v5e: ``scripts/select_times.py``, root PERF.md.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ordered_bits(x):
    """float32 -> uint32 with the same order (-0.0 below +0.0): the sign bit
    set on the non-negative, every bit flipped on the negative. The smallest
    finite or infinite float maps above 0, which then stands for 'invalid'."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    negative = (bits >> 31).astype(bool)
    return jnp.where(negative, ~bits, bits | jnp.uint32(0x80000000))


def _kth_largest_bits(keys, k: int):
    """The largest threshold that at least ``k`` of each row's ``keys``
    (..., n) uint32 reach, (...,); 0 in a row where fewer than ``k`` are
    above 0."""
    def bit(i, thr):
        cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        reach = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, thr)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def topk_mask(scores, k: int, valid=None):
    """Boolean mask, ``scores``'s shape: in each row of the last axis the
    ``k`` largest of the entries ``valid`` allows (all where None), or every
    valid entry where there are no more than ``k``. No gradient."""
    scores = jax.lax.stop_gradient(scores).astype(jnp.float32)
    if valid is None:
        valid = jnp.ones(scores.shape, bool)
    k = min(int(k), scores.shape[-1])
    keys = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    kth = _kth_largest_bits(keys, k)
    return jnp.logical_and(valid, keys >= kth[..., None])
