"""Fused paged-attention decode kernel (vLLM-style PagedAttention).

The serving decode hot loop reads per-slot KV through a block table: the
reference path (``nn.attention.MultiHeadAttention.paged_decode``) first
GATHERS every slot's blocks into a contiguous ``(S, L, H, hd)`` view
(``_paged_view`` — one HBM round-trip for the whole view, L = table
width x block size), then runs dense masked attention over it (a second
pass over the same bytes). This kernel fuses the two: the Pallas grid
walks ``(slot, table_entry)`` with the table dimension innermost and
sequential, the block table rides as a SCALAR-PREFETCH operand so each
grid step's BlockSpec index map picks the pool block to stream into VMEM
(``tables[s, j]`` — the PagedAttention gather, done by the memory system
instead of a materialized gather), and the online-softmax recurrence
(running max / sum / accumulator in VMEM scratch, exactly flash
attention's) folds each block into the context as it arrives. No
``(S, L, H, hd)`` view ever exists.

Covers decode (K=1 query row per slot) and the speculative ``paged_verify``
dispatch (K candidate rows per slot at consecutive positions) with the
same kernel: query row k of slot s attends to absolute positions
``<= positions[s] + k``. Plain f32/bf16 pools and the int8 ``{"q","scale"}``
pools (quant.py idiom) are both handled — int8 payload blocks and their
per-(position, head) scales stream separately and dequantize IN-KERNEL,
per head, in VMEM (the reference path dequantizes the whole gathered view
in HBM first).

The K/V SCATTER of the new rows stays plain XLA in the caller — it is a
tiny ``S`` (or ``S*K``)-row write, not a per-layer L-sized pass; only the
gather + attention read path is worth fusing.

Selection is ambient at trace time (``decode_kernel_scope`` /
``current_decode_kernel``, the same threadlocal idiom as
``parallel.strategy.current_strategy``): ``serving.Engine(decode_kernel=
"fused")`` and ``fleet.EnginePrograms(decode_kernel="fused")`` enter the
scope around their jitted dispatches, so the attention layer picks the
kernel while tracing and the jit cache keys stay per-engine.

CPU/tests run the kernel via Pallas interpret mode (same semantics); on
TPU it compiles to Mosaic. Parity vs the reference path is pinned by
tests/test_paged_kernel.py; the throughput claim is reserved for a real
accelerator (docs/API.md "Fused paged attention").
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quant import QKEY, SKEY
from ._pallas_common import NEG as _NEG, interpret as _interpret

# ------------------------------------------------ kernel selection (ambient)
REFERENCE = "reference"
FUSED = "fused"
KINDS = (REFERENCE, FUSED)

_local = threading.local()


def current_decode_kernel() -> str:
    """The ambient decode-kernel choice ('reference' outside any scope).
    Read at TRACE time by MultiHeadAttention.paged_decode/paged_verify —
    like ``current_strategy``, an ambient-context seam so layer call
    signatures don't grow an engine-plumbing argument."""
    return getattr(_local, "kind", REFERENCE)


@contextlib.contextmanager
def decode_kernel_scope(kind: str):
    """Make ``kind`` ('reference' | 'fused') the ambient decode kernel for
    the duration — wrap the first (tracing) call of a jitted decode/verify
    dispatch so the traced program bakes the chosen kernel in."""
    if kind not in KINDS:
        raise ValueError(
            f"decode_kernel must be one of {KINDS}, got {kind!r}"
        )
    prev = getattr(_local, "kind", REFERENCE)
    _local.kind = kind
    try:
        yield
    finally:
        _local.kind = prev


# ------------------------------------------------------------------ kernels
def _decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, sqrt_hd, bs, h, hd, nb, kw):
    """One (slot s, table entry j) grid step over a PLAIN pool block.

    q_ref (1, kw, h*hd): slot s's kw query rows, heads flattened into the
    lane dim; k_ref/v_ref (1, bs, h*hd): pool block ``tables[s, j]``
    (the scalar-prefetch index map IS the gather). Scratch m/l (kw, h) and
    acc (kw, h*hd) carry the per-head online-softmax state across the
    sequential j dimension; the causal mask compares each block column's
    absolute position ``j*bs + c`` against query row k's own position
    ``pos[s] + k`` (K=1 decode degenerates to ``<= pos[s]``)."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[s]
    q = q_ref[0]  # (kw, h*hd)
    k = k_ref[0]  # (bs, h*hd)
    v = v_ref[0]
    col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (kw, bs), 1)
    row = pos + jax.lax.broadcasted_iota(jnp.int32, (kw, bs), 0)
    valid = col <= row
    for hx in range(h):
        sl = slice(hx * hd, (hx + 1) * hd)
        sc = jax.lax.dot_general(
            q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / sqrt_hd  # (kw, bs); divide (not scale-multiply) matches the
        # reference path bit-for-bit
        sc = jnp.where(valid, sc, _NEG)
        m_prev = m_ref[:, hx:hx + 1]
        l_prev = l_ref[:, hx:hx + 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v[:, sl], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, hx:hx + 1] = m_new
        l_ref[:, hx:hx + 1] = l_new

    @pl.when(j == nb - 1)
    def _finish():
        for hx in range(h):
            sl = slice(hx * hd, (hx + 1) * hd)
            o_ref[0, :, sl] = (
                acc_ref[:, sl]
                / jnp.maximum(l_ref[:, hx:hx + 1], 1e-30)
            ).astype(o_ref.dtype)


def _decode_kernel_quant(tables_ref, pos_ref, q_ref, k_ref, ks_ref,
                         v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref,
                         *, sqrt_hd, bs, h, hd, nb, kw):
    """int8-pool variant: payload blocks (int8) and their per-(position,
    head) scales (f32, (1, bs, h)) stream as separate operands through the
    same table-indexed BlockSpecs; each head's rows dequantize in VMEM
    (``q * scale`` in f32, rounded once to the query dtype — the same
    single-rounding contract as quant.dequantize) right before its dot."""
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[s]
    q = q_ref[0]   # (kw, h*hd), query dtype
    k = k_ref[0]   # (bs, h*hd), int8
    ks = ks_ref[0]  # (bs, h), f32 scales
    v = v_ref[0]
    vs = vs_ref[0]
    col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (kw, bs), 1)
    row = pos + jax.lax.broadcasted_iota(jnp.int32, (kw, bs), 0)
    valid = col <= row
    for hx in range(h):
        sl = slice(hx * hd, (hx + 1) * hd)
        kh = (
            k[:, sl].astype(jnp.float32) * ks[:, hx:hx + 1]
        ).astype(q.dtype)
        vh = (
            v[:, sl].astype(jnp.float32) * vs[:, hx:hx + 1]
        ).astype(q.dtype)
        sc = jax.lax.dot_general(
            q[:, sl], kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / sqrt_hd
        sc = jnp.where(valid, sc, _NEG)
        m_prev = m_ref[:, hx:hx + 1]
        l_prev = l_ref[:, hx:hx + 1]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
            p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:, hx:hx + 1] = m_new
        l_ref[:, hx:hx + 1] = l_new

    @pl.when(j == nb - 1)
    def _finish():
        for hx in range(h):
            sl = slice(hx * hd, (hx + 1) * hd)
            o_ref[0, :, sl] = (
                acc_ref[:, sl]
                / jnp.maximum(l_ref[:, hx:hx + 1], 1e-30)
            ).astype(o_ref.dtype)


# -------------------------------------------------------------- entry point
def paged_attention(q, k_pool, v_pool, block_tables, positions):
    """Fused gather + masked attention over paged KV pools.

    ``q`` (S, K, H, hd): K query rows per slot at consecutive absolute
    positions starting at ``positions[s]`` (K=1 is plain decode, K>1 the
    speculative verify window). ``k_pool``/``v_pool``: a plain
    (num_blocks, bs, H, hd) array or an int8 ``{"q","scale"}`` dict
    (scales (num_blocks, bs, H, 1)). ``block_tables`` (S, NB) int32 maps
    each slot's logical block j to its pool block. Returns the context
    (S, K, H, hd) in ``q.dtype`` — what the reference path's
    ``softmax(q @ view_k / sqrt(hd), causal mask) @ view_v`` computes,
    without materializing the view.
    """
    s, kw, h, hd = q.shape
    quant = isinstance(k_pool, dict)
    kq = k_pool[QKEY] if quant else k_pool
    nblocks, bs = kq.shape[0], kq.shape[1]
    nb = block_tables.shape[1]
    tables = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)
    q2 = q.reshape(s, kw, h * hd)
    sqrt_hd = float(math.sqrt(hd))

    def q_map(si, j, t, p):
        return (si, 0, 0)

    def pool_map(si, j, t, p):
        return (t[si, j], 0, 0)

    q_spec = pl.BlockSpec((1, kw, h * hd), q_map)
    pool_spec = pl.BlockSpec((1, bs, h * hd), pool_map)
    if quant:
        kernel = _decode_kernel_quant
        scale_spec = pl.BlockSpec((1, bs, h), pool_map)
        in_specs = [q_spec, pool_spec, scale_spec, pool_spec, scale_spec]
        inputs = [
            q2,
            k_pool[QKEY].reshape(nblocks, bs, h * hd),
            k_pool[SKEY].reshape(nblocks, bs, h),
            v_pool[QKEY].reshape(nblocks, bs, h * hd),
            v_pool[SKEY].reshape(nblocks, bs, h),
        ]
    else:
        kernel = _decode_kernel
        in_specs = [q_spec, pool_spec, pool_spec]
        inputs = [
            q2,
            k_pool.reshape(nblocks, bs, h * hd),
            v_pool.reshape(nblocks, bs, h * hd),
        ]

    out = pl.pallas_call(
        functools.partial(
            kernel, sqrt_hd=sqrt_hd, bs=bs, h=h, hd=hd, nb=nb, kw=kw,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kw, h * hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((kw, h), jnp.float32),
                pltpu.VMEM((kw, h), jnp.float32),
                pltpu.VMEM((kw, h * hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kw, h * hd), q.dtype),
        name="dtpu_paged_attention_int8" if quant else "dtpu_paged_attention",
        interpret=_interpret(),
    )(tables, pos, *inputs)
    return out.reshape(s, kw, h, hd)
