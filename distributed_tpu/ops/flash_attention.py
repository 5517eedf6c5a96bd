"""Flash attention: Pallas TPU forward kernel + blockwise backward.

The dense attention path materializes the (B, H, T, T) score tensor in HBM —
at T=8k and 12 heads that is the whole memory budget. This kernel computes
softmax(QK^T)V with the online-softmax recurrence entirely in VMEM: the
grid walks (batch*heads, q_blocks, kv_blocks) with the kv dimension
innermost and sequential, carrying the running max/sum/accumulator in
scratch, so HBM traffic is O(T*D) instead of O(T^2).

The backward pass is a pair of Pallas kernels (dq with the kv dimension
innermost; dk/dv with the q dimension innermost) that recompute the
probabilities in VMEM from the saved per-row statistics (m, l) —
flash-style rematerialization; HBM traffic stays O(T*D) and no (T, T)
matrix ever exists. (The first implementation was a plain-JAX blockwise
scan; on the TPU it ran at ~12% MFU per layer because XLA serialized the
kv-block loop as a while op — the kernels keep the MXU busy instead.)

The reference has no attention anywhere (SURVEY.md §2c); this is part of the
long-context tier the framework adds (with ops.ring_attention for the
sequence-parallel case — ring attention distributes *across chips*, flash
attention blocks *within* a chip; MultiHeadAttention composes them).

CPU/tests run the same kernel via Pallas interpret mode; on TPU it compiles
to Mosaic.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from ._pallas_common import (
    LANES as _LANES,
    NEG as _NEG,
    interpret as _interpret,
    packed_supported as _packed_supported,
    round_up as _round_up,
)


# -------------------------------------------------- shared kernel helpers --
def _valid_mask(qi, ki, block_q, block_k, t_actual, causal):
    """(block_q, block_k) mask: real columns, and under causality the
    lower-triangular band for this (qi, ki) block pair. The single source
    of truth for masking across all six kernels (folded + packed)."""
    col = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    valid = col < t_actual
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        valid = jnp.logical_and(valid, col <= row)
    return valid


def _p_ds(q, k, v, do, m, l, delta, valid, scale):
    """Backward-pass block math shared by all dq/dk/dv kernels: recompute
    p from the saved row stats (flash-style), then ds = p*(dO V^T -
    delta)*scale. q/do: (bq, d); k/v: (bk, d); m/l/delta: (bq, 1)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    p = jnp.where(valid, jnp.exp(s - m) / jnp.maximum(l, 1e-30), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta) * scale
    return p, ds


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                m_ref, l_ref, acc_ref,
                *, scale, block_q, block_k, t_actual, causal, nk):
    """One (bh, qi, ki) grid step. Scratch carries the online-softmax state
    across the sequential ki dimension."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: a kv block strictly above the diagonal band contributes
    # nothing — skip its matmuls entirely (the scratch carries through).
    def compute():
        # Keep inputs in their storage dtype for the MXU (bf16 matmul with
        # f32 accumulate); only the softmax recurrence runs in f32.
        q = q_ref[0]  # (block_q, d_pad)
        k = k_ref[0]  # (block_k, d_pad)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k) f32

        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        s = jnp.where(valid, s, _NEG)

        m_prev = m_ref[...]  # (block_q, 128), all lanes equal
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (block_q, 1)
        p = jnp.exp(s - m_new[:, :1])  # (block_q, block_k)
        l_new = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_prev.shape
        )
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # Not taken only when the whole block is above the diagonal.
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k):
    """q,k,v: (BH, T, D). Returns (out, m_rows, l_rows) with m/l: (BH, T)."""
    bh, t, d = q.shape
    if max(block_q, block_k) % min(block_q, block_k):
        raise ValueError(
            f"block_q={block_q} and block_k={block_k} must divide each "
            "other, or trailing rows would fall outside the grid"
        )
    t_pad = _round_up(t, max(block_q, block_k))
    d_pad = _round_up(max(d, 128), 128)
    pad = lambda x: jnp.pad(
        x, ((0, 0), (0, t_pad - t), (0, d_pad - d))
    )
    qp, kp, vp = pad(q), pad(k), pad(v)
    nq = t_pad // block_q
    nk = t_pad // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        t_actual=t, causal=causal, nk=nk,
    )
    out, m_out, l_out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), q.dtype),
            jax.ShapeDtypeStruct((bh, t_pad, 128), jnp.float32),
            jax.ShapeDtypeStruct((bh, t_pad, 128), jnp.float32),
        ],
        scratch_shapes=[
            # m, l, acc live across the sequential ki dimension.
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_pad), jnp.float32),
        ],
        name="dtpu_flash_fwd",
        interpret=_interpret(),
    )(qp, kp, vp)
    # Residual stats are sliced to one value per row: the lane-replicated
    # (bh, t_pad, 128) kernel form is 128x larger and would dominate
    # forward->backward residual memory at long T; the backward
    # re-broadcasts transiently instead.
    return out[:, :t, :d], m_out[:, :t, 0], l_out[:, :t, 0]


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref, dq_ref,
               acc_ref, *, scale, block_q, block_k, t_actual, causal, nk):
    """dq for one (bh, qi, ki) grid step; ki sequential, acc in scratch.

    p is recomputed from the saved row statistics (m, l) flash-style —
    never a (T, T) tensor in HBM; ds = p * (dO V^T - delta) * scale;
    dq += ds K."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        k = k_ref[0]
        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        _, ds = _p_ds(
            q_ref[0], k, v_ref[0], do_ref[0],
            m_ref[0][:, :1], l_ref[0][:, :1], dl_ref[0][:, :1],
            valid, scale,
        )
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref,
                dk_ref, dv_ref, acc_dk, acc_dv,
                *, scale, block_q, block_k, t_actual, causal, nq):
    """dk/dv for one (bh, ki, qi) grid step; qi sequential, accs in scratch.

    dv += p^T dO; dk += ds^T q — both contractions over the q-block rows."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    def compute():
        q = q_ref[0]
        do = do_ref[0]
        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        p, ds = _p_ds(
            q, k_ref[0], v_ref[0], do,
            m_ref[0][:, :1], l_ref[0][:, :1], dl_ref[0][:, :1],
            valid, scale,
        )
        acc_dv[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, d)
        acc_dk[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bk, d)

    if causal:
        # Skip q blocks entirely above the diagonal band (no row of this
        # q block can see any column of this kv block).
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = acc_dk[...].astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


def _bwd_pallas(res, g, *, scale, causal, block_q, block_k):
    """Pallas dq/dk/dv from the saved row stats: two kernels (dq with kv
    innermost; dk/dv with q innermost), each O(T*D) HBM traffic."""
    q, k, v, out, m_rows, l_rows = res  # m/l: (bh, t)
    bh, t, d = q.shape
    t_pad = _round_up(t, max(block_q, block_k))
    d_pad = _round_up(max(d, 128), 128)
    pad = lambda x: jnp.pad(x, ((0, 0), (0, t_pad - t), (0, d_pad - d)))
    qp, kp, vp = pad(q), pad(k), pad(v)
    dop = pad(g.astype(q.dtype))
    nq = t_pad // block_q
    nk = t_pad // block_k

    # delta_i = sum_j dO_ij O_ij; m/l/delta broadcast across lanes into
    # the kernels' (1, block_q, 128) row-stat form (transient buffers —
    # only the (bh, t) stats are held as residuals from the forward).
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (bh, t)

    def rowstat(x):
        return jnp.broadcast_to(
            jnp.pad(x, ((0, 0), (0, t_pad - t)))[..., None],
            (bh, t_pad, 128),
        )

    m_b, l_b, dl_b = rowstat(m_rows), rowstat(l_rows), rowstat(delta)

    row_spec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            t_actual=t, causal=causal, nk=nk,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
            row_spec, row_spec, row_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t_pad, d_pad), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d_pad), jnp.float32)],
        name="dtpu_flash_dq",
        interpret=_interpret(),
    )(qp, kp, vp, dop, m_b, l_b, dl_b)

    row_spec_kv = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            t_actual=t, causal=causal, nq=nq,
        ),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d_pad), lambda b, i, j: (b, j, 0)),
            row_spec_kv, row_spec_kv, row_spec_kv,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d_pad), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), k.dtype),
            jax.ShapeDtypeStruct((bh, t_pad, d_pad), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d_pad), jnp.float32),
            pltpu.VMEM((block_k, d_pad), jnp.float32),
        ],
        name="dtpu_flash_dkv",
        interpret=_interpret(),
    )(qp, kp, vp, dop, m_b, l_b, dl_b)
    return dq[:, :t, :d], dk[:, :t, :d], dv[:, :t, :d]


# ------------------------------------------------- lane-packed (B,T,H*D) --
# The folded kernels above take (B*H, T, D) and therefore need a
# (B,T,H,D) -> (B,H,T,D) transpose around every call — profiled at
# 25-30% of a GPT-2-small training step on v5e (the transposes run at
# ~150 GB/s and there are ~8 per layer). The kernels below read the
# attention heads straight out of the projection layout (B, T, H*D):
# each 128-lane block holds 128//D whole heads side by side, the grid
# walks (batch, head-block, q-block, kv-block), and the per-head math
# slices lanes in VMEM. No HBM transpose exists in either direction.
# Requires 128 % D == 0 and H % (128//D) == 0 (covers head_dim 64/128);
# other shapes fall back to the folded path.

# Sequence length (padded) above which the packed kernels save their row
# stats compactly ((b, nh, t_pad, heads_per_block)) and re-expand in the
# backward: the lane-replicated form reads fastest under Mosaic but costs
# 128/heads_per_block x the residual memory, which only matters once T is
# long enough for stats to rival the activations themselves.
_COMPACT_STATS_MIN_T = 2048


def _fwd_kernel_packed(q_ref, k_ref, v_ref, o_ref, m_out_ref, l_out_ref,
                       m_ref, l_ref, acc_ref,
                       *, scale, hd, block_q, block_k, t_actual, causal, nk):
    """One (b, hblk, qi, ki) grid step on (1, block, 128) lane-packed tiles;
    the 128 lanes hold 128//hd heads. Scratch m/l keep each head's running
    stat replicated across that head's lane span."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0]  # (block_q, 128)
        k = k_ref[0]  # (block_k, 128)
        v = v_ref[0]
        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        for hx in range(_LANES // hd):
            sl = slice(hx * hd, (hx + 1) * hd)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (block_q, block_k)
            s = jnp.where(valid, s, _NEG)
            m_prev = m_ref[:, sl]  # (block_q, hd), lanes equal
            l_prev = l_ref[:, sl]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(
                m_prev, jnp.broadcast_to(m_cur, m_prev.shape)
            )
            alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
            p = jnp.exp(s - m_new[:, :1])
            l_new = l_prev * alpha + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), l_prev.shape
            )
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jnp.dot(
                p.astype(v.dtype), v[:, sl],
                preferred_element_type=jnp.float32,
            )
            m_ref[:, sl] = m_new
            l_ref[:, sl] = l_new

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        for hx in range(_LANES // hd):
            sl = slice(hx * hd, (hx + 1) * hd)
            o_ref[0, :, sl] = (
                acc_ref[:, sl]
                / jnp.maximum(l_ref[:, hx * hd : hx * hd + 1], 1e-30)
            ).astype(o_ref.dtype)
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def _dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref,
                      dq_ref, acc_ref,
                      *, scale, hd, block_q, block_k, t_actual, causal, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        for hx in range(_LANES // hd):
            sl = slice(hx * hd, (hx + 1) * hd)
            _, ds = _p_ds(
                q[:, sl], k[:, sl], v[:, sl], do[:, sl],
                m_ref[0, 0, :, hx * hd : hx * hd + 1],
                l_ref[0, 0, :, hx * hd : hx * hd + 1],
                dl_ref[0, 0, :, hx * hd : hx * hd + 1],
                valid, scale,
            )
            acc_ref[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dl_ref,
                       dk_ref, dv_ref, acc_dk, acc_dv,
                       *, scale, hd, block_q, block_k, t_actual, causal, nq):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    def compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        valid = _valid_mask(qi, ki, block_q, block_k, t_actual, causal)
        for hx in range(_LANES // hd):
            sl = slice(hx * hd, (hx + 1) * hd)
            p, ds = _p_ds(
                q[:, sl], k[:, sl], v[:, sl], do[:, sl],
                m_ref[0, 0, :, hx * hd : hx * hd + 1],
                l_ref[0, 0, :, hx * hd : hx * hd + 1],
                dl_ref[0, 0, :, hx * hd : hx * hd + 1],
                valid, scale,
            )
            acc_dv[:, sl] += jax.lax.dot_general(
                p.astype(do.dtype), do[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_dk[:, sl] += jax.lax.dot_general(
                ds.astype(q.dtype), q[:, sl], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            compute()
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = acc_dk[...].astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


def _fwd_pallas_packed(qf, kf, vf, h, d, scale, causal, block_q, block_k):
    """qf,kf,vf: (B, T, H*D) lane-packed. Returns (out, m, l) with out in
    the same layout and m/l: (B, H//hpb, t_pad, 128)."""
    b, t, _ = qf.shape
    t_pad = _round_up(t, max(block_q, block_k))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    qp, kp, vp = pad(qf), pad(kf), pad(vf)
    hpb = _LANES // d
    nh = h // hpb
    nq = t_pad // block_q
    nk = t_pad // block_k

    kernel = functools.partial(
        _fwd_kernel_packed, scale=scale, hd=d, block_q=block_q,
        block_k=block_k, t_actual=t, causal=causal, nk=nk,
    )
    lane_q = pl.BlockSpec((1, block_q, _LANES), lambda b, h, i, j: (b, i, h))
    lane_k = pl.BlockSpec((1, block_k, _LANES), lambda b, h, i, j: (b, j, h))
    stat = pl.BlockSpec((1, 1, block_q, _LANES),
                        lambda b, h, i, j: (b, h, i, 0))
    out, m_out, l_out = pl.pallas_call(
        kernel,
        grid=(b, nh, nq, nk),
        in_specs=[lane_q, lane_k, lane_k],
        out_specs=[lane_q, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad, h * d), qf.dtype),
            jax.ShapeDtypeStruct((b, nh, t_pad, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, t_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        name="dtpu_flash_fwd_packed",
        interpret=_interpret(),
    )(qp, kp, vp)
    if t_pad >= _COMPACT_STATS_MIN_T:
        # Long context: slice to one value per head per row (the 128-lane
        # block holds 128//d heads, each replicated over its d-lane span)
        # so the residual is 1/d the size; the backward re-expands.
        return out[:, :t], m_out[..., ::d], l_out[..., ::d]
    return out[:, :t], m_out, l_out


def _bwd_pallas_packed(h, d, causal, block_q, block_k, res, g):
    qf, kf, vf, out, m_rows, l_rows = res  # m/l: (b, nh, t_pad)
    b, t, _ = qf.shape
    scale = 1.0 / np.sqrt(d)
    t_pad = _round_up(t, max(block_q, block_k))
    pad = lambda x: jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
    qp, kp, vp = pad(qf), pad(kf), pad(vf)
    dop = pad(g.astype(qf.dtype))
    hpb = _LANES // d
    nh = h // hpb
    nq = t_pad // block_q
    nk = t_pad // block_k

    # Short-T residuals arrive lane-replicated (fastest Mosaic reads);
    # long-T residuals arrive compact and are re-expanded transiently.
    if m_rows.shape[-1] == _LANES:
        m_out, l_out = m_rows, l_rows
    else:
        m_out = jnp.repeat(m_rows, d, axis=-1)  # (b, nh, t_pad, 128)
        l_out = jnp.repeat(l_rows, d, axis=-1)

    # delta per (b, t, head) -> the (b, nh, t_pad, 128) stat layout with
    # each head's value replicated across its lane span.
    gf = g.astype(jnp.float32).reshape(b, t, h, d)
    of = out.astype(jnp.float32).reshape(b, t, h, d)
    delta = jnp.sum(gf * of, axis=-1)  # (b, t, h)
    delta = jnp.repeat(
        delta.reshape(b, t, nh, hpb), d, axis=-1
    )  # (b, t, nh, 128)
    delta = jnp.moveaxis(delta, 2, 1)  # (b, nh, t, 128) — small tensor
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, t_pad - t), (0, 0)))

    lane_q = pl.BlockSpec((1, block_q, _LANES), lambda b, h, i, j: (b, i, h))
    lane_k = pl.BlockSpec((1, block_k, _LANES), lambda b, h, i, j: (b, j, h))
    stat_q = pl.BlockSpec((1, 1, block_q, _LANES),
                          lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel_packed, scale=scale, hd=d, block_q=block_q,
            block_k=block_k, t_actual=t, causal=causal, nk=nk,
        ),
        grid=(b, nh, nq, nk),
        in_specs=[lane_q, lane_k, lane_k, lane_q, stat_q, stat_q, stat_q],
        out_specs=lane_q,
        out_shape=jax.ShapeDtypeStruct((b, t_pad, h * d), qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32)],
        name="dtpu_flash_dq_packed",
        interpret=_interpret(),
    )(qp, kp, vp, dop, m_out, l_out, delta)

    lane_q_kv = pl.BlockSpec((1, block_q, _LANES),
                             lambda b, h, i, j: (b, j, h))
    lane_k_kv = pl.BlockSpec((1, block_k, _LANES),
                             lambda b, h, i, j: (b, i, h))
    stat_kv = pl.BlockSpec((1, 1, block_q, _LANES),
                           lambda b, h, i, j: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel_packed, scale=scale, hd=d, block_q=block_q,
            block_k=block_k, t_actual=t, causal=causal, nq=nq,
        ),
        grid=(b, nh, nk, nq),
        in_specs=[lane_q_kv, lane_k_kv, lane_k_kv, lane_q_kv,
                  stat_kv, stat_kv, stat_kv],
        out_specs=[lane_k_kv, lane_k_kv],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_pad, h * d), kf.dtype),
            jax.ShapeDtypeStruct((b, t_pad, h * d), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, _LANES), jnp.float32),
            pltpu.VMEM((block_k, _LANES), jnp.float32),
        ],
        name="dtpu_flash_dkv_packed",
        interpret=_interpret(),
    )(qp, kp, vp, dop, m_out, l_out, delta)
    return dq[:, :t], dk[:, :t], dv[:, :t]


def _make_packed(h, d, causal, block_q, block_k):
    """custom_vjp fn over (B, T, H*D) arrays for this static config."""

    @jax.custom_vjp
    def packed(qf, kf, vf):
        scale = 1.0 / np.sqrt(d)
        out, _, _ = _fwd_pallas_packed(
            qf, kf, vf, h, d, scale, causal, block_q, block_k
        )
        return out

    def fwd(qf, kf, vf):
        scale = 1.0 / np.sqrt(d)
        out, m_out, l_out = _fwd_pallas_packed(
            qf, kf, vf, h, d, scale, causal, block_q, block_k
        )
        return out, (qf, kf, vf, out, m_out, l_out)

    packed.defvjp(fwd, functools.partial(
        _bwd_pallas_packed, h, d, causal, block_q, block_k
    ))
    return packed


@functools.lru_cache(maxsize=64)
def _packed_cached(h, d, causal, block_q, block_k):
    return _make_packed(h, d, causal, block_q, block_k)


# -------------------------------------------------------------------- public
@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5)
)
def _flash(q, k, v, causal, block_q, block_k):
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, _, _ = _fwd_pallas(q, k, v, scale, causal, block_q, block_k)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k):
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, m_rows, l_rows = _fwd_pallas(q, k, v, scale, causal, block_q, block_k)
    return out, (q, k, v, out, m_rows, l_rows)


def _flash_bwd(causal, block_q, block_k, res, g):
    scale = 1.0 / np.sqrt(res[0].shape[-1])
    return _bwd_pallas(res, g, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def dense_attention(q, k, v, causal: bool):
    """Stock-XLA attention over (B, T, H, D) tensors — THE dense softmax
    path, shared by MultiHeadAttention's short-T branch and the Ulysses
    non-flash branch, so mask/scale/dtype policy lives in exactly one
    place."""
    hd = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(hd))
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, jnp.float32(-1e30))
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", a, v)


def flash_attention(
    q, k, v, *, causal: bool = False,
    block_q: Optional[int] = None, block_k: int = 1024,
):
    """softmax(Q K^T / sqrt(d)) V without materializing the (T, T) scores.

    q, k, v: (B, T, H, D) — same layout MultiHeadAttention produces.
    Returns (B, T, H, D) in q's dtype. Scores/softmax compute in float32.
    Mosaic on TPU, the Pallas interpreter on CPU (the test configuration);
    any other backend is an error (``_pallas_common.interpret``).

    ``block_q=None`` (default) resolves to the swept 1024, scoped-VMEM-
    clamped to 512 for float32 inputs (any length) and for bf16 above
    T=2048 (see the comment at the clamp). An EXPLICIT block_q is honored
    as passed — sweeps on chips with different VMEM budgets must measure
    what they ask for.
    """
    b, t, h, d = q.shape
    rt = _round_up(t, 8)
    if block_q is None:
        # Swept default with scoped-VMEM clamps (16MB limit on v5e):
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
        if jnp.dtype(q.dtype).itemsize >= 4 or rt > 2048:
            block_q = 512
    bq = min(block_q, rt)
    # Clamp block_k to the q-rounded sequence length: t_pad is a multiple of
    # max(bq, bk), so an unclamped default (1024) would pad mid-size
    # sequences (e.g. T=600) up to 2x. With bk <= round_up(t, bq) the padded
    # work is bounded by one q-block: t_pad <= t + bq.
    bk = min(block_k, _round_up(t, bq))
    if max(bq, bk) % min(bq, bk):  # clamping broke divisibility
        bq = bk = min(bq, bk)
    if _packed_supported(h, d):
        # Lane-packed path: kernels read heads straight from the (B, T,
        # H*D) projection layout — the reshape is free, no transposes.
        packed = _packed_cached(h, d, causal, bq, bk)
        return packed(
            q.reshape(b, t, h * d), k.reshape(b, t, h * d),
            v.reshape(b, t, h * d),
        ).reshape(b, t, h, d)
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)
    out = _flash(fold(q), fold(k), fold(v), causal, bq, bk)
    return jnp.moveaxis(out.reshape(b, h, t, d), 1, 2)
