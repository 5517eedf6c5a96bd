"""Ring attention: exact attention over a sequence-sharded mesh axis.

Long-context sequence parallelism (absent from the reference, which has no
sequence dimension at all — SURVEY.md §5 "long-context"): Q/K/V stay sharded
on the sequence dimension across the 'seq' mesh axis; K/V blocks rotate
around the ring with ``lax.ppermute`` while each device folds every block
into a running (max, denominator, accumulator) — the online-softmax
recurrence of FlashAttention, distributed. No device ever materializes the
full (T, T) score matrix or an all-gathered K/V: per-device memory is
O(T/n), and on a TPU torus the ppermute is a neighbor hop over ICI that
overlaps with the block matmuls.

Exactness: the result equals dense softmax attention up to float
associativity — verified against the dense path in tests on the 8-device
sim. Causal masking uses global positions, so the blockwise result is
identical to masking the full matrix.

Causal schedule: the naive ring folds every rotated block on every device,
so with causal masking ~half the (device, block) pairs are fully masked —
wasted FLOPs, and imbalanced (the last shard does n live folds, the first
does 1). The default causal path therefore uses the ZIGZAG (striped)
schedule: the sequence is viewed as 2n half-chunks and each device is
re-sharded (boundary ppermutes) to hold chunks (i, 2n-1-i) — one early, one
late. Then every rotated hop has EXACTLY two live chunk-pairs per device,
fully unmasked ((q_hi, k_lo) always; (q_lo, k_lo) when my > src else
(q_hi, k_hi)), and only the resident hop applies triangular masks — ~half
the matmul FLOPs of the naive schedule, perfectly load-balanced, same
O(T/n) memory and ring traffic (docs/PERF_ROUNDS_1-5.md, round 4).
``schedule="naive"`` keeps the old path for reference/debugging.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec


def _online_fold(m, l, acc, qf, kc, vc, scale, mask):
    """One block fold of the distributed online-softmax recurrence.

    Shared by both causal schedules — the numerically delicate guard chain
    (rows with no live key yet have m == -inf; exp(-inf - -inf) would be
    NaN) lives exactly once. ``mask=None`` means the block is fully live.
    """
    s = (
        jnp.einsum(
            "bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    safe = jnp.isfinite(m_new)
    m_ref = jnp.where(safe, m_new, 0.0)
    alpha = jnp.where(safe, jnp.exp(m - m_ref), 0.0)
    p = jnp.exp(s - m_ref[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = l * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def ring_attention(
    q,
    k,
    v,
    *,
    mesh: Mesh,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = None,
    causal: bool = False,
    schedule: str = "auto",
):
    """Attention over (B, T, H, D) tensors whose T dim is sharded on
    ``seq_axis`` (and optionally B on ``batch_axis``). Returns (B, T, H, D)
    with the same sharding.

    ``schedule``: "auto" (zigzag for causal when the shard splits in half,
    else naive), "zigzag", or "naive" — see the module docstring.
    """
    n = int(mesh.shape[seq_axis])
    if q.shape[1] % n:
        raise ValueError(
            f"sequence length {q.shape[1]} not divisible by "
            f"{seq_axis}={n} shards"
        )
    if schedule not in ("auto", "zigzag", "naive"):
        raise ValueError(f"unknown schedule {schedule!r}")
    tb = q.shape[1] // n
    use_zigzag = causal and n > 1 and tb % 2 == 0
    if schedule == "zigzag" and not use_zigzag:
        raise ValueError(
            "schedule='zigzag' needs causal=True, >1 seq shard, and an "
            f"even per-shard length (got causal={causal}, shards={n}, "
            f"per-shard={tb})"
        )
    if use_zigzag and schedule != "naive":
        return _ring_attention_zigzag(
            q, k, v, mesh=mesh, seq_axis=seq_axis, batch_axis=batch_axis
        )
    spec = PartitionSpec(batch_axis, seq_axis, None, None)

    def local_fn(ql, kl, vl):
        # ql/kl/vl: (B, Tb, H, D) — this device's block.
        b, tb, h, d = ql.shape
        my = lax.axis_index(seq_axis)
        scale = 1.0 / jnp.sqrt(jnp.float32(d))
        qf = ql.astype(jnp.float32)
        q_pos = my * tb + jnp.arange(tb)

        m0 = jnp.full((b, h, tb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, tb), jnp.float32)
        acc0 = jnp.zeros((b, h, tb, d), jnp.float32)
        perm = [(j, (j + 1) % n) for j in range(n)]

        def fold(m, l, acc, kc, vc, i):
            """Fold one K/V block into the online-softmax accumulators.
            After i rotations each device holds the block that started on
            device (my - i) mod n."""
            src = (my - i) % n
            mask = None
            if causal:
                k_pos = src * tb + jnp.arange(tb)
                mask = q_pos[:, None] >= k_pos[None, :]  # (Tb_q, Tb_k)
            return _online_fold(m, l, acc, qf, kc, vc, scale, mask)

        # Fold the resident block, then scan n-1 rotate-and-fold steps (the
        # rotation leads the fold so no final rotation is wasted — XLA can't
        # DCE a collective inside a loop). lax.scan, not fori_loop: the ring
        # must be reverse-mode differentiable for training.
        m, l, acc = fold(m0, l0, acc0, kl, vl, 0)

        def body(carry, i):
            m, l, acc, kc, vc = carry
            kc = lax.ppermute(kc, seq_axis, perm)
            vc = lax.ppermute(vc, seq_axis, perm)
            m, l, acc = fold(m, l, acc, kc, vc, i)
            return (m, l, acc, kc, vc), None

        (m, l, acc, _, _), _ = lax.scan(
            body, (m, l, acc, kl, vl), jnp.arange(1, n)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, H, Tb, D)
        return jnp.transpose(out, (0, 2, 1, 3)).astype(ql.dtype)

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _zigzag_perms(n: int):
    """Boundary permutations between contiguous and zigzag chunk layouts.

    The sequence is 2n half-chunks; contiguous device s holds (2s, 2s+1),
    zigzag device i holds (i, 2n-1-i). Chunk c's zigzag home is device c
    (lo slot) when c < n, else device 2n-1-c (hi slot). Each returned perm
    is a (source, dest) list for one (source slot -> dest slot) ppermute;
    unlisted destinations receive zeros, so slot contents sum cleanly.
    """
    lo_from_lo = [(s, 2 * s) for s in range(n) if 2 * s < n]
    lo_from_hi = [(s, 2 * s + 1) for s in range(n) if 2 * s + 1 < n]
    hi_from_lo = [(s, 2 * n - 1 - 2 * s) for s in range(n) if 2 * s >= n]
    hi_from_hi = [(s, 2 * n - 2 - 2 * s) for s in range(n) if 2 * s + 1 >= n]
    # Inverse: contiguous device d's lo = chunk 2d, hi = chunk 2d+1.
    inv_lo_from_lo = [(2 * d, d) for d in range(n) if 2 * d < n]
    inv_lo_from_hi = [(2 * n - 1 - 2 * d, d) for d in range(n) if 2 * d >= n]
    inv_hi_from_lo = [(2 * d + 1, d) for d in range(n) if 2 * d + 1 < n]
    inv_hi_from_hi = [
        (2 * n - 2 - 2 * d, d) for d in range(n) if 2 * d + 1 >= n
    ]
    return (
        (lo_from_lo, lo_from_hi, hi_from_lo, hi_from_hi),
        (inv_lo_from_lo, inv_lo_from_hi, inv_hi_from_lo, inv_hi_from_hi),
    )


def _ring_attention_zigzag(q, k, v, *, mesh, seq_axis, batch_axis):
    """Causal ring attention on the balanced zigzag schedule (module doc)."""
    n = int(mesh.shape[seq_axis])
    spec = PartitionSpec(batch_axis, seq_axis, None, None)
    fwd_perms, inv_perms = _zigzag_perms(n)

    def local_fn(ql, kl, vl):
        b, tb, h, d = ql.shape
        c = tb // 2
        my = lax.axis_index(seq_axis)
        scale = 1.0 / jnp.sqrt(jnp.float32(d))

        def to_zigzag(x):
            lo, hi = x[:, :c], x[:, c:]
            p_ll, p_lh, p_hl, p_hh = fwd_perms
            new_lo = lax.ppermute(lo, seq_axis, p_ll) + lax.ppermute(
                hi, seq_axis, p_lh
            )
            new_hi = lax.ppermute(lo, seq_axis, p_hl) + lax.ppermute(
                hi, seq_axis, p_hh
            )
            return new_lo, new_hi

        def from_zigzag(lo, hi):
            p_ll, p_lh, p_hl, p_hh = inv_perms
            orig_lo = lax.ppermute(lo, seq_axis, p_ll) + lax.ppermute(
                hi, seq_axis, p_lh
            )
            orig_hi = lax.ppermute(lo, seq_axis, p_hl) + lax.ppermute(
                hi, seq_axis, p_hh
            )
            return jnp.concatenate([orig_lo, orig_hi], axis=1)

        q_lo, q_hi = to_zigzag(ql)
        k_lo, k_hi = to_zigzag(kl)
        v_lo, v_hi = to_zigzag(vl)
        qf_lo = q_lo.astype(jnp.float32)
        qf_hi = q_hi.astype(jnp.float32)

        def fold(m, l, acc, qf, kc, vc, mask):
            # mask=None means fully live (the zigzag invariant for every
            # rotated hop); numerics live in the shared _online_fold.
            return _online_fold(m, l, acc, qf, kc, vc, scale, mask)

        zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
        m_lo = jnp.full((b, h, c), -jnp.inf, jnp.float32)
        m_hi = jnp.full((b, h, c), -jnp.inf, jnp.float32)
        l_lo, l_hi = zeros(b, h, c), zeros(b, h, c)
        acc_lo, acc_hi = zeros(b, h, c, d), zeros(b, h, c, d)

        # Resident hop (src == my): the only hop with masked (triangular)
        # pairs — (q_lo, k_lo) and (q_hi, k_hi) are diagonal chunks,
        # (q_hi, k_lo) is fully live, (q_lo, k_hi) is fully dead.
        tri = jnp.tril(jnp.ones((c, c), bool))
        m_lo, l_lo, acc_lo = fold(m_lo, l_lo, acc_lo, qf_lo, k_lo, v_lo, tri)
        m_hi, l_hi, acc_hi = fold(m_hi, l_hi, acc_hi, qf_hi, k_lo, v_lo,
                                  None)
        m_hi, l_hi, acc_hi = fold(m_hi, l_hi, acc_hi, qf_hi, k_hi, v_hi, tri)

        perm = [(j, (j + 1) % n) for j in range(n)]

        def body(carry, j):
            m_lo, l_lo, acc_lo, m_hi, l_hi, acc_hi, klo, khi, vlo, vhi = carry
            klo = lax.ppermute(klo, seq_axis, perm)
            khi = lax.ppermute(khi, seq_axis, perm)
            vlo = lax.ppermute(vlo, seq_axis, perm)
            vhi = lax.ppermute(vhi, seq_axis, perm)
            src = (my - j) % n
            # Always live: this device's late chunk vs src's early chunk.
            m_hi, l_hi, acc_hi = fold(m_hi, l_hi, acc_hi, qf_hi, klo, vlo,
                                      None)
            # Second live pair depends on ring position: my > src pairs the
            # early q chunk with src's early k chunk; my < src pairs the
            # late q chunk with src's late k chunk. Same shapes, so one
            # predicated fold covers both (src == my impossible here).
            pred = my > src
            q_sel = jnp.where(pred, qf_lo, qf_hi)
            k_sel = jnp.where(pred, klo, khi)
            v_sel = jnp.where(pred, vlo, vhi)
            m_sel = jnp.where(pred, m_lo, m_hi)
            l_sel = jnp.where(pred, l_lo, l_hi)
            acc_sel = jnp.where(pred, acc_lo, acc_hi)
            m2, l2, acc2 = fold(m_sel, l_sel, acc_sel, q_sel, k_sel, v_sel,
                                None)
            m_lo = jnp.where(pred, m2, m_lo)
            l_lo = jnp.where(pred, l2, l_lo)
            acc_lo = jnp.where(pred, acc2, acc_lo)
            m_hi = jnp.where(pred, m_hi, m2)
            l_hi = jnp.where(pred, l_hi, l2)
            acc_hi = jnp.where(pred, acc_hi, acc2)
            return (m_lo, l_lo, acc_lo, m_hi, l_hi, acc_hi,
                    klo, khi, vlo, vhi), None

        carry = (m_lo, l_lo, acc_lo, m_hi, l_hi, acc_hi,
                 k_lo, k_hi, v_lo, v_hi)
        carry, _ = lax.scan(body, carry, jnp.arange(1, n))
        m_lo, l_lo, acc_lo, m_hi, l_hi, acc_hi = carry[:6]

        def finish(acc, l):
            out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, H, c, D)
            return jnp.transpose(out, (0, 2, 1, 3)).astype(ql.dtype)

        return from_zigzag(finish(acc_lo, l_lo), finish(acc_hi, l_hi))

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
