"""Pallas TPU kernels for hot ops.

Currently: fused softmax cross-entropy (forward + backward via custom_vjp).
XLA already fuses the elementwise chain of ``log_softmax + gather`` well at
small class counts, but at large-vocabulary scale (LM heads; fused up to
``MAX_FUSED_CLASSES`` = 64k classes, stock-XLA fallback beyond) the fused
kernel avoids materializing the (N, C) log-probability tensor in HBM: each
block computes max/sum/pick in VMEM and writes only the (N,) losses — HBM
traffic drops from ~3x logits-size to ~1x. The backward
kernel recomputes the softmax from the saved logits (flash-style
rematerialization) instead of storing probabilities.

The reference has nothing comparable in-repo (its compute lives in TF's C++
kernels, SURVEY.md §2b); this is the TPU-native answer for the op tier.

CPU/tests run the same kernels via Pallas interpret mode; on TPU they
compile to Mosaic. Kernels are opt-in: compile with
``loss="pallas_sparse_categorical_crossentropy"`` (registered lazily in
``ops.losses``). Under data parallelism the batch dimension is the grid
dimension, so blocks never span replicas.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas_common import interpret as _interpret, round_up as _round_up


# --------------------------------------------------------------- kernels --
def _xent_fwd_kernel(logits_ref, labels_ref, loss_ref):
    x = logits_ref[...].astype(jnp.float32)          # (bm, c_pad)
    lbl = labels_ref[...][:, 0]                      # (bm,)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    lse = jnp.log(jnp.sum(e, axis=-1)) + m[:, 0]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(col == lbl[:, None], x, 0.0), axis=-1)
    loss_ref[...] = (lse - picked)[:, None]


def _xent_bwd_kernel(logits_ref, labels_ref, g_ref, dlogits_ref):
    x = logits_ref[...].astype(jnp.float32)
    lbl = labels_ref[...][:, 0]
    g = g_ref[...][:, 0]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (col == lbl[:, None]).astype(jnp.float32)
    dlogits_ref[...] = ((p - onehot) * g[:, None]).astype(dlogits_ref.dtype)


# --------------------------------------------------------------- wrappers --
# Column padding: exp(NEG - max) == 0, never the row max (shared constant).
from ._pallas_common import NEG as _NEG  # noqa: E402


def _pad_inputs(logits, labels, bm):
    n, c = logits.shape
    n_pad = _round_up(n, bm)
    c_pad = _round_up(max(c, 128), 128)  # TPU lane tile
    lp = jnp.pad(
        logits, ((0, n_pad - n), (0, c_pad - c)), constant_values=_NEG
    )
    yp = jnp.pad(labels.astype(jnp.int32), (0, n_pad - n))[:, None]
    return lp, yp, n_pad, c_pad


def _block_rows(n: int, c_pad: int) -> int:
    # VMEM is ~16MB and the backward kernel holds ~6 block-sized float32
    # temporaries (logits, exp, softmax, onehot, grad-out, spill), so cap
    # the block's logits at 2MB: 6 x 2MB stays under the scoped-vmem limit.
    for bm in (256, 128, 64, 32, 16, 8):
        if bm * c_pad * 4 <= (1 << 21):
            return bm
    return 8


def _check_classes(c: int):
    if c > MAX_FUSED_CLASSES:
        raise ValueError(
            f"fused_softmax_xent supports at most {MAX_FUSED_CLASSES} "
            f"classes (got {c}): a row block would not fit VMEM. Use "
            "losses.sparse_categorical_crossentropy (the registry-level "
            "pallas loss falls back automatically)."
        )


def _xent_forward(logits, labels):
    n, c = logits.shape
    _check_classes(c)
    lp, yp, n_pad, c_pad = _pad_inputs(logits, labels, 8)
    bm = _block_rows(n_pad, c_pad)
    if n_pad % bm:
        bm = 8  # n_pad is a multiple of 8 by construction
    loss = pl.pallas_call(
        _xent_fwd_kernel,
        grid=(n_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        name="dtpu_xent_fwd",
        interpret=_interpret(),
    )(lp, yp)
    return loss[:n, 0]


def _xent_backward(logits, labels, g):
    n, c = logits.shape
    lp, yp, n_pad, c_pad = _pad_inputs(logits, labels, 8)
    bm = _block_rows(n_pad, c_pad)
    if n_pad % bm:
        bm = 8
    gp = jnp.pad(g.astype(jnp.float32), (0, n_pad - n))[:, None]
    dl = pl.pallas_call(
        _xent_bwd_kernel,
        grid=(n_pad // bm,),
        in_specs=[
            pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, c_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, c_pad), logits.dtype),
        name="dtpu_xent_bwd",
        interpret=_interpret(),
    )(lp, yp, gp)
    return dl[:n, :c]


# Ceiling for the fused path: the kernel blocks over rows only, so a single
# row's padded class dim must fit the minimum 8-row block within the VMEM
# budget (8 * 65536 * 4B = 2MB of logits; ~12MB with backward temporaries,
# against ~16MB VMEM). Beyond it the registry wrappers fall back to the
# stock XLA loss rather than fail Mosaic compilation.
MAX_FUSED_CLASSES = 65536


@jax.custom_vjp
def fused_softmax_xent(logits, labels):
    """Per-example cross-entropy from logits: (N, C), (N,) -> (N,) float32.

    Equivalent to ``-log_softmax(logits)[labels]`` but computed blockwise in
    VMEM without materializing log-probabilities in HBM. C must be at most
    ``MAX_FUSED_CLASSES``; the registry-level loss falls back automatically.
    """
    return _xent_forward(logits, labels)


def _vjp_fwd(logits, labels):
    return _xent_forward(logits, labels), (logits, labels)


def _vjp_bwd(res, g):
    logits, labels = res
    return _xent_backward(logits, labels, g), None


fused_softmax_xent.defvjp(_vjp_fwd, _vjp_bwd)


def loss_path(c: int) -> str:
    """Which implementation the registry-level pallas loss runs for a
    ``c``-class head: ``"fused"`` (the Pallas kernel) or ``"stock"`` (the
    XLA loss, above ``MAX_FUSED_CLASSES``)."""
    return "fused" if c <= MAX_FUSED_CLASSES else "stock"


def _stock_fallback(c: int) -> bool:
    if loss_path(c) == "fused":
        return False
    from ..utils import logging as dlog

    # Trace-time, so once per compiled program — never once per process:
    # every program that swaps losses says so.
    dlog.warning(
        f"pallas loss: {c} classes exceeds the fused ceiling "
        f"({MAX_FUSED_CLASSES}); this program uses the stock XLA loss"
    )
    return True


def _sharded_fused_xent(flat_logits, flat_labels):
    """fused_softmax_xent per-shard under the ambient mesh: GSPMD cannot
    partition a Pallas custom call (it would all-gather the logits and run
    the global problem on every device), so batch-sharded rows go through
    shard_map (parallel.auto_shard). Plain call off-mesh."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.auto_shard import ambient_mesh, shard_rows

    mesh, batch_axis, _ = ambient_mesh()
    if mesh is None or batch_axis is None:
        return fused_softmax_xent(flat_logits, flat_labels)
    return shard_rows(
        fused_softmax_xent,
        (flat_logits, flat_labels),
        (P(batch_axis, None), P(batch_axis)),
        P(batch_axis),
    )


def pallas_sparse_categorical_crossentropy(logits, labels):
    """Mean fused cross-entropy — drop-in for the stock loss via
    ``compile(loss="pallas_sparse_categorical_crossentropy")``.

    Leading batch dims are flattened ((B, T, C) token losses included).
    Class counts beyond ``MAX_FUSED_CLASSES`` fall back to the stock loss.
    """
    c = logits.shape[-1]
    if _stock_fallback(c):
        from . import losses

        return losses.sparse_categorical_crossentropy(logits, labels)
    flat = logits.reshape(-1, c)
    return jnp.mean(_sharded_fused_xent(flat, labels.reshape(-1)))


def per_example_pallas_xent(logits, labels):
    c = logits.shape[-1]
    if _stock_fallback(c):
        from . import losses

        return losses._per_example_sparse_cce(logits, labels)
    out = _sharded_fused_xent(logits.reshape(-1, c), labels.reshape(-1))
    return out.reshape(labels.shape)
