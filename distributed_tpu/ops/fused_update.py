"""Fused Adam/AdamW optimizer update as one Pallas pass over flat segments.

The stock optax update walks the parameter TREE: for every leaf it emits
the ~10-op elementwise chain (two moment EMAs, two bias corrections, the
rsqrt-normalized step, the -lr scale; AdamW adds the decay term). On a
model with hundreds of leaves that is hundreds of small kernels per
optimizer step — each paying launch overhead and reading/writing its
operands through HBM separately: the per-step cost this module
removes (on the chip: root PERF.md, ``optimizer_device_ms``).

This module factors the update the other way: the leaves of the master
tree are raveled and concatenated into one flat buffer per dtype (the
"same-dtype segments"), padded to the TPU lane tile, and a SINGLE Pallas
kernel per segment performs the whole Adam recurrence — moment update,
bias correction, and the parameter-step computation — in one pass through
VMEM: every element of g/m/v is read once, every element of m'/v'/delta
written once. The per-leaf views are then sliced back out (XLA fuses the
slices into the consumers). The arithmetic is kept OPERATION-FOR-OPERATION
identical to ``optax.scale_by_adam`` + ``add_decayed_weights`` + ``scale``
so the fused path is bit-comparable to stock optax on the same backend
(tests/test_fused_update.py pins 10-step trajectories under SingleDevice/
DP/ZeRO-1/FSDP).

Optax compatibility: :func:`fused_adam` / :func:`fused_adamw` are ordinary
``GradientTransformation`` factories — ``update`` returns the DELTA tree
and ``optax.apply_updates`` adds it, so they drop into ``Model.compile``,
``Strategy.init_opt_state`` (the ``FusedAdamState`` moments are a plain
pytree, so ZeRO-1/FSDP shard them leaf-for-leaf like stock Adam state) and
``Strategy.constrain_step`` unchanged. The public constructors in
``distributed_tpu.optim`` wrap them in ``optax.inject_hyperparams`` so the
learning rate lives in the state and ``set_learning_rate`` keeps working.

Sharded strategies: GSPMD cannot partition a Pallas custom call, so on a
mesh the kernel computes the segment REPLICATED on every device — which
for a data-parallel optimizer update is the stock placement anyway (every
DP replica computes the full update), and what keeps the step's output
layouts stable: a sharded-kernel constraint here was measured to leak
row-sharding into the updated params under plain DataParallel, whose
constrain_step pins nothing (see _segment_update). Under ZeRO/FSDP the
segment concat gathers the sharded leaves transiently and constrain_step
re-pins the outputs; those strategies get the fused arithmetic, not a
comms win (docs/API.md, Design notes).

CPU/tests run the kernel via Pallas interpret mode (same semantics); on
TPU it compiles to Mosaic.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import pallas as pl

from ._pallas_common import (
    LANES as _LANES,
    interpret as _interpret,
    round_up as _round_up,
)

# Rows (of 128 lanes) per grid block: 256*128 f32 = 128 KiB per operand;
# the kernel holds 5 inputs + 3 outputs + temporaries, comfortably inside
# the ~16 MB VMEM budget.
_BLOCK_ROWS = 256


class FusedAdamState(NamedTuple):
    """State of the fused Adam family: the step count and the first/second
    moment trees. Same content as ``optax.ScaleByAdamState`` — a NamedTuple
    pytree, so it shards/replicates under the usual NamedSharding rules,
    checkpoints leaf-for-leaf, and ``Strategy.constrain_step`` pins it
    exactly like stock Adam state."""

    count: Any
    mu: Any
    nu: Any


def _adam_kernel(hyper_ref, p_ref, g_ref, m_ref, v_ref,
                 u_ref, m_out_ref, v_out_ref):
    """One (block_rows, 128) tile of the fused update. ``hyper`` carries
    the traced scalars [lr, b1, b2, eps, wd, c1, c2, 0] where c1/c2 are
    the bias-correction denominators ``1 - b**count`` (computed outside so
    the count stays a scalar). The arithmetic mirrors optax exactly:

        m' = (1-b1)*g + b1*m            (tree_update_moment, order 1)
        v' = (1-b2)*g^2 + b2*v          (tree_update_moment_per_elem_norm)
        u  = -lr * ((m'/c1) / (sqrt(v'/c2) + eps) + wd*p)

    wd = 0 recovers plain Adam (optax.adam); wd > 0 is AdamW's decoupled
    decay (add_decayed_weights before the -lr scale)."""
    lr = hyper_ref[0, 0]
    b1 = hyper_ref[0, 1]
    b2 = hyper_ref[0, 2]
    eps = hyper_ref[0, 3]
    wd = hyper_ref[0, 4]
    c1 = hyper_ref[0, 5]
    c2 = hyper_ref[0, 6]
    g = g_ref[...]
    m = m_ref[...]
    v = v_ref[...]
    m_new = (1.0 - b1) * g + b1 * m
    v_new = (1.0 - b2) * (g * g) + b2 * v
    m_hat = m_new / c1
    v_hat = v_new / c2
    u = m_hat / (jnp.sqrt(v_hat) + eps)
    u = u + wd * p_ref[...]
    u_ref[...] = (-lr) * u
    m_out_ref[...] = m_new
    v_out_ref[...] = v_new


def _segment_update(hyper, flat_p, flat_g, flat_m, flat_v):
    """Run the fused kernel over one flat (n,) f32 segment, padded to
    whole (block, 128) tiles. Returns (delta, m', v') flat (n,).

    Deliberately NOT routed through shard_map (unlike the fused-xent /
    flash kernels): under a mesh GSPMD replicates the custom call, which
    for the OPTIMIZER is the right placement — data-parallel updates are
    computed replicated on every device by definition (stock optax pays
    the same), and a row-sharding constraint here was measured to LEAK
    through GSPMD propagation into the updated params under plain
    DataParallel (whose constrain_step is the identity), silently turning
    replicated params into row-sharded ones from step 1. ZeRO/FSDP re-pin
    their own layouts in constrain_step; their sharded-update compute is
    a future lever (the segment concat regroups their layouts anyway —
    see the module docstring)."""
    n = flat_p.shape[0]
    rows = _round_up(max(n, 1), _LANES) // _LANES
    bm = min(_BLOCK_ROWS, _round_up(rows, 8))
    rows = _round_up(rows, bm)
    total = rows * _LANES

    def pad2d(a):
        return jnp.pad(a, (0, total - n)).reshape(rows, _LANES)

    p2, g2, m2, v2 = pad2d(flat_p), pad2d(flat_g), pad2d(flat_m), pad2d(flat_v)
    shape = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
    u2, m2n, v2n = pl.pallas_call(
        _adam_kernel,
        grid=(rows // bm,),
        in_specs=[
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
        ] + [pl.BlockSpec((bm, _LANES), lambda i: (i, 0))] * 4,
        out_specs=[pl.BlockSpec((bm, _LANES), lambda i: (i, 0))] * 3,
        out_shape=[shape, shape, shape],
        name="dtpu_fused_adam",
        interpret=_interpret(),
    )(hyper, p2, g2, m2, v2)
    return (
        u2.reshape(-1)[:n],
        m2n.reshape(-1)[:n],
        v2n.reshape(-1)[:n],
    )


def _is_float(leaf) -> bool:
    return jnp.issubdtype(jnp.result_type(leaf), jnp.floating)


def fused_adam(learning_rate, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0):
    """Fused-kernel Adam/AdamW as an optax ``GradientTransformation``.

    Use through ``distributed_tpu.optim.fused_adam(...)`` (which adds the
    ``inject_hyperparams`` wrapper so the learning rate is runtime-mutable
    and checkpointable); this factory is the raw transform. ``update``
    returns the parameter DELTAS (optax contract — ``apply_updates`` adds
    them, and XLA fuses that add into the surrounding jitted step), with
    the moment update + bias correction + step computation performed by
    one Pallas kernel per same-dtype flat segment of the tree.

    Non-float32 floating leaves are updated in f32 inside the kernel and
    cast back (the framework's masters are f32, where the path is exact
    vs stock optax); integer leaves pass through with zero updates."""

    def init_fn(params):
        def zeros(p):
            return jnp.zeros_like(p)

        return FusedAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(zeros, params),
            nu=jax.tree_util.tree_map(zeros, params),
        )

    def update_fn(grads, state, params=None):
        if params is None:
            # The params are a kernel operand (AdamW's decay term); plain
            # Adam (wd == 0) gets a zeros stand-in so callers following
            # the optax "params optional" convention still work.
            params = jax.tree_util.tree_map(jnp.zeros_like, grads)
        count_inc = optax.safe_int32_increment(state.count)
        b1_ = jnp.asarray(b1, jnp.float32)
        b2_ = jnp.asarray(b2, jnp.float32)
        c1 = 1.0 - b1_ ** count_inc
        c2 = 1.0 - b2_ ** count_inc
        hyper = jnp.stack([
            jnp.asarray(learning_rate, jnp.float32),
            b1_, b2_,
            jnp.asarray(eps, jnp.float32),
            jnp.asarray(weight_decay, jnp.float32),
            c1, c2,
            jnp.float32(0.0),
        ]).reshape(1, 8)

        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = treedef.flatten_up_to(params)
        m_leaves = treedef.flatten_up_to(state.mu)
        v_leaves = treedef.flatten_up_to(state.nu)

        # Same-dtype segments: group the floating leaves by dtype so each
        # group concatenates into ONE flat buffer and pays one kernel.
        groups: dict = {}
        for i, g in enumerate(g_leaves):
            if _is_float(g):
                groups.setdefault(jnp.result_type(g), []).append(i)

        u_leaves = [None] * len(g_leaves)
        new_m = list(m_leaves)
        new_v = list(v_leaves)
        for dt, idxs in groups.items():
            sizes = [int(np.prod(g_leaves[i].shape)) for i in idxs]
            offs = np.concatenate([[0], np.cumsum(sizes)]).tolist()

            def flat(leaves):
                return jnp.concatenate([
                    leaves[i].reshape(-1).astype(jnp.float32) for i in idxs
                ]) if idxs else jnp.zeros((0,), jnp.float32)

            du, dm, dv = _segment_update(
                hyper, flat(p_leaves), flat(g_leaves), flat(m_leaves),
                flat(v_leaves),
            )
            for k, i in enumerate(idxs):
                sl = slice(offs[k], offs[k + 1])
                shape = g_leaves[i].shape
                u_leaves[i] = du[sl].reshape(shape).astype(dt)
                new_m[i] = dm[sl].reshape(shape).astype(dt)
                new_v[i] = dv[sl].reshape(shape).astype(dt)
        for i, g in enumerate(g_leaves):
            if u_leaves[i] is None:  # integer leaf: no update
                u_leaves[i] = jnp.zeros_like(g)

        updates = jax.tree_util.tree_unflatten(treedef, u_leaves)
        new_state = FusedAdamState(
            count=count_inc,
            mu=jax.tree_util.tree_unflatten(treedef, new_m),
            nu=jax.tree_util.tree_unflatten(treedef, new_v),
        )
        return updates, new_state

    return optax.GradientTransformation(init_fn, update_fn)


def fused_adamw(learning_rate, b1=0.9, b2=0.999, eps=1e-8,
                weight_decay=0.01):
    """AdamW spelling of :func:`fused_adam` (decoupled weight decay folded
    into the same single kernel pass)."""
    return fused_adam(
        learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay
    )


__all__ = ["FusedAdamState", "fused_adam", "fused_adamw"]
