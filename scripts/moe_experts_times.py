#!/usr/bin/env python3
"""Device time of one expert layer's ``experts`` scope, value and gradient,
by formulation and by kernel: how ``ops/grouped_matmul.py`` came to follow
the tiles in use, to repeat on another chip or shape. Run on the chip (device
time comes from a profiler trace, read with ``benchmarks/trace.py``):

    chiprun -- python3 scripts/moe_experts_times.py

The formulations, each the gated MLP of the held experts over their buffer
and its vector-Jacobian product against a given d out:

``parent``  PR 34's: three grouped matmuls whose grids cover the buffer's
            static worst case (a tile not in use is a grid step that fetches
            nothing and, in the two products, writes a block of zeros), the
            activation between them and its backward left to XLA, which goes
            over every row of the buffer. Its kernels are kept here, for
            this comparison alone;
``grids``   the package's kernels, whose row-tile axis ends at
            ``tiles_used``, one product a call with no epilogue, the
            activation still XLA's;
``tiles``   ``grouped_matmul.grouped_gated_mlp``: the same grids, the
            activation, its backward and the sum of d buf's two terms as
            epilogues of the products.

The cases are the three expert cells' shapes with a router whose busiest
expert takes about twice the mean's pairs (kanana, Keye) or 1.2 times (LFM2;
each line prints its ``load_max_over_mean``), and ``all-held``, a layer that
holds every expert under an even router (the buffer is nearly full, so there
is nothing to skip and an epilogue runs on every tile). One JSON line a case
and formulation: milliseconds of the whole program, of each kernel name with
its calls, and of everything else (XLA's passes); for ``parent`` the grid
steps that multiply nothing, and for the others, where more tiles are empty
than in use, ``empty_step_us``, what such a step cost in ``dtpu_gmm_tn``
(which writes nothing for it): the parent's time there less this one's, over
the parent's empty steps. ``--rehearse`` runs each formulation once anywhere
at a small size, compares it with ``parent`` on the tiles in use and times
nothing. No cell imports this file.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

# name: tokens, top_k, experts, held, hidden, spread of the popularities
CASES = {
    "kanana": (4096, 6, 128, 16, 768, 0.45),
    "keye": (8192, 8, 128, 16, 768, 0.45),
    "lfm2": (8192, 4, 32, 8, 1792, 0.08),
    "all-held": (4096, 6, 16, 16, 768, 0.0),
}
REHEARSAL = {
    "uneven": (64, 3, 16, 4, 256, 0.45),
    "all-held": (64, 3, 4, 4, 256, 0.0),
}


def parent_kernels(jax, jnp, pl, pltpu, gm, interpret):
    """PR 34's ``_gmm_call`` and ``_gmm_tn_call``: static grids over every
    tile of the buffer."""

    def gmm_kernel(tg_ref, used_ref, lhs_ref, rhs_ref, out_ref, *,
                   transpose_rhs):
        i = pl.program_id(1)

        @pl.when(i < used_ref[0])
        def _():
            dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
                ((1,), (0,)), ((), ()))
            out_ref[...] = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[0], dims,
                preferred_element_type=jnp.float32).astype(out_ref.dtype)

        @pl.when(i >= used_ref[0])
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

    def tn_kernel(tg_ref, used_ref, lhs_ref, dout_ref, out_ref, acc_ref, *,
                  num_tiles):
        i = pl.program_id(2)

        @pl.when(i < used_ref[0])
        def _():
            prod = jax.lax.dot_general(
                lhs_ref[...], dout_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            group = tg_ref[i]
            first = jnp.logical_or(
                i == 0, group != tg_ref[jnp.maximum(i - 1, 0)])
            last = jnp.logical_or(
                i == used_ref[0] - 1,
                group != tg_ref[jnp.minimum(i + 1, num_tiles - 1)])

            @pl.when(first)
            def _():
                acc_ref[...] = prod

            @pl.when(jnp.logical_not(first))
            def _():
                acc_ref[...] += prod

            @pl.when(last)
            def _():
                out_ref[0] = acc_ref[...].astype(out_ref.dtype)

    def used_tile(i, used):
        return jnp.minimum(i, used[0] - 1)

    def gmm_call(lhs, rhs, tg, used, *, transpose_rhs):
        m, k = lhs.shape
        n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
        tn = gm._pick_tile(n, max(128, gm._RHS_BLOCK_ELEMENTS // k))
        if transpose_rhs:
            rhs_spec = pl.BlockSpec(
                (1, tn, k), lambda j, i, tg, used: (tg[i], j, 0))
        else:
            rhs_spec = pl.BlockSpec(
                (1, k, tn), lambda j, i, tg, used: (tg[i], 0, j))
        return pl.pallas_call(
            functools.partial(gmm_kernel, transpose_rhs=transpose_rhs),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n // tn, m // gm.TILE_M),
                in_specs=[
                    pl.BlockSpec((gm.TILE_M, k), lambda j, i, tg, used: (
                        used_tile(i, used), 0)),
                    rhs_spec],
                out_specs=pl.BlockSpec((gm.TILE_M, tn),
                                       lambda j, i, tg, used: (i, j))),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            name="dtpu_gmm_nt" if transpose_rhs else "dtpu_gmm",
            interpret=interpret,
        )(tg, used, lhs, rhs)

    def tn_call(lhs, dout, tg, used, groups, dtype):
        m, k = lhs.shape
        n = dout.shape[1]
        tn = gm._pick_tile(n, 1024)
        tk = gm._pick_tile(k, max(128, gm._DRHS_BLOCK_ELEMENTS // tn))
        return pl.pallas_call(
            functools.partial(tn_kernel, num_tiles=m // gm.TILE_M),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(k // tk, n // tn, m // gm.TILE_M),
                in_specs=[
                    pl.BlockSpec((gm.TILE_M, tk), lambda a, b, i, tg, used: (
                        used_tile(i, used), a)),
                    pl.BlockSpec((gm.TILE_M, tn), lambda a, b, i, tg, used: (
                        used_tile(i, used), b))],
                out_specs=pl.BlockSpec(
                    (1, tk, tn), lambda a, b, i, tg, used: (tg[i], a, b)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
            name="dtpu_gmm_tn", interpret=interpret,
        )(tg, used, lhs, dout)

    return gmm_call, tn_call


def one_product(jax, gmm_call, tn_call):
    """A grouped matmul differentiable in lhs and rhs, as PR 34 tied its
    three kernels."""

    @jax.custom_vjp
    def product(lhs, rhs, tg, used):
        return gmm_call(lhs, rhs, tg, used, transpose_rhs=False)

    def fwd(lhs, rhs, tg, used):
        return product(lhs, rhs, tg, used), (lhs, rhs, tg, used)

    def bwd(res, dout):
        lhs, rhs, tg, used = res
        dout = dout.astype(lhs.dtype)
        return (gmm_call(dout, rhs, tg, used, transpose_rhs=True),
                tn_call(lhs, dout, tg, used, rhs.shape[0], rhs.dtype),
                None, None)

    product.defvjp(fwd, bwd)
    return product


def by_products(jax, product):
    """PR 34's ``experts`` scope over one differentiable ``product``."""

    def mlp(buf, w_gate, w_up, w_down, tg, used):
        hidden = jax.nn.silu(product(buf, w_gate, tg, used)) * product(
            buf, w_up, tg, used)
        return product(hidden, w_down, tg, used)

    return mlp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", default="", help="comma-separated; all")
    ap.add_argument("--only", default="", help="formulations, comma-separated")
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "moe_experts_times"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from benchmarks import trace as trace_lib
    from distributed_tpu.ops import grouped_matmul as gm

    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("device times come from a TPU's trace; no TPU here "
                 "(--rehearse runs the formulations once, untimed)")
    interpret = jax.default_backend() != "tpu"
    tile = gm.TILE_M
    forms = {
        "parent": by_products(jax, one_product(jax, *parent_kernels(
            jax, jnp, pl, pltpu, gm, interpret))),
        "grids": by_products(jax, one_product(
            jax,
            lambda lhs, rhs, tg, used, transpose_rhs: gm._gmm_call(
                lhs, rhs, tg, used, transpose_rhs=transpose_rhs,
                tile_m=tile),
            functools.partial(gm._gmm_tn_call, tile_m=tile))),
        "tiles": gm.grouped_gated_mlp,
    }
    cases = REHEARSAL if args.rehearse else CASES
    d, dtype = (128 if args.rehearse else args.width), jnp.dtype(args.dtype)
    for case in (args.cases.split(",") if args.cases else cases):
        n, k, e, held, hidden, spread = cases[case]
        rng = np.random.default_rng(0)
        # Gumbel top-k: k distinct experts a token, by popularity.
        popularity = spread * rng.standard_normal(e)
        idx = np.argsort(-(popularity + rng.gumbel(size=(n, e))),
                         axis=1)[:, :k]
        loads = np.bincount(idx.reshape(-1), minlength=e)
        sizes = loads[:held]
        rows = gm.buffer_rows(n * k, held)
        starts, tg, used = gm.group_layout(jnp.asarray(sizes), rows // tile)
        valid = np.zeros((rows,), bool)
        for s, size in zip(np.asarray(starts), sizes):
            valid[s:s + size] = True
        in_use = int(used[0]) * tile
        draw = lambda shape, scale: jnp.asarray(
            scale * rng.standard_normal(shape), dtype)
        buf = jnp.where(valid[:, None], draw((rows, d), 1.0), 0)
        d_out = jnp.where(valid[:, None], draw((rows, d), 1.0), 0)
        weights = (draw((held, d, hidden), d ** -0.5),
                   draw((held, d, hidden), d ** -0.5),
                   draw((held, hidden, d), hidden ** -0.5))
        empty = rows // tile - int(used[0])
        # The grids of PR 34's nine calls, less their steps on tiles in use.
        blocks = lambda n_, k_: n_ // gm._pick_tile(
            n_, max(128, gm._RHS_BLOCK_ELEMENTS // k_))
        tn_blocks = lambda k_, n_: (n_ // gm._pick_tile(n_, 1024)) * (
            k_ // gm._pick_tile(k_, max(128, gm._DRHS_BLOCK_ELEMENTS
                                        // gm._pick_tile(n_, 1024))))
        empty_steps = {
            "dtpu_gmm": empty * (2 * blocks(hidden, d) + blocks(d, hidden)),
            "dtpu_gmm_nt": empty * (2 * blocks(d, hidden)
                                    + blocks(hidden, d)),
            "dtpu_gmm_tn": empty * (2 * tn_blocks(d, hidden)
                                    + tn_blocks(hidden, d)),
        }
        want, parent_tn_ms = None, None
        for name, form in forms.items():
            if args.only and name not in args.only.split(","):
                continue

            def step(buf, w_gate, w_up, w_down, d_out, form=form):
                out, vjp = jax.vjp(
                    lambda *a: form(*a, tg, used), buf, w_gate, w_up, w_down)
                return out, vjp(d_out)

            step = jax.jit(step)
            got = jax.block_until_ready(step(buf, *weights, d_out))
            line = {"case": case, "formulation": name, "tokens": n,
                    "top_k": k, "held_experts": held, "hidden": hidden,
                    "width": d, "dtype": dtype.name,
                    "load_max_over_mean": round(
                        float(loads.max() / loads.mean()), 3),
                    "held_rows": int(sizes.sum()), "buffer_tiles": rows // tile,
                    "tiles_used": int(used[0])}
            if name == "parent":
                want = got
                line["empty_steps"] = empty_steps
            elif want is not None:
                worst = 0.0
                for a, b in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want)):
                    a, b = (np.asarray(v, np.float32) for v in (a, b))
                    if a.shape[0] == rows:
                        a, b = a[:in_use], b[:in_use]
                    worst = max(worst, float(np.max(np.abs(a - b))))
                line["max_abs_difference_from_parent"] = worst
            if not args.rehearse:
                tdir = os.path.join(args.out, f"{case}_{name}")
                shutil.rmtree(tdir, ignore_errors=True)
                jax.profiler.start_trace(tdir)
                for _ in range(args.steps):
                    out = step(buf, *weights, d_out)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                dev = trace_lib.device(
                    trace_lib.load(trace_lib.find_xplane(tdir)))
                busy = sorted(trace_lib.run_busy_seconds(dev, dev.modules))
                line["ms"] = round(1e3 * busy[len(busy) // 2], 4)
                kernels, calls, other = {}, {}, {}
                for ev in dev.ops:
                    key = trace_lib.kernel_name(ev)
                    ms = 1e3 * ev.seconds / args.steps
                    if key:
                        kernels[key] = kernels.get(key, 0.0) + ms
                        calls[key] = calls.get(key, 0) + 1
                    else:
                        other[ev.op] = other.get(ev.op, 0.0) + ms
                line["kernel_ms"] = {key: round(v, 4)
                                     for key, v in sorted(kernels.items())}
                line["kernel_calls"] = {key: v // args.steps
                                        for key, v in sorted(calls.items())}
                line["kernels_ms"] = round(sum(kernels.values()), 4)
                line["other_ms"] = round(sum(other.values()), 4)
                line["other_top"] = [
                    [key, round(v, 4)] for key, v in sorted(
                        other.items(), key=lambda kv: -kv[1])[:6]]
                tn_ms = kernels.get("dtpu_gmm_tn", 0.0)
                if name == "parent":
                    parent_tn_ms = tn_ms
                elif parent_tn_ms is not None and empty > int(used[0]):
                    line["empty_step_us"] = round(
                        1e3 * (parent_tn_ms - tn_ms)
                        / empty_steps["dtpu_gmm_tn"], 4)
                shutil.rmtree(tdir, ignore_errors=True)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
