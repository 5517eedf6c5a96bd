#!/usr/bin/env python3
"""Device time of one expert layer's five row movements, by formulation: how
``ops/moe_rows.py`` was chosen, to repeat on another chip or shape. Run on
the chip (device time comes from a profiler trace, read with
``benchmarks/trace.py``):

    chiprun -- python3 scripts/moe_route_times.py --tokens 4096 --top-k 6 \
        --held 16,128 --experts 128 --width 2048

The movements (``nn/moe.py``): dispatch forward (token rows into the
experts' buffer), combine forward (buffer rows back, weighted, summed over a
token's choices), combine backward (dy rows into the buffer times the row's
gate, and each row's dot with dy for the gate's gradient) and dispatch
backward (buffer rows summed over a token's choices). The formulations:

``take``    the parent's: ``jnp.take`` over the buffer's static worst case
            (24,576 or 26,624 rows in the cell), combine backward gathering
            the buffer twice;
``walk``    ``ops/moe_rows.py``'s Pallas kernels over the tiles in use;
``loop``    row-major movements as a ``fori_loop`` to ``tiles_used`` of
            128-row XLA gathers and ``dynamic_update_slice``;
``onehot``  every movement as one one-hot (128 x n) product a tile in use.

The router draws each token's experts without replacement from popularities
whose busiest expert takes about three times the mean (the cell's
``moe_load_max_over_mean``); ``--held`` lists how many of the experts the
layer holds (all of them: every pair is held and the buffer is full). One
JSON line a formulation and held count: milliseconds a movement and the GB/s
of the held rows moved (read and written once). ``--rehearse`` runs each
formulation once anywhere, compares it with ``take`` and times nothing. No
cell imports this file.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

MOVEMENTS = ("dispatch_fwd", "combine_fwd", "combine_bwd", "dispatch_bwd")


def layout(jnp, gmm, moe_rows, idx, held_experts):
    """``DroplessMoE.apply``'s sort, for experts ``idx`` (n, k)."""
    n, k = idx.shape
    g = held_experts
    local = idx.T
    held = local < g
    group = jnp.where(held, local, g).reshape(-1)
    onehot = (group[:, None] == jnp.arange(g)[None]).astype(jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    sizes = jnp.sum(onehot, axis=0)
    rows = gmm.buffer_rows(n * k, g)
    row_starts, tile_group, tiles_used = gmm.group_layout(
        sizes, rows // gmm.TILE_M)
    start = jnp.take(row_starts, jnp.minimum(group, g - 1))
    dest = jnp.where(held.reshape(-1), start + rank, rows)
    src_pair = jnp.full((rows,), n * k, jnp.int32).at[dest].set(
        jnp.arange(n * k, dtype=jnp.int32), mode="drop")
    row_valid = src_pair < n * k
    return dict(
        held=held, dest=jnp.where(held, dest.reshape(k, n), 0),
        row_pair=src_pair, row_valid=row_valid,
        src_pair=jnp.minimum(src_pair, n * k - 1),
        tiles_used=tiles_used, held_rows=int(jnp.sum(sizes)), rows=rows,
        tile_rows=moe_rows.tile_rows(sizes, row_starts, tile_group,
                                     tiles_used))


def formulations(jax, jnp, moe_rows, lay, n, tile):
    f32 = jnp.float32
    dest, held, valid = lay["dest"], lay["held"], lay["row_valid"]
    token = lay["src_pair"] % n
    walk = (lay["row_pair"], lay["tile_rows"], lay["tiles_used"])
    used = lay["tiles_used"]
    k = dest.shape[0]

    def pairs(buf):
        return jnp.take(buf, dest.reshape(-1), axis=0, mode="clip").reshape(
            k, n, buf.shape[-1])

    take = {
        "dispatch_fwd": lambda flat, buf, gate: jnp.where(
            valid[:, None], jnp.take(flat, token, axis=0, mode="clip"),
            jnp.zeros((), flat.dtype)),
        "combine_fwd": lambda flat, buf, gate: jnp.sum(
            gate[:, :, None] * pairs(buf).astype(f32), axis=0).astype(
                buf.dtype),
        "combine_bwd": lambda flat, buf, gate: (
            (jnp.take(flat, token, axis=0, mode="clip").astype(f32)
             * jnp.where(valid, gate.reshape(-1)[lay["src_pair"]], 0.0)[
                 :, None]).astype(buf.dtype),
            jnp.sum(pairs(buf).astype(f32) * flat.astype(f32)[None],
                    axis=-1)),
        "dispatch_bwd": lambda flat, buf, gate: jnp.sum(jnp.where(
            held[:, :, None], pairs(buf).astype(f32), 0.0), axis=0).astype(
                buf.dtype),
    }

    def row_gate(gate):
        return jnp.where(valid, gate.reshape(-1)[lay["src_pair"]], 0.0)

    walk_forms = {
        "dispatch_fwd": lambda flat, buf, gate: moe_rows.gather_rows(
            flat, *walk),
        "combine_fwd": lambda flat, buf, gate: moe_rows.sum_rows(
            buf, *walk, n, pair_scale=gate),
        "combine_bwd": lambda flat, buf, gate: moe_rows.gather_rows(
            flat, *walk, pair_scale=gate, dot_with=buf),
        "dispatch_bwd": lambda flat, buf, gate: moe_rows.sum_rows(
            buf, *walk, n),
    }

    def loop_rows(flat, scale=None):
        m = token.shape[0]

        def body(t, out):
            at = t * tile
            rows = jnp.take(flat, jax.lax.dynamic_slice(token, (at,), (tile,)),
                            axis=0, mode="clip")
            ok = jax.lax.dynamic_slice(valid, (at,), (tile,))
            rows = jnp.where(ok[:, None], rows, jnp.zeros((), flat.dtype))
            if scale is not None:
                rows = (rows.astype(f32) * jax.lax.dynamic_slice(
                    scale, (at,), (tile,))[:, None]).astype(flat.dtype)
            return jax.lax.dynamic_update_slice(out, rows, (at, 0))

        return jax.lax.fori_loop(
            0, used[0], body, jnp.zeros((m, flat.shape[1]), flat.dtype))

    loop = {
        "dispatch_fwd": lambda flat, buf, gate: loop_rows(flat),
        "combine_bwd": lambda flat, buf, gate: loop_rows(
            flat, row_gate(gate)),  # d_out alone: the dots stay a gather
    }

    def onehot_rows(flat):
        m = token.shape[0]

        def body(t, out):
            at = t * tile
            tok = jax.lax.dynamic_slice(token, (at,), (tile,))
            ok = jax.lax.dynamic_slice(valid, (at,), (tile,))
            pick = jnp.logical_and(
                tok[:, None] == jnp.arange(n)[None], ok[:, None])
            rows = jnp.dot(pick.astype(flat.dtype), flat,
                           preferred_element_type=f32).astype(flat.dtype)
            return jax.lax.dynamic_update_slice(out, rows, (at, 0))

        return jax.lax.fori_loop(
            0, used[0], body, jnp.zeros((m, flat.shape[1]), flat.dtype))

    def onehot_sum(buf):
        # The gates would ride in the one-hot in f32 (three bf16 passes);
        # timed here without them, as dispatch backward.
        def body(t, y):
            at = t * tile
            tok = jax.lax.dynamic_slice(token, (at,), (tile,))
            ok = jax.lax.dynamic_slice(valid, (at,), (tile,))
            pick = jnp.logical_and(
                tok[None, :] == jnp.arange(n)[:, None], ok[None, :])
            rows = jax.lax.dynamic_slice(buf, (at, 0), (tile, buf.shape[1]))
            return y + jnp.dot(pick.astype(buf.dtype), rows,
                               preferred_element_type=f32)

        return jax.lax.fori_loop(
            0, used[0], body, jnp.zeros((n, buf.shape[1]), f32)).astype(
                buf.dtype)

    onehot = {
        "dispatch_fwd": lambda flat, buf, gate: onehot_rows(flat),
        "dispatch_bwd": lambda flat, buf, gate: onehot_sum(buf),
    }
    return {"take": take, "walk": walk_forms, "loop": loop,
            "onehot": onehot}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--top-k", type=int, default=6)
    ap.add_argument("--held", default="16,128")
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--only", default="", help="formulations, comma-separated")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "moe_route_times"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import trace as trace_lib
    from distributed_tpu.ops import grouped_matmul as gmm, moe_rows

    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("device times come from a TPU's trace; no TPU here "
                 "(--rehearse runs the formulations once, untimed)")
    n, k, e, d = args.tokens, args.top_k, args.experts, args.width
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(0)
    # Gumbel top-k: k distinct experts a token, by popularity.
    popularity = 0.45 * rng.standard_normal(e)
    idx = np.argsort(-(popularity + rng.gumbel(size=(n, e))), axis=1)[:, :k]
    loads = np.bincount(idx.reshape(-1), minlength=e)
    flat = jnp.asarray(rng.standard_normal((n, d)), dtype)
    gate = jnp.asarray(rng.uniform(0.1, 1.0, (k, n)), jnp.float32)

    for held_experts in (int(x) for x in args.held.split(",")):
        lay = layout(jnp, gmm, moe_rows, jnp.asarray(idx, jnp.int32),
                     held_experts)
        buf = jnp.where(lay["row_valid"][:, None], jnp.asarray(
            rng.standard_normal((lay["rows"], d)), dtype), 0)
        gates = jnp.where(lay["held"], gate, 0.0)
        forms = formulations(jax, jnp, moe_rows, lay, n, gmm.TILE_M)
        used_rows = int(lay["tiles_used"][0]) * gmm.TILE_M
        moved = 2.0 * lay["held_rows"] * d * dtype.itemsize
        want = None
        for name, form in forms.items():
            if args.only and name not in args.only.split(","):
                continue
            line = {"formulation": name, "held_experts": held_experts,
                    "tokens": n, "top_k": k, "width": d, "dtype": dtype.name,
                    "load_max_over_mean": round(
                        float(loads.max() / loads.mean()), 3),
                    "held_rows": lay["held_rows"], "buffer_rows": lay["rows"],
                    "tiles_used": used_rows // gmm.TILE_M}
            got = {}
            for movement in MOVEMENTS:
                if movement not in form:
                    continue
                step = jax.jit(form[movement])
                got[movement] = jax.block_until_ready(
                    step(flat, buf, gates))
                if args.rehearse:
                    continue
                tdir = os.path.join(args.out, f"{name}_{movement}")
                shutil.rmtree(tdir, ignore_errors=True)
                jax.profiler.start_trace(tdir)
                for _ in range(args.steps):
                    out = step(flat, buf, gates)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                dev = trace_lib.device(
                    trace_lib.load(trace_lib.find_xplane(tdir)))
                busy = sorted(trace_lib.run_busy_seconds(dev, dev.modules))
                ms = 1e3 * busy[len(busy) // 2]
                line[movement + "_ms"] = round(ms, 4)
                line[movement + "_held_GBps"] = round(moved / ms / 1e6, 1)
                shutil.rmtree(tdir, ignore_errors=True)
            if name == "take":
                want = got
            elif want is not None:
                # Rows of tiles in use (a walk leaves the others alone);
                # gate gradients where a pair is held.
                worst = 0.0
                for movement, value in got.items():
                    pairs = zip(jax.tree_util.tree_leaves(value),
                                jax.tree_util.tree_leaves(want[movement]))
                    for a, b in pairs:
                        a, b = (np.asarray(v, np.float32) for v in (a, b))
                        if a.shape == (k, n):
                            mask = np.asarray(lay["held"])
                            a, b = a * mask, b * mask
                        elif a.shape[0] == lay["rows"]:
                            a, b = a[:used_rows], b[:used_rows]
                        worst = max(worst, float(np.max(np.abs(a - b))))
                line["max_abs_difference_from_take"] = worst
            if not args.rehearse:
                line["sum_ms"] = round(sum(
                    v for key, v in line.items() if key.endswith("_ms")), 4)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
