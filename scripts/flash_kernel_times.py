#!/usr/bin/env python3
"""Device time of the three flash kernels at one shape, by sub-tile side:
how ``flash_attention._SUBTILE`` was chosen, to repeat on another chip or
shape. Run on the chip (a TPU is required: kernel time comes from a
profiler trace, read with ``benchmarks/trace.py``):

    chiprun -- python3 scripts/flash_kernel_times.py --subtiles 128,256,512
    ... --shape 1,4096,32,192 --value-width 128    (latent attention: folded)
    ... --shape 1,8192,32,128 --kv-heads 4 --select-topk 2048  (the ``_sel``
        kernels: grouped queries over a selection of keys)

``--subtiles`` sets ``_SUBTILE``, the side under a head block of 128 lanes (a
folded head of 192 lanes sits in 256 and takes twice the side given).
``--root DIR`` imports ``distributed_tpu`` from another checkout (a parent
commit unpacked beside this one; ``--subtiles 0`` leaves its module as it
is). ``--check`` also compares values and gradients with
``dense_attention`` at the shape (which holds every score: at a T it fits).
``--kv-heads N`` shares each K/V head among ``heads // N`` query heads;
``--select-topk K`` hands the kernels a selection, seeded and causal, each
query's K keys spread evenly over those before it as a fresh indexer's are.
One JSON line per sub-tile side, with the K/V (and selection) block fetches
of a forward call as it published them (the ``flash.kv_block_fetches``
gauges; null from a checkout from before them, which fetched once a query
head) and the bytes they move.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = ("dtpu_flash_fwd", "dtpu_flash_dq", "dtpu_flash_dkv")


def spread_selection(rng, t, topk):
    """(1, t, t) int8: each query's ``topk`` keys (all, where it has fewer)
    drawn evenly from the keys at or before it."""
    import numpy as np
    scores = np.tril(rng.random((t, t), dtype=np.float32) + 1.0)
    kth = np.partition(scores, t - topk, axis=1)[:, t - topk]
    return (scores >= np.maximum(kth, 1.0)[:, None]).astype(np.int8)[None]


def fetches(fa, registry, shape, dv, blocks, selecting):
    """The forward call's K/V (and selection) block fetches, beside one a
    query head block, as the call just traced published them (None: a
    checkout from before the gauges, which fetched once a query head), and
    the bytes they move (bf16)."""
    n, a_head = (registry.gauge_value(f"flash.kv_block_fetches{x}")
                 for x in ("", "_a_head"))
    if n is None:
        return {"kv_block_fetches": None, "kv_fetch_bytes": None}
    _, t, _, d = shape
    bq, bk = fa.resolve_blocks(t, 2, blocks.get("block_q"),
                               blocks.get("block_k", 1024))
    return {"kv_block_fetches": [n, a_head], "kv_fetch_bytes": n * bk * (
        2 * (fa._lane_pad(d) + fa._lane_pad(dv)) + bq * selecting)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,1024,16,64")
    ap.add_argument("--value-width", type=int, default=0,
                    help="v's head width where it is not q's (0: q's)")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="K/V heads where fewer than the query heads")
    ap.add_argument("--select-topk", type=int, default=0,
                    help="keys a query selects (0: no selection)")
    ap.add_argument("--subtiles", default="0")
    ap.add_argument("--blocks", default="", help="block_q,block_k")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "flash_kernel_times"))
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    sys.path.insert(1, HERE)  # benchmarks.trace, wherever the kernels are

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import trace as trace_lib
    from distributed_tpu.ops import flash_attention as fa

    if jax.default_backend() != "tpu":
        sys.exit("kernel times come from a TPU's trace; no TPU here")
    shape = tuple(int(x) for x in args.shape.split(","))
    kw = {}
    if args.blocks:
        kw["block_q"], kw["block_k"] = (int(x) for x in args.blocks.split(","))
    causal = bool(args.causal)
    rng = np.random.default_rng(0)
    b, t, heads, d = shape
    kv_heads = args.kv_heads or heads
    dv = args.value_width or d
    q, k, v, g = (jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
                  for s in (shape, (b, t, kv_heads, d), (b, t, kv_heads, dv),
                            (b, t, heads, dv)))
    sel = None
    if args.select_topk:
        sel = jnp.broadcast_to(
            spread_selection(rng, t, args.select_topk), (b, t, t))

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

    from distributed_tpu.obs.registry import default_registry
    for s in (int(x) for x in args.subtiles.split(",")):
        if s:
            fa._SUBTILE = s
        for cached in ("_flash_cached", "_packed_cached", "subtile_counts"):
            if hasattr(fa, cached):  # by checkout: --root
                getattr(fa, cached).cache_clear()
        flash = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, **kw,
            **({} if sel is None else {"selection": sel}))
        step = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))
        t0 = time.perf_counter()
        lowered = step.lower(q, k, v)
        trace_s = time.perf_counter() - t0
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0 - trace_s
        jax.block_until_ready(compiled(q, k, v))
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = compiled(q, k, v)
        jax.block_until_ready(out)
        host_ms = 1e3 * (time.perf_counter() - t0) / args.steps
        tdir = os.path.join(args.out, f"s{s}")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        for _ in range(args.steps):
            out = compiled(q, k, v)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = trace_lib.device(trace_lib.load(trace_lib.find_xplane(tdir)))
        line = {"root": args.root, "subtile": s, "shape": shape, **kw,
                "causal": causal, "trace_s": round(trace_s, 3),
                "compile_s": round(compile_s, 3),
                "grad_host_ms": round(host_ms, 4)}
        for name in KERNELS:  # folded, or with _packed behind the name
            ev = [e for e in trace_lib.matching(dev, name)]
            line[name.replace("dtpu_flash_", "") + "_ms"] = round(
                1e3 * sum(e.seconds for e in ev) / max(len(ev), 1), 4)
            line[name.replace("dtpu_flash_", "") + "_calls"] = len(ev)
        # What the call just traced published (None: this checkout's path
        # for the shape publishes nothing).
        line["subtiles"] = [
            default_registry().gauge_value(f"flash.subtiles_{n}")
            for n in ("square", "computed", "masked")]
        line.update(fetches(fa, default_registry(), shape, dv, kw,
                            sel is not None))
        if args.check:
            dense = lambda q, k, v: fa.dense_attention(
                q, k, v, causal, *([] if sel is None else [sel]))
            f32 = lambda t: np.asarray(t.astype(jnp.float32))
            want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
            got = compiled(q, k, v)
            line["grad_rel_err"] = [
                float(np.linalg.norm(f32(a) - f32(b))
                      / np.linalg.norm(f32(b))) for a, b in zip(got, want)]
            line["out_max_err"] = float(np.max(np.abs(
                f32(jax.jit(flash)(q, k, v)) - f32(jax.jit(dense)(q, k, v)))))
        shutil.rmtree(tdir, ignore_errors=True)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
