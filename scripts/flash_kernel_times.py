#!/usr/bin/env python3
"""Device time of the three flash kernels at one shape, by sub-tile side:
how ``flash_attention._SUBTILE`` was chosen, to repeat on another chip or
shape. Run on the chip (a TPU is required: kernel time comes from a
profiler trace, read with ``benchmarks/trace.py``):

    chiprun -- python3 scripts/flash_kernel_times.py --subtiles 128,256,512
    ... --shape 1,4096,32,192 --value-width 128    (latent attention: folded)

``--subtiles`` sets ``_SUBTILE``, the side under a head block of 128 lanes (a
folded head of 192 lanes sits in 256 and takes twice the side given).
``--root DIR`` imports ``distributed_tpu`` from another checkout (a parent
commit unpacked beside this one; ``--subtiles 0`` leaves its module as it
is). ``--check`` also compares values and gradients with
``dense_attention`` at the shape. One JSON line per sub-tile side.
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,1024,16,64")
    ap.add_argument("--value-width", type=int, default=0,
                    help="v's head width where it is not q's (0: q's)")
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
    v_shape = shape[:3] + (args.value_width or shape[3],)
    q, k, v, g = (jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
                  for s in (shape, shape, v_shape, v_shape))

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
        flash = lambda q, k, v: fa.flash_attention(q, k, v, causal=causal,
                                                   **kw)
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
        if args.check:
            dense = lambda q, k, v: fa.dense_attention(q, k, v, causal)
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
