#!/usr/bin/env python3
"""Device time of the q and k path of one ``GroupedQueryAttention`` layer
alone, the projection's output in and the flash kernels' operand out, forward
and backward: the layer's plain lines (``_rms`` then ``rope_half`` /
``rope_rotary`` on a (1, T, H, 128) view) beside ``ops/head_norm_rope.py``'s
two kernels, at the head counts and rotations of the two cells with 128-wide
heads (Laguna: 64 and 8 heads at theta 1e4, 48 and 8 under YaRN over 64
dimensions; Keye: 32 and 4 at theta 1e7).

    chiprun -- python3 scripts/head_prep_times.py
    JAX_PLATFORMS=cpu python3 scripts/head_prep_times.py --rehearse --t 64

One JSON line a shape and form: milliseconds a call of value and gradient on
the device (the busy time inside the program's runs, from a profiler trace
read with ``benchmarks/trace.py``), the kernels' own, the bytes the form
moves (XLA's ``bytes accessed`` for the plain lines; for the kernels two
passes over the rows forward and three backward, and a row block's tables
once), and that over the HBM's 819 GB/s. Off the TPU it refuses;
``--rehearse`` runs both forms once there (in float32; in bfloat16 with
``--definition``), compares values and gradients and prints no time.
``--block-rows`` and ``--block-heads`` set the kernels' blocks (how
``blocks`` was chosen). ``--definition`` also holds both forms'
values, at the shapes of 8 heads and fewer, to the definition computed on
the host in float64 with its two roundings to the operand's dtype (after the
norm, after the rotation), from the frequencies and angles as the device's
float32 has them: on the v5e XLA keeps the norm's result in float32 inside
the fusion that rotates it, so it is the plain lines that part from it (root
PERF.md section 6, PR 39).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tpu.nn.attention import (  # noqa: E402
    _rms, rope_half, rope_rotary, yarn_inv_freq)
from distributed_tpu.ops import head_norm_rope as hn  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmarks/peaks.json)
EPS = 1e-6
YARN = (yarn_inv_freq(64, 500000.0, factor=64.0, original_max_position=4096,
                      beta_fast=64.0, beta_slow=1.0), 1.4159)
# (heads, rotation): a float is rope_half's theta.
CASES = [(64, 1e4), (8, 1e4), (48, YARN), (8, YARN), (32, 1e7), (4, 1e7)]


def plain(rotation):
    def f(x, scale):
        b, t, width = x.shape
        y = _rms(x.reshape(b, t, width // 128, 128), scale, EPS)
        y = (rope_rotary(y, *rotation) if isinstance(rotation, tuple)
             else rope_half(y, rotation))
        return y.reshape(b, t, width)
    return f


def fused(rotation, **blocks):
    def f(x, scale):
        rot = rotation if isinstance(rotation, tuple) else (
            1.0 / (rotation ** (jnp.arange(0, 128, 2, dtype=jnp.float32)
                                / 128)), 1.0)
        return hn.head_norm_rope(x, scale, rot, epsilon=EPS, **blocks)
    return f


def both_passes(f):
    def run(x, scale, g):
        out, vjp = jax.vjp(f, x, scale)
        return out, vjp(g)
    return run


def definition(x, scale, rotation):
    """The two steps as written, float64 inside, rounded to ``x``'s dtype
    after each; (1, T, H x 128) float32."""
    inv_freq, factor = rotation if isinstance(rotation, tuple) else (
        1.0 / (rotation ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)),
        1.0)
    inv_freq = np.asarray(inv_freq, np.float32)
    t, r = x.shape[1], 2 * len(inv_freq)
    rounded = lambda a: np.asarray(jnp.asarray(a, jnp.float32).astype(
        x.dtype).astype(jnp.float32), np.float64)
    xf = np.asarray(x.astype(jnp.float32), np.float64).reshape(t, -1, 128)
    n = xf / np.sqrt(np.mean(xf * xf, axis=-1, keepdims=True) + EPS)
    y = rounded(n * np.asarray(scale, np.float64))
    angle = (np.arange(t, dtype=np.float32)[:, None] * inv_freq[None]
             ).astype(np.float64)
    cos, sin = (np.tile(f(angle), 2)[:, None, :] * factor
                for f in (np.cos, np.sin))
    rot = y[..., :r]
    turned = np.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    out = np.concatenate([rot * cos + turned * sin, y[..., r:]], axis=-1)
    return rounded(out).astype(np.float32).reshape(x.shape)


def kernel_bytes(t, heads, itemsize, tables):
    rows = t * heads * 128 * itemsize
    return 5 * rows + 2 * tables * t * 128 * 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--block-rows", type=int, default=0)
    ap.add_argument("--block-heads", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--definition", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "head_prep_times"))
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.rehearse):
        sys.exit("device times come from a TPU's trace; no TPU here "
                 "(--rehearse compares the forms on the CPU)")
    from benchmarks import trace as trace_lib

    dtype = jnp.bfloat16 if on_tpu or args.definition else jnp.float32
    rng = np.random.default_rng(0)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    for heads, rotation in CASES:
        blocks = {}
        if args.block_rows:
            blocks["block_rows"] = args.block_rows
        if args.block_heads and heads % args.block_heads == 0:
            blocks["block_heads"] = args.block_heads
        shape = (1, args.t, heads * 128)
        x, g = (jnp.asarray(rng.standard_normal(shape), dtype)
                for _ in range(2))
        scale = jnp.asarray(1.0 + 0.1 * rng.standard_normal(128), jnp.float32)
        name = "yarn64" if isinstance(rotation, tuple) else f"theta{rotation:g}"
        results = {}
        for form, f in (("plain", plain(rotation)),
                        ("kernels", fused(rotation, **blocks))):
            compiled = jax.jit(both_passes(f)).lower(x, scale, g).compile()
            results[form] = jax.block_until_ready(compiled(x, scale, g))
            line = {"backend": jax.default_backend(), "dtype": str(
                jnp.dtype(dtype)), "t": args.t, "heads": heads,
                "rotation": name, "form": form, **blocks}
            if form == "kernels":
                partial = isinstance(rotation, tuple) and len(
                    rotation[0]) < 64
                line["bytes"] = kernel_bytes(
                    args.t, heads, jnp.dtype(dtype).itemsize,
                    3 if partial else 2)
            else:
                cost = compiled.cost_analysis()
                cost = cost[0] if isinstance(cost, (list, tuple)) else cost
                line["bytes"] = float(cost.get("bytes accessed", 0.0))
            line["bytes_ms_at_hbm_peak"] = round(
                1e3 * line["bytes"] / HBM_BYTES_PER_S, 4)
            if on_tpu:
                tdir = os.path.join(args.out, f"h{heads}-{name}-{form}")
                shutil.rmtree(tdir, ignore_errors=True)
                jax.profiler.start_trace(tdir)
                for _ in range(args.steps):
                    out = compiled(x, scale, g)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                dev = trace_lib.device(trace_lib.load(
                    trace_lib.find_xplane(tdir)))
                runs = trace_lib.module_runs(dev)
                line["device_ms"] = round(1e3 * float(np.median(
                    trace_lib.run_busy_seconds(dev, runs))), 4)
                line["runs"] = len(runs)
                for kernel in ("dtpu_head_norm_rope_bwd",
                               "dtpu_head_norm_rope"):
                    ev = [e for e in dev.ops
                          if trace_lib.kernel_name(e) == kernel]
                    if ev:
                        line[kernel + "_ms"] = round(
                            1e3 * sum(e.seconds for e in ev) / len(ev), 4)
                shutil.rmtree(tdir, ignore_errors=True)
            print(json.dumps(line), flush=True)
        (o, (dx, ds)), (wo, (wdx, wds)) = results["kernels"], results["plain"]
        err = lambda a, b: float(np.max(np.abs(f32(a) - f32(b)))
                                 / max(np.max(np.abs(f32(b))), 1e-30))
        line = {"heads": heads, "rotation": name,
                "kernels_against_plain_max_err_over_max": {
                    "out": err(o, wo), "dx": err(dx, wdx),
                    "dscale": err(ds, wds)},
                "out_equal_share": float(np.mean(f32(o) == f32(wo)))}
        if args.definition and heads <= 8:
            want = definition(x, scale, rotation)
            line["equal_to_the_definition_share"] = {
                "plain": float(np.mean(f32(wo) == want)),
                "kernels": float(np.mean(f32(o) == want))}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
