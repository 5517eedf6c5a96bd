#!/usr/bin/env python3
"""Device time of one ``GroupedQueryAttention`` layer's head-wise output gate
alone, the heads' outputs and the gate's pre-activation ``z = x Wg`` in, the
gated outputs out, value and gradient, in three forms: the layer's plain
lines (``round(ctx * sigmoid(z)[..., None])`` on a float32 (1, T, H, 128)
view), the same on the flash kernels' (1, T, H x 128) layout in XLA (the
sigmoid repeated over each head's 128 lanes, ``z``'s gradient a reduction on
that layout), and ``ops/head_gate.py``'s two kernels; at the Laguna cell's
64 and 48 heads of 128 (the projection ``x Wg`` and its two gradient
products are left out: every form leaves them to XLA).

    python3 scripts/gate_times.py                  # on a TPU host
    JAX_PLATFORMS=cpu python3 scripts/gate_times.py --rehearse --t 64

One JSON line a shape and form: milliseconds a call of value and gradient on
the device (the busy time inside the program's runs, from a profiler trace
read with ``benchmarks/trace.py``), the kernels' own, the five largest
operations by name, the least bytes the gate moves (ctx and the gated output
forward; the cotangent, ctx and d ctx backward: five passes over (T, H x
128), and ``z`` and ``dz``), that over the HBM's 819 GB/s and the form's time
over it; and ``relayouts``: the compiled program's copies, transposes and
reshapes of arrays of T x H x 128 elements. Off the TPU it refuses;
``--rehearse`` runs the forms once there (in float32), compares values and
gradients with the plain lines and prints no time.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tpu.ops import head_gate as hg  # noqa: E402

HBM_BYTES_PER_S = 819e9  # one v5e chip (benchmarks/peaks.json)
HEADS = (64, 48)
# ``%name = type[dims]{layout} opcode(``, an instruction of the HLO text.
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* (\w[\w\-]*)\(")


def plain(ctx, z):
    """The layer's lines, on the (1, T, H, 128) view."""
    b, t, width = ctx.shape
    g = jax.nn.sigmoid(z.astype(jnp.float32))
    c = ctx.reshape(b, t, width // 128, 128)
    return (c.astype(jnp.float32) * g[..., None]).astype(ctx.dtype).reshape(
        b, t, width)


def xla_2d(ctx, z):
    """The same on the (1, T, H x 128) layout: the sigmoid repeated over
    each head's 128 lanes, ``z``'s gradient autodiff's sum of the
    repetition."""
    s = jnp.repeat(jax.nn.sigmoid(z.astype(jnp.float32)), 128, axis=-1)
    return (ctx.astype(jnp.float32) * s).astype(ctx.dtype)


def both_passes(f):
    def run(ctx, z, g):
        out, vjp = jax.vjp(f, ctx, z)
        return out, vjp(g)
    return run


def least_bytes(t, heads, itemsize):
    return (5 * t * heads * 128 + 2 * t * heads) * itemsize


def relayouts(text, elements):
    """``name opcode dtype[dims]`` of every copy, transpose or reshape (by
    opcode, or by the name of the fusion XLA made of one) whose result has
    ``elements`` entries."""
    found = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name, dtype, dims, opcode = m.groups()
        size = int(np.prod([int(d) for d in dims.split(",") if d]))
        if size == elements and (
                opcode in ("copy", "transpose", "reshape")
                or re.match(r"(copy|transpose|reshape)", name)):
            found.append(f"{name} {opcode} {dtype}[{dims}]")
    return found


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, ".bench_trace", "gate_times"))
    args = ap.parse_args()
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.rehearse):
        sys.exit("device times come from a TPU's trace; no TPU here "
                 "(--rehearse compares the forms on the CPU)")
    from benchmarks import trace as trace_lib

    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    itemsize = jnp.dtype(dtype).itemsize
    rng = np.random.default_rng(0)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    forms = (("plain", plain), ("xla_2d", xla_2d),
             ("kernels", hg.head_gate))
    for heads in HEADS:
        shape = (1, args.t, heads * 128)
        ctx, g = (jnp.asarray(rng.standard_normal(shape), dtype)
                  for _ in range(2))
        z = jnp.asarray(2.0 * rng.standard_normal(shape[:2] + (heads,)),
                        dtype)
        nbytes = least_bytes(args.t, heads, itemsize)
        results = {}
        for form, f in forms:
            compiled = jax.jit(both_passes(f)).lower(ctx, z, g).compile()
            results[form] = jax.block_until_ready(compiled(ctx, z, g))
            line = {"backend": jax.default_backend(),
                    "dtype": str(jnp.dtype(dtype)), "t": args.t,
                    "heads": heads, "form": form,
                    "least_bytes": nbytes,
                    "bytes_ms_at_hbm_peak": round(
                        1e3 * nbytes / HBM_BYTES_PER_S, 4),
                    "relayouts": relayouts(compiled.as_text(),
                                           args.t * heads * 128)}
            if on_tpu:
                tdir = os.path.join(args.out, f"h{heads}-{form}")
                shutil.rmtree(tdir, ignore_errors=True)
                jax.profiler.start_trace(tdir)
                for _ in range(args.steps):
                    out = compiled(ctx, z, g)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                trace = trace_lib.load(trace_lib.find_xplane(tdir))
                dev = trace_lib.device(trace)
                runs = trace_lib.module_runs(dev)
                ms = 1e3 * float(np.median(
                    trace_lib.run_busy_seconds(dev, runs)))
                line["device_ms"] = round(ms, 4)
                line["over_bytes_time"] = round(
                    ms / line["bytes_ms_at_hbm_peak"], 3)
                line["runs"] = len(runs)
                line["top_ops_ms"] = {
                    name: round(1e3 * s / len(runs), 4)
                    for name, s in trace_lib.top_ops(trace, 5)}
                for kernel in ("dtpu_head_gate_bwd", "dtpu_head_gate"):
                    ev = [e for e in dev.ops
                          if trace_lib.kernel_name(e) == kernel]
                    if ev:
                        line[kernel + "_ms"] = round(
                            1e3 * sum(e.seconds for e in ev) / len(ev), 4)
                shutil.rmtree(tdir, ignore_errors=True)
            print(json.dumps(line), flush=True)
        want_out, (want_dc, want_dz) = results["plain"]
        line = {"heads": heads, "against_plain": {}}
        for form in ("xla_2d", "kernels"):
            out, (dc, dz) = results[form]
            line["against_plain"][form] = {
                name: {"equal_share": float(np.mean(f32(a) == f32(b))),
                       "max_abs_err": float(np.max(np.abs(f32(a) - f32(b))))}
                for name, a, b in (("out", out, want_out),
                                   ("dctx", dc, want_dc),
                                   ("dz", dz, want_dz))}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
