#!/usr/bin/env python3
"""Chip-free cost of the three flash kernels: the TPU compiler's own count
of VLIW bundles a grid step, and how busy each unit is in them.

    JAX_PLATFORMS=cpu python3 scripts/flash_bundles.py --subtiles 128,256
    ... --shape 1,4096,32,192 --value-width 128    (latent attention: folded)

compiles ``jax.grad`` of ``flash_attention`` for a described (not attached)
v5e with libtpu's LLO dump on and reads each kernel's
``*final_hlo-static-per-bundle-utilization.txt``. Where the grid is one
block (T <= 1024) the kernel body is straight-line code, so bundles x grid
steps is its cycles less stalls: on the chip the three kernels ran 21.2-22.3
thousand such bundles a millisecond at every sub-tile side tried (PR 26),
which makes this the place to try a reordering or a reformulation before
spending chip time on ``flash_kernel_times.py``. It is a count, not a time:
a kernel with a loop or several ``pl.when`` regions counts each once (at T
= 4096 the grid has three block views, each a region: the sum is no grid
step's, but a variant's sums still compare). The compiling child dies at exit after the dump is written (libtpu, described
device); only its files are read.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = """
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from distributed_tpu.ops import flash_attention as fa
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
fa._interpret = lambda: False
jax.config.update("jax_enable_compilation_cache", False)
if {side}:
    fa._SUBTILE = {side}
x, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
        for s in ({shape!r}, {v_shape!r}))
loss = lambda q, k, v: jnp.sum(fa.flash_attention(
    q, k, v, causal={causal}).astype(jnp.float32))
jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, v).compile()
"""


def utilization(path):
    """(bundles, {unit: busy share}) of one per-bundle utilization dump."""
    lines = open(path).read().split("\n")
    units = lines[1].split(", ")
    capacity = [int(x) for x in lines[2].split()]
    rows = [[int(x) for x in line.split()] for line in lines[4:]
            if len(line.split()) == len(units)]
    busy = [sum(col) for col in zip(*rows)]
    return len(rows), {
        u: b / (len(rows) * c) for u, b, c in zip(units, busy, capacity)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,1024,16,64")
    ap.add_argument("--value-width", type=int, default=0,
                    help="v's head width where it is not q's (0: q's)")
    ap.add_argument("--subtiles", default="0", help="0: the module's own")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--root", default=HERE)
    args = ap.parse_args()
    shape = tuple(int(x) for x in args.shape.split(","))
    v_shape = shape[:3] + (args.value_width or shape[3],)
    for side in (int(x) for x in args.subtiles.split(",")):
        out = tempfile.mkdtemp(prefix="flash_llo_")
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
            f"--xla_jf_dump_to={out} --xla_jf_dump_llo_text=true"))
        subprocess.run(
            [sys.executable, "-c", CHILD.format(
                root=os.path.abspath(args.root), side=side, shape=shape,
                v_shape=v_shape, causal=bool(args.causal))],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        found = sorted(glob.glob(os.path.join(
            out, "*flash_*final_hlo-static-per-bundle-utilization.txt")))
        if not found:
            sys.exit(f"no LLO dump under {out}: did the compile fail?")
        for path in found:
            kernel = re.search(
                r"(flash_(?:fwd|dq|dkv)(?:_packed)?)", path).group(1)
            bundles, busy = utilization(path)
            print(f"side {side:4d} {kernel:18s} {bundles:6d} bundles  "
                  + "  ".join(f"{u} {100 * b:.0f}%" for u, b in busy.items()
                              if u in ("MXU", "XLU", "VALU", "EUP", "VSTORE")))
        shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()
