#!/usr/bin/env python3
"""What the flash backward's row statistic ``delta = sum(dO O)`` moves, by
the TPU's own compiler for a described (not attached) v5e: nothing runs, no
chip is needed (about 45 s of compiling on the host's CPU).

    JAX_PLATFORMS=cpu python3 scripts/flash_delta_bytes.py

For each attention layer shape of the six benchmark cells, one JSON line:
XLA's ``bytes accessed`` of one layer's ``delta`` in two forms, the reduction
on a float32 (b, T, H, D) view (``by_head``: the lines before
``ops/flash_attention.py`` contracted it) and the contraction on the
kernels' (b, T, H x D) layout (``contracted``, as ``_bwd_pallas`` computes
it), beside the least bytes (dO and O read once), each form's instructions
of the entry computation; and ``backward_f32_views``: the instructions of the
whole flash backward as the package compiles it (``jax.vjp`` of
``flash_attention`` with a bf16 cotangent) that are outside the
``dtpu_flash_*`` kernels and make a float32 array of T x H x D or more
entries (none is left since the contraction).
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tpu.ops import flash_attention as fa  # noqa: E402

# Cell, (B, T, H, D) of q, K/V heads, value width, window, a selection.
LAYERS = (
    ("laguna-xs2.train.swa8k sliding", (1, 8192, 64, 128), 8, 128, 512, False),
    ("laguna-xs2.train.swa8k full", (1, 8192, 48, 128), 8, 128, None, False),
    ("keye-vl2-30b.train.dsa8k", (1, 8192, 32, 128), 4, 128, None, True),
    ("gpt2-medium.train.1chip", (8, 1024, 16, 64), 16, 64, None, False),
    ("gpt2-large.train.fsdp4", (4, 1024, 20, 64), 20, 64, None, False),
    ("lfm2-8b-a1b.train.ep4share", (1, 8192, 32, 64), 8, 64, None, False),
    ("kanana2-30b.train.ep8share", (1, 4096, 32, 192), 32, 128, None, False),
)
# ``%name = type opcode(`` of the HLO text: the type a shape or a tuple.
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\(")
F32 = re.compile(r"f32\[([\d,]*)\]")


def by_head(g, out, heads, hpb):
    """The lines before the contraction: a float32 (b, T, H, D) view."""
    b, t, width = out.shape
    view = (b, t, heads // hpb, hpb, width // heads)
    gf = g.astype(jnp.float32).reshape(view)
    of = out.astype(jnp.float32).reshape(view)
    return jnp.transpose(jnp.sum(gf * of, axis=-1), (0, 2, 3, 1))


def contracted(g, out, heads, hpb):
    """``_bwd_pallas``'s: the product on (b, T, H x D), each head's lanes
    summed by a 0/1 (H x D, H) matrix."""
    b, t, width = out.shape
    lanes = jnp.arange(width) // (width // heads)
    return jnp.einsum("btw,wh->bht", g.astype(jnp.float32) * out, (
        lanes[:, None] == jnp.arange(heads)).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).reshape(
            b, heads // hpb, hpb, t)


def entry(text):
    """(line, name, type, opcode) of every instruction of the ENTRY
    computation."""
    found, inside = [], False
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside and (m := INSTRUCTION.match(line)):
            found.append((line, *m.groups()))
    return found


def f32_views(text, elements):
    """``name opcode type`` of the ENTRY's instructions other than the flash
    kernels' custom calls whose result holds a float32 array of ``elements``
    entries or more."""
    return [f"{name} {opcode} {kind}"
            for line, name, kind, opcode in entry(text)
            if any(np.prod([int(d) for d in dims.split(",") if d]) >= elements
                   for dims in F32.findall(kind))
            and not (opcode == "custom-call" and "dtpu_flash" in line)]


def instructions(text):
    """``opcode type`` of the ENTRY's instructions that compute something
    (parameters, tuples and bitcasts left out)."""
    return [f"{opcode} {kind}" for _, _, kind, opcode in entry(text)
            if opcode not in ("parameter", "tuple", "get-tuple-element",
                              "bitcast", "constant")]


def bytes_accessed(compiled):
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return int(cost["bytes accessed"])


def backward(shape, kv_heads, dv, window, selecting, one_chip):
    """The compiled text of ``jax.vjp`` of ``flash_attention`` at a layer's
    shape, its cotangent in bf16."""
    b, t, h, d = shape
    spec = lambda s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        s, dtype, sharding=one_chip)
    sel = spec((b, t, t), jnp.int8) if selecting else None

    def vjp(q, k, v, sel, g):
        out, pull = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, selection=sel, window=window), q, k, v)
        return pull(g)

    return jax.jit(vjp).lower(
        spec(shape), spec((b, t, kv_heads, d)), spec((b, t, kv_heads, dv)),
        sel, spec((b, t, h, dv))).compile().as_text()


def main():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    fa._interpret = lambda: False  # Mosaic, though the backend is the CPU
    jax.config.update("jax_enable_compilation_cache", False)
    for cell, shape, kv_heads, dv, window, selecting in LAYERS:
        began = time.perf_counter()
        b, t, h, d = shape
        packed = dv == d and fa._packed_supported(h, d)
        # The layout _bwd_pallas sees: (b, T, H x D) with 128 // D heads a
        # lane block, or folded, (B x H, T, Dv) with one head a row.
        rows, width, heads, hpb = (b, h * dv, h, 128 // d) if packed else (
            b * h, dv, 1, 1)
        out = jax.ShapeDtypeStruct((rows, t, width), jnp.bfloat16,
                                   sharding=one_chip)
        line = {"cell": cell, "layout": "packed" if packed else "folded",
                "out": list(out.shape), "heads": heads,
                "least_mb": 2 * out.size * 2 / 1e6}
        for form, f in (("by_head", by_head), ("contracted", contracted)):
            compiled = jax.jit(f, static_argnums=(2, 3)).lower(
                out, out, heads, hpb).compile()
            line[form] = {"mb": bytes_accessed(compiled) / 1e6,
                          "instructions": instructions(compiled.as_text())}
        line["backward_f32_views"] = f32_views(
            backward(shape, kv_heads, dv, window, selecting, one_chip),
            t * h * dv)
        line["seconds"] = round(time.perf_counter() - began, 1)
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
