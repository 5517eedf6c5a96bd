#!/usr/bin/env python3
"""Device time of one selecting attention layer (``nn.GroupedQueryAttention``
with an indexer) at the Keye cell's shape, forward and backward, by the
layer's own scopes and by operation: where a step's attention time goes,
without the rest of the model. Run on the chip (times come from a profiler
trace, read with ``benchmarks/trace.py`` and ``benchmarks/scopes.py``):

    chiprun -- python3 scripts/dsa_layer_times.py
    JAX_PLATFORMS=cpu python3 scripts/dsa_layer_times.py --rehearse --t 256 \\
        --topk 64 --heads 4 --kv-heads 2 --d-model 64

One JSON line: milliseconds a call on the host clock, and from the trace the
milliseconds under ``indexer``, ``select`` and the rest of the layer, the
twelve operations with most time, and every instruction under ``indexer``
(``select`` apart) that takes 0.05 ms or more: its name, its output's shape,
its calls and their milliseconds together (a block of queries is a call:
16 a layer at T = 8192), and the end of its ``op_name``. ``--rehearse`` runs
it once on the CPU (dense path, no trace) to prove the control flow.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "dsa_layer_times"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import scopes, scopes_dsa, trace as trace_lib
    from distributed_tpu import nn

    if jax.default_backend() != "tpu" and not args.rehearse:
        sys.exit("times come from a TPU's trace; no TPU here (--rehearse)")
    layer = nn.GroupedQueryAttention(
        args.heads, args.kv_heads, args.head_dim, rope_theta=1e7,
        index_topk=args.topk,
        record_selection=True, dtype=jnp.bfloat16)
    layer.name = layer.default_name()
    params, state, _ = layer.init(jax.random.PRNGKey(0),
                                  (args.t, args.d_model))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, args.t, args.d_model),
                          jnp.bfloat16)

    @jax.jit
    def step(params, state, x):
        def loss(p, x):
            with jax.named_scope(layer.name):
                y, new = layer.apply(p, state, x, train=True)
            return jnp.sum(y.astype(jnp.float32)) + new["aux_loss"], new
        (_, new), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            params, x)
        return new, grads

    jax.block_until_ready(step(params, state, x))
    times = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        jax.block_until_ready(step(params, state, x))
        times.append(time.perf_counter() - t0)
    out = {"t": args.t, "topk": args.topk,
           "index_block": nn.attention.INDEX_BLOCK,
           "backend": jax.default_backend(),
           "host_ms": 1e3 * float(np.median(times))}
    if not args.rehearse:
        shutil.rmtree(args.out, ignore_errors=True)
        jax.profiler.start_trace(args.out)
        jax.block_until_ready(step(params, state, x))
        jax.profiler.stop_trace()
        path = trace_lib.find_xplane(args.out)
        parsed = trace_lib.load(path)
        names = scopes.op_names(path)
        # By scope and by instruction; a loop's own event encloses its
        # body's and is left out of both.
        sums, by_name, indexer = {}, {}, {}
        for e in trace_lib.device(parsed).ops:
            if e.op.startswith("while"):
                continue
            scope_path, _ = scopes.scope_of(names.get(e.name, ""))
            inner = scopes_dsa._inner(scope_path) or "rest"
            sums[inner] = sums.get(inner, 0.0) + 1e3 * e.seconds
            by_name[e.name] = by_name.get(e.name, 0.0) + 1e3 * e.seconds
            if inner == "indexer":
                calls_ms = indexer.setdefault(e.name, [0, 0.0])
                calls_ms[0] += 1
                calls_ms[1] += 1e3 * e.seconds
        out["scope_ms"] = sums
        out["indexer_instructions"] = [
            [name.split(" = ")[0].lstrip("%"),
             "".join(re.findall(r" = (\(?\w+\[[\d,]*\])", name)[:1]),
             calls, ms, names.get(name, "")[-70:]]
            for name, (calls, ms) in sorted(
                indexer.items(), key=lambda kv: -kv[1][1]) if ms >= 0.05]
        out["top_ops_ms"] = [[n, 1e3 * s]
                             for n, s in trace_lib.top_ops(parsed, 12)]
        out["top_instructions_ms"] = [
            [name[:90], ms, "/".join(scopes.scope_of(names.get(name, ""))[0]
                                     )[-60:]]
            for name, ms in sorted(by_name.items(),
                                   key=lambda kv: -kv[1])[:10]]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
