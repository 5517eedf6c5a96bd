#!/usr/bin/env python3
"""Device time of the row-wise top-k selection (``ops/topk_select.py``), the
bisection beside XLA's own ``top_k``, at the Keye cell's shape: each of T
queries keeps ``k`` of its causal keys, a block of queries at a time.

    chiprun -- python3 scripts/select_times.py              # 8192, 2048, 512
    JAX_PLATFORMS=cpu python3 scripts/select_times.py --t 256 --k 64 --block 64

Prints one JSON line a method: milliseconds a call (median of ``--reps``,
each closed by a sync; on the CPU a count of nothing) and whether the two
masks are the same.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_tpu.ops.topk_select import topk_mask  # noqa: E402


def sort_mask(scores, k, valid):
    """The same mask from XLA's own ``top_k`` and its last value: what the
    bisection is measured against."""
    low = jnp.where(valid, scores, -jnp.inf)
    kth = jax.lax.top_k(low, min(k, scores.shape[-1]))[0][..., -1:]
    return jnp.logical_and(valid, low >= kth)


MASKS = {"bisect": topk_mask, "sort": sort_mask}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--k", type=int, default=2048)
    ap.add_argument("--block", type=int, default=512)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    t, block = args.t, args.block
    scores = jax.random.normal(jax.random.PRNGKey(0), (t, t), jnp.float32)

    def select(method):
        def rows(blk):
            s, r = blk
            causal = r[:, None] >= jnp.arange(t)[None, :]
            return MASKS[method](s, args.k, causal).astype(jnp.int8)
        return jax.jit(lambda s: jax.lax.map(rows, (
            s.reshape(t // block, block, t),
            jnp.arange(t).reshape(t // block, block))))

    masks = {}
    for method in MASKS:
        fn = select(method)
        masks[method] = jax.block_until_ready(fn(scores))
        times = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(scores))
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "method": method, "t": t, "k": args.k, "block": block,
            "ms": 1e3 * float(np.median(times)),
            "selected": int(jnp.sum(masks[method], dtype=jnp.int32)),
            "backend": jax.default_backend()}), flush=True)
    print(json.dumps({"same_mask": bool(jnp.array_equal(
        masks["bisect"], masks["sort"]))}))


if __name__ == "__main__":
    main()
