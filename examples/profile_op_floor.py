"""Measure the per-op execution floor of this runtime, latency-cancelled.

The round-3 profile shows ResNet-50's 161-tensor optimizer bucket and the
BN reductions running far below HBM bandwidth. Hypothesis: each XLA
fusion/op instance pays a fixed floor (DMA setup / dispatch) on this
runtime, so many-small-op program regions are op-count-bound, not
byte-bound. All timings here use the differential two-run-length method
from profile_convs.py, which cancels the constant cost of the sync that
ends each run.

Usage: python examples/profile_op_floor.py
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


def sync1(v):
    np.asarray(jax.device_get(jnp.ravel(v)[:1]))


def timeit(fn, state, warmup=3, n1=10, n2=60):
    """Per-call time via the difference of two pipelined run lengths,
    threading (possibly donated) state through consecutive calls."""

    def run(n, state):
        t0 = time.perf_counter()
        for _ in range(n):
            state = fn(*state)
        sync1(jax.tree_util.tree_leaves(state)[0])
        return time.perf_counter() - t0, state

    for _ in range(warmup):
        state = fn(*state)
    sync1(jax.tree_util.tree_leaves(state)[0])
    _, state = run(n1, state)  # extra warm pass: equal starting conditions
    t1, state = run(n1, state)
    t2, state = run(n2, state)
    return max(t2 - t1, 1e-9) / (n2 - n1)


def main():
    key = jax.random.PRNGKey(0)

    # (a) N independent tiny elementwise ops in one program
    for n in (1, 40, 160):
        xs = [jax.random.normal(jax.random.fold_in(key, i), (256,))
              for i in range(n)]

        def many(*xs):
            return tuple(x * 1.0001 + 0.1 for x in xs)

        t = timeit(jax.jit(many), tuple(xs))
        print(f"{n:4d} tiny (256,) mul-adds       {t*1e3:8.3f} ms "
              f"({t/n*1e6:7.1f} us/op)", flush=True)

    # (b) one big elementwise op at SGD+momentum traffic (p, m, g -> p', m')
    p = jax.random.normal(key, (25_600_000,))
    m = jnp.zeros_like(p)
    g = jax.random.normal(key, (25_600_000,)) * 0.01

    def sgdm(p, m, g):
        m2 = 0.9 * m + g
        return p - 0.1 * m2, m2, g

    t = timeit(jax.jit(sgdm, donate_argnums=(0, 1)), (p, m, g))
    gbps = (5 * 25.6e6 * 4) / t / 1e9
    print(f"one 25.6M-elem SGD+momentum    {t*1e3:8.3f} ms ({gbps:6.1f} GB/s)",
          flush=True)

    # (c) N-operand concat of 25.6M total elements
    for n in (8, 161):
        sizes = [25_600_000 // n] * n
        parts = [jax.random.normal(jax.random.fold_in(key, i), (s,))
                 for i, s in enumerate(sizes)]

        def cat(out_prev, *parts):
            return (jnp.concatenate(parts), *parts)

        t = timeit(jax.jit(cat), (jnp.zeros((sum(sizes),)), *parts))
        gbps = (2 * 25.6e6 * 4) / t / 1e9
        print(f"concat {n:4d} x {sizes[0]/1e3:7.0f}K        {t*1e3:8.3f} ms "
              f"({gbps:6.1f} GB/s)", flush=True)

    # (d) minimal Pallas kernel launch cost
    from jax.experimental import pallas as pl

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 1.0001

    @jax.jit
    def pk(x):
        return pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    x = jax.random.normal(key, (8, 128))
    t = timeit(lambda x: (pk(x),), (x,))
    print(f"one minimal pallas call        {t*1e3:8.3f} ms", flush=True)

    # (e) lax.scan of 161 iterations over a stacked (161, 256) buffer
    xs = jax.random.normal(key, (161, 256))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scanned(xs):
        def body(c, x):
            return c, x * 1.0001 + 0.1
        _, ys = jax.lax.scan(body, 0.0, xs)
        return ys

    t = timeit(lambda xs: (scanned(xs),), (xs,))
    print(f"scan 161 tiny iterations       {t*1e3:8.3f} ms", flush=True)


if __name__ == "__main__":
    main()
