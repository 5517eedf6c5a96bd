"""Microbenchmark every distinct conv shape in ResNet-50 (fwd + both grads).

Pinpoints which convolutions run far below peak so the model-level fixes
(space-to-depth stem, width padding) target the right layers.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def sync(v):
    np.asarray(jax.device_get(v))


def timeit(fn, warmup=2, n1=5, n2=25):
    """Per-call time via the difference of two pipelined run lengths.

    (t(n2) - t(n1)) / (n2 - n1) cancels the constant cost of the sync that
    ends each run; outputs are reduced to a scalar on device so the fetch
    is 4 bytes."""
    tiny = jax.jit(lambda t: jax.tree_util.tree_reduce(
        lambda a, l: a + jnp.sum(l).astype(jnp.float32), t, 0.0))

    def run(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        sync(tiny(out))
        return time.perf_counter() - t0

    for _ in range(warmup):
        out = fn()
    sync(tiny(out))
    run(n1)  # one more warm pass so both measured runs start identically
    t1 = run(n1)
    t2 = run(n2)
    return max(t2 - t1, 1e-9) / (n2 - n1)


# (label, H, Cin, Cout, k, stride) — batch fixed at 256, NHWC
SHAPES = [
    ("stem 7x7/2", 224, 3, 64, 7, 2),
    ("s2d stem 4x4/1", 112, 12, 64, 4, 1),
    ("s1 1x1 64->64", 56, 64, 64, 1, 1),
    ("s1 3x3 64->64", 56, 64, 64, 3, 1),
    ("s1 1x1 64->256", 56, 64, 256, 1, 1),
    ("s1 1x1 256->64", 56, 256, 64, 1, 1),
    ("s2 3x3/2 128", 56, 128, 128, 3, 2),
    ("s2 1x1 128->512", 28, 128, 512, 1, 1),
    ("s2 3x3 128", 28, 128, 128, 3, 1),
    ("s3 3x3 256", 14, 256, 256, 3, 1),
    ("s4 3x3 512", 7, 512, 512, 3, 1),
]

B = 256


def main():
    key = jax.random.PRNGKey(0)
    for label, h, cin, cout, k, stride in SHAPES:
        x = jax.random.normal(key, (B, h, h, cin), jnp.bfloat16)
        w = jax.random.normal(key, (k, k, cin, cout), jnp.bfloat16)

        def conv(x, w):
            return lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        fwd = jax.jit(conv)

        @jax.jit
        def bwd(x, w):
            y, vjp = jax.vjp(conv, x, w)
            return vjp(jnp.ones_like(y))

        out_h = -(-h // stride)
        flops = 2 * k * k * cin * cout * out_h * out_h * B
        tf = timeit(lambda: fwd(x, w))
        tb = timeit(lambda: bwd(x, w))
        print(f"{label:20s} fwd {tf*1e3:7.2f} ms {flops/tf/1e12:6.1f} TF/s"
              f"   bwd {tb*1e3:7.2f} ms {2*flops/tb/1e12:6.1f} TF/s",
              flush=True)

    # maxpool 3x3/2 fwd+bwd at stem resolution
    x = jax.random.normal(key, (B, 112, 112, 64), jnp.bfloat16)

    def pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")

    pf = jax.jit(pool)

    @jax.jit
    def pb(x):
        y, vjp = jax.vjp(pool, x)
        return vjp(jnp.ones_like(y))

    tf_, tb_ = timeit(lambda: pf(x)), timeit(lambda: pb(x))
    print(f"{'maxpool 3x3/2 @112':20s} fwd {tf_*1e3:7.2f} ms"
          f"          bwd {tb_*1e3:7.2f} ms", flush=True)

    # the BN stats + normalize elementwise cost at stage-1 size
    x = jax.random.normal(key, (B, 56, 56, 256), jnp.bfloat16)

    @jax.jit
    def bn_stats(x):
        xf = x.astype(jnp.float32)
        m1 = jnp.mean(xf, axis=(0, 1, 2))
        m2 = jnp.mean(jnp.square(xf), axis=(0, 1, 2))
        return (x - m1.astype(x.dtype)) * lax.rsqrt(
            m2 - jnp.square(m1) + 1e-5).astype(x.dtype)

    t = timeit(lambda: bn_stats(x))
    gb = x.size * 2 * 3 / 1e9  # 2 reads + 1 write
    print(f"{'BN train @56x56x256':20s}     {t*1e3:7.2f} ms "
          f"{gb/t:6.0f} GB/s effective", flush=True)


if __name__ == "__main__":
    main()
