#!/usr/bin/env python3
"""Device time of one gated short convolution (``nn.ShortConv``), forward and
backward, at the LFM2 cell's shape, and of the part between its two
products written three ways: the layer's own ``gated_taps`` (shifted copies
of its input, a ``jax.checkpoint``), the same equation with g computed
first and plain autodiff, and ``lax.conv_general_dilated`` with
``feature_group_count = D`` on g.

    chiprun -- python3 scripts/shortconv_times.py            # 1 x 8192 x 2048
    JAX_PLATFORMS=cpu python3 scripts/shortconv_times.py --rehearse --t 64 --d 128

Prints one JSON line a form, each with the backend and the dtype:
milliseconds a call of value-and-gradient (median of ``--reps``, each closed
by a sync) and its largest difference from the layer's own form, output and
gradients. Off the TPU it refuses; ``--rehearse`` runs every form once there,
in float32, compares them and prints no time.
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

from distributed_tpu import nn  # noqa: E402
from distributed_tpu.nn.layers import _shifted, gated_taps  # noqa: E402


def g_first(bcz, taps):
    """The equation as it reads, g before its shifts, in float32; autodiff
    keeps what it multiplied by."""
    b, c, z = jnp.split(bcz.astype(jnp.float32), 3, axis=-1)
    g = b * z
    k = taps.shape[0]
    h = sum(taps[j] * _shifted(g, k - 1 - j) for j in range(k))
    return (c * h).astype(bcz.dtype)


def grouped_conv(bcz, taps):
    """The taps as XLA's own depthwise convolution of g, padded K - 1 on the
    left."""
    b, c, z = jnp.split(bcz.astype(jnp.float32), 3, axis=-1)
    k, d = taps.shape
    h = jax.lax.conv_general_dilated(
        b * z, taps[:, None, :], window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d)
    return (c * h).astype(bcz.dtype)


FORMS = {"gated_taps": gated_taps, "g_first": g_first,
         "conv_general_dilated": grouped_conv}


def timed(fn, args, reps):
    """Median milliseconds a call; None for ``reps`` 0 (a rehearsal)."""
    jax.block_until_ready(fn(*args))
    if not reps:
        return None
    seconds = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        seconds.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(seconds))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        sys.exit("device times come from a TPU; no TPU here (--rehearse "
                 "runs the forms once, untimed, and compares them)")
    dt = jnp.bfloat16 if on_chip else jnp.float32
    reps = args.reps if on_chip else 0
    where = {"backend": jax.default_backend(), "dtype": jnp.dtype(dt).name}
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(keys[0], (1, args.t, args.d), dt)
    w = jax.random.normal(keys[1], x.shape, dt)
    layer = nn.ShortConv(args.k, dtype=dt)
    layer.name = layer.default_name()
    params, _, _ = layer.init(keys[2], x.shape[1:])
    bcz = jax.random.normal(keys[3], (1, args.t, 3 * args.d), dt)

    whole = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, {}, x, train=True)[0] * w
                             ).astype(jnp.float32), (0, 1)))
    print(json.dumps({"form": "nn.ShortConv, both products included",
                      **where, "shape": list(x.shape),
                      "ms": timed(whole, (params, x), reps)}),
          flush=True)
    got = {}
    for name, form in FORMS.items():
        fn = jax.jit(jax.value_and_grad(
            lambda a, t, form=form: jnp.sum(
                form(a, t).astype(jnp.float32) * w), (0, 1)))
        got[name] = jax.device_get(fn(bcz, params["taps"]))
        ref = jax.tree_util.tree_leaves(got["gated_taps"])
        diff = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                       - np.asarray(b, np.float32))))
                   for a, b in zip(jax.tree_util.tree_leaves(got[name]), ref))
        print(json.dumps({"form": name, **where, "ms": timed(
            fn, (bcz, params["taps"]), reps),
            "largest_difference_from_gated_taps": diff}), flush=True)


if __name__ == "__main__":
    main()
