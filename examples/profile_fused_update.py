"""Microbench: tree-form vs flat-space optimizer updates on the real chip.

PERF.md's round-3 finding: a tree-form SGD+momentum update over ResNet-50's
161 tensors costs ~30 ms while the numerically identical update on one
raveled vector costs ~0.8 ms. The round-3 "flat master params" A/B moved
the cost into grad-side unravel/transpose ops because the LOSS took the
flat vector. This bench tests the other factoring: keep tree params and
tree grads (the forward/backward never changes), and go flat only inside
the optimizer — concatenate grad leaves once, update flat param/momentum
buffers (donated), slice the new params back out.

Usage: python examples/profile_fused_update.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import distributed_tpu as dtpu


def sync(v):
    # Fetch ONE element, never the full buffer.
    np.asarray(jax.device_get(v.ravel()[:1]))


def timeit(fn, state, warmup=3, measure=20):
    for _ in range(warmup):
        state = fn(*state)
    sync(jax.tree_util.tree_leaves(state)[0])
    t0 = time.perf_counter()
    for _ in range(measure):
        state = fn(*state)
    sync(jax.tree_util.tree_leaves(state)[0])
    return (time.perf_counter() - t0) / measure, state


def main():
    model = dtpu.Model(dtpu.models.resnet(50, 1000, dtype=jnp.bfloat16))
    model.compile(optimizer=dtpu.optim.SGD(0.1, momentum=0.9),
                  loss="sparse_categorical_crossentropy")
    model.build((224, 224, 3))
    params = model.params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    shapes = [l.shape for l in leaves]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).tolist()
    total = offsets[-1]
    print(f"{len(leaves)} tensors, {total/1e6:.1f}M params", flush=True)

    key = jax.random.PRNGKey(0)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(key, p.shape, p.dtype) * 0.01, params)

    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    # (a) tree-form update, donated
    @jax.jit
    def tree_update(params, opt_state, grads):
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, grads

    copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
    t, _ = timeit(jax.jit(tree_update, donate_argnums=(0, 1)),
                  (copy(params), opt_state, grads))
    print(f"tree update (161 tensors)      {t*1e3:8.2f} ms", flush=True)

    # (b) flat-space update: concat grads -> flat sgd+momentum -> slice back
    flat_p = jnp.concatenate([l.ravel() for l in leaves])
    flat_m = jnp.zeros_like(flat_p)

    def to_tree(flat):
        out = [flat[offsets[i]:offsets[i + 1]].reshape(shapes[i])
               for i in range(len(sizes))]
        return jax.tree_util.tree_unflatten(treedef, out)

    def flat_update(flat_p, flat_m, tree_prev, grads):
        g = jnp.concatenate(
            [l.ravel() for l in jax.tree_util.tree_leaves(grads)])
        new_m = 0.9 * flat_m + g
        new_p = flat_p - 0.1 * new_m
        return new_p, new_m, to_tree(new_p), grads

    t, _ = timeit(jax.jit(flat_update, donate_argnums=(0, 1, 2)),
                  (jnp.copy(flat_p), jnp.copy(flat_m), copy(params), grads))
    print(f"flat update incl concat+slice  {t*1e3:8.2f} ms", flush=True)

    # (c) flat update alone (no concat, no slice-back) — the lower bound
    flat_g = jnp.concatenate(
        [l.ravel() for l in jax.tree_util.tree_leaves(grads)])

    def flat_only(flat_p, flat_m, flat_g):
        new_m = 0.9 * flat_m + flat_g
        return flat_p - 0.1 * new_m, new_m, flat_g

    t, _ = timeit(jax.jit(flat_only, donate_argnums=(0, 1)),
                  (jnp.copy(flat_p), jnp.copy(flat_m), flat_g))
    print(f"flat update alone              {t*1e3:8.2f} ms", flush=True)

    # (d) concat alone
    @jax.jit
    def concat_only(grads, prev):
        return (grads, jnp.concatenate(
            [l.ravel() for l in jax.tree_util.tree_leaves(grads)]))

    t, _ = timeit(concat_only, (grads, flat_g))
    print(f"concat 161 -> flat alone       {t*1e3:8.2f} ms", flush=True)

    # (e) slice-back alone
    @jax.jit
    def slice_only(flat, prev):
        return (flat, to_tree(flat))

    t, _ = timeit(slice_only, (jnp.copy(flat_p), copy(params)))
    print(f"slice flat -> 161 alone        {t*1e3:8.2f} ms", flush=True)


if __name__ == "__main__":
    main()
