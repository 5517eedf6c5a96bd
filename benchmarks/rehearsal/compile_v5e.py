#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile the cells' programs
at their real sizes for a described (not attached) v5e and print the bytes
each device needs (``memory_analysis``), before any chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearsal/compile_v5e.py train gpt2-medium train-b8-t1024
    JAX_PLATFORMS=cpu python3 benchmarks/rehearsal/compile_v5e.py train gpt2-large train-b16-t1024
    JAX_PLATFORMS=cpu python3 benchmarks/rehearsal/compile_v5e.py serve gpt2-large <serve mix>
    JAX_PLATFORMS=cpu python3 benchmarks/rehearsal/compile_v5e.py reference gpt2-medium train-b8-t1024

A scratch script, run by hand: it reaches into ``Model._train_step_body`` and
the engine's dispatch functions to hand them described devices and abstract
shapes, and it tells the program's trace-time backend checks "tpu" (they
would take their CPU branches otherwise). Nothing runs; a compile that
passes is not a chip run. Not imported by the benchmark.
"""

from __future__ import annotations

import functools
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import harness  # noqa: E402

GB = 1e9


def mosaic_kernel_names(program_text: str):
    """Names of the Mosaic (``tpu_custom_call``) kernels in a compiled
    program's text. After ``chip_smoke.mosaic_calls`` (PR 21)."""
    names = []
    for line in program_text.splitlines():
        if "tpu_custom_call" in line:
            names += [n.rstrip("_") for n in
                      re.findall(r"dtpu_[a-z0-9_]+", line)[:1]]
    return names


def report(name, compiled, t0):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    kernels = mosaic_kernel_names(compiled.as_text())
    print(f"{name}: compiled in {time.perf_counter() - t0:.0f}s; per device "
          f"arguments {mem.argument_size_in_bytes / GB:.2f} GB, outputs "
          f"{mem.output_size_in_bytes / GB:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / GB:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / GB:.2f} GB, live at once about "
          f"{total / GB:.2f} GB; Mosaic kernels "
          f"{sorted(set(kernels))} x{len(kernels)}", flush=True)


def sds(tree, shardings):
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def train(topo, manifest, config, traffic):
    import distributed_tpu as dtpu

    fam = harness.load_module(manifest, "families", config["family"])
    batch, seq_len = traffic["global_batch"], traffic["seq_len"]
    if traffic["strategy"] == "SingleDevice":
        strategy = dtpu.SingleDevice(topo.devices[0])
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda tree: jax.tree_util.tree_map(lambda _: one, tree)
        p_shard = o_shard = place
        b_shard = one
    else:
        strategy = getattr(dtpu, traffic["strategy"])(devices=topo.devices)
        p_shard = strategy.params_sharding
        b_shard = strategy.batch_sharding()
    with strategy.scope():
        model = dtpu.Model(fam.build_module(config))
        model.compile(optimizer=dtpu.optim.Adam(traffic["learning_rate"]),
                      loss=traffic["loss"], metrics=())
    params, state, _ = jax.eval_shape(
        lambda k: model.module.init(k, (seq_len,)), jax.random.PRNGKey(0))
    opt = jax.eval_shape(model.tx.init, params)
    if traffic["strategy"] != "SingleDevice":
        o_shard = lambda o: strategy.opt_state_sharding(o, params)
    model._dtype_hints = model.module.dtype_hints()
    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32, sharding=b_shard)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    step = jax.jit(model._train_step_body(), donate_argnums=(0, 1, 2))
    compiled = model._scoped(step.lower)(
        sds(params, p_shard(params)), state, sds(opt, o_shard(opt)), tok, tok,
        rng).compile()
    report(f"train {config['name']} B={batch} {traffic['strategy']}",
           compiled, t0)
    text = compiled.as_text()
    for op in ("all-gather", "reduce-scatter", "all-reduce"):
        print(f"  {op}: {text.count(' ' + op)} in the compiled program")


def reference(topo, manifest, config, traffic):
    """The plain reference's loss-and-gradient program on the first batch,
    on the leaves as the train cell's strategy places them."""
    import distributed_tpu as dtpu

    fam = harness.load_module(manifest, "families", config["family"])
    ref = harness.load_module(manifest, "reference", config["family"])
    batch, seq_len = traffic["global_batch"], traffic["seq_len"]
    module = fam.build_module(config)
    params, _, _ = jax.eval_shape(
        lambda k: module.init(k, (seq_len,)), jax.random.PRNGKey(0))
    if traffic["strategy"] == "SingleDevice":
        one = SingleDeviceSharding(topo.devices[0])
        shard = jax.tree_util.tree_map(lambda _: one, params)
        tok_s = one
    else:
        strategy = getattr(dtpu, traffic["strategy"])(devices=topo.devices)
        shard = strategy.params_sharding(params)
        from jax.sharding import NamedSharding, PartitionSpec
        tok_s = NamedSharding(strategy.mesh, PartitionSpec())
    tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32, sharding=tok_s)
    t0 = time.perf_counter()
    compiled = ref.loss_and_grad_norm.lower(
        fam.reference_params(sds(params, shard), config), tok, tok,
        n_head=config["n_head"], eps=fam.layer_norm_epsilon(config)).compile()
    report(f"reference loss+gradient {config['name']} B={batch}", compiled,
           t0)


def serve(topo, manifest, config, traffic):
    import distributed_tpu as dtpu
    from distributed_tpu.serving import engine as eng

    fam = harness.load_module(manifest, "families", config["family"])
    sv = traffic["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    module = fam.build_module(config)
    model = dtpu.Model(module)
    params, state, _ = jax.eval_shape(
        lambda k: module.init(k, (config["n_positions"],)),
        jax.random.PRNGKey(0))
    nb = -(-sv["max_len"] // sv["block_size"])
    blocks = sv["max_slots"] * nb + 1
    caches = jax.eval_shape(
        lambda p: module.init_paged_cache(p, blocks, sv["block_size"],
                                          fam.compute_dtype(config)), params)
    hints = module.dtype_hints()
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one)
    s = sv["max_slots"]
    t0 = time.perf_counter()
    decode = jax.jit(functools.partial(
        eng._decode_dispatch, module, 0.0, None, None, hints),
        donate_argnums=(2,))
    report("serve decode", model._scoped(decode.lower)(
        put(params), state, put(caches), i32(s), i32(s, nb), i32(s),
        u32(s, 2)).compile(), t0)
    for cb in (64, sv["prefill_chunk"]):
        t0 = time.perf_counter()
        prefill = jax.jit(functools.partial(
            eng._prefill_dispatch, module, 0.0, None, None, hints),
            donate_argnums=(2,))
        report(f"serve prefill bucket {cb}", model._scoped(prefill.lower)(
            put(params), state, put(caches), i32(1, cb), i32(nb), i32(),
            i32(), u32(2)).compile(), t0)


def main():
    kind, config_name = sys.argv[1], sys.argv[2]
    manifest = harness.load_manifest()
    config = harness.load_json(os.path.join(
        ROOT, harness.entry(manifest, "configs", config_name)["file"]))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # steer trace-time backend checks
    traffic = harness.load_json(
        harness.find_file(manifest, "traffic", sys.argv[3]))
    if len(sys.argv) > 4:
        traffic["global_batch"] = int(sys.argv[4])
    {"train": train, "reference": reference, "serve": serve}[kind](
        topo, manifest, config, traffic)


if __name__ == "__main__":
    main()
