"""Driver of training cells: ``Model.fit`` on seeded token batches.

One run: build and compile the configuration's model under the traffic
file's strategy; compare the very first step (loss, gradient) with the plain
reference; warm up; then time ``Model.fit`` in windows of ten steps, each
closed by ``block_until_ready`` on the loss inside a callback, until
``--seconds`` have passed. The window arithmetic (median of windows, each
ending in a sync) is that of ``bench.py:_time_steps``. Nothing is lowered or
compiled after the window: which Mosaic kernels ran is read from the traced
run's device trace (``expect_kernels`` of the traffic file, ``run.py``).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import flops, harness, traffic as traffic_lib

# |system - reference| the first step may show. The system computes in
# bfloat16 (8 bits of mantissa) from float32 masters, the reference in
# float32 at precision "highest"; on near-uniform logits at initialisation
# the loss then differs by 5e-5 and the gradient norm by 2e-4 relative
# (measured on the chip at 24 x 1024, PR 22: PERF.md section 6); the
# tolerances leave a factor of twenty. int8 weights or activations, a
# dropped bias, a wrong mask or epsilon move the gradient norm by percents.
LOSS_TOL = 1e-3
GRAD_NORM_RTOL = 5e-3
FIRST_LOSS_TOL = 0.2  # of ln(vocabulary rows): the head starts near uniform
ADAM_B1 = 0.9  # mu after one step is (1 - b1) * gradient
WARMUP_STEPS = 5
WINDOW_STEPS = 10
TRACE_STEPS = 5  # a traced run profiles this many steps of its second window


def find_field(tree, name: str):
    """First attribute called ``name`` in a nest of optax state tuples."""
    if hasattr(tree, name) and hasattr(tree, "_fields"):
        return getattr(tree, name)
    if isinstance(tree, dict):
        children = tree.values()
    elif isinstance(tree, (tuple, list)):
        children = tree
    else:
        return None
    for child in children:
        found = find_field(child, name)
        if found is not None:
            return found
    return None


def global_norm(tree) -> float:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norm(t):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                            for x in jax.tree_util.tree_leaves(t)))

    return float(norm(tree))


def run(env) -> dict:
    import jax

    import distributed_tpu as dtpu

    cfg, tr, fam = env.config, env.traffic, env.family
    batch, seq_len = int(tr["global_batch"]), int(tr["seq_len"])
    warmup, window = WARMUP_STEPS, WINDOW_STEPS
    vocab = int(cfg["vocab_size"])
    x, y = traffic_lib.train_batches(tr, vocab, env.seed)

    # ------------------------------------------------------------ set-up --
    with getattr(dtpu, tr["strategy"])().scope():
        model = dtpu.Model(fam.build_module(cfg))
        model.compile(
            optimizer=dtpu.optim.Adam(float(tr["learning_rate"]), b1=ADAM_B1),
            loss=tr["loss"], metrics=())
    model.build((seq_len,), seed=env.seed)
    t_built = time.perf_counter()

    # The reference's loss and gradient on the first batch, from the
    # system's own initial leaves, before a step has changed them. Its
    # seconds are no part of the system's set-up and are taken out of it.
    n_head, eps = int(cfg["n_head"]), fam.layer_norm_epsilon(cfg)
    ref_loss, ref_gnorm = env.reference.loss_and_grad_norm(
        fam.reference_params(model.params, cfg), x[:batch], y[:batch],
        n_head=n_head, eps=eps)
    ref_loss, ref_gnorm = float(ref_loss), float(ref_gnorm)
    reference_s = time.perf_counter() - t_built
    devices = jax.devices()[:env.cell["chips"]]
    peak_after_reference = harness.memory_peak_bytes(devices)

    # First step: compiles the one train program. With Adam, mu after one
    # step is (1 - b1) * gradient, so the system's gradient norm is read
    # from its own optimizer state.
    snap = env.cache.snapshot()
    t0 = time.perf_counter()
    first = model.fit(x[:batch], y[:batch], batch_size=batch, epochs=1,
                      steps_per_epoch=1, shuffle=False, verbose=0,
                      seed=env.seed)
    first_fit_s = time.perf_counter() - t0
    first_loss = float(first.history["loss"][0])
    mu = find_field(model.opt_state, "mu")
    sys_gnorm = global_norm(mu) / (1.0 - ADAM_B1)
    compile_cache = env.cache.since(snap)

    # ------------------------------------------------- warm-up and window --
    tracing = {"on": False, "done": False, "start_at": None}
    state = {"n": 0, "t_first": None, "t_window": None, "windows": [],
             "losses": [], "all": []}

    def on_batch_end(model, step, logs):
        state["n"] += 1
        state["all"].append(logs["loss"])
        n = state["n"] - warmup
        if n < 0:
            return
        if tracing["on"] and n == tracing["start_at"] + TRACE_STEPS:
            jax.block_until_ready(logs["loss"])
            jax.profiler.stop_trace()
            tracing["on"], tracing["done"] = False, True
        if n % window:
            return
        loss = float(jax.block_until_ready(logs["loss"]))
        now = time.perf_counter()
        if n == 0:
            state["t_first"] = now
            state["cache"] = env.cache.snapshot()
        else:
            state["windows"].append(now - state["t_window"])
            state["losses"].append(loss)
            if now - state["t_first"] >= env.seconds:
                model.stop_training = True
        if (env.trace and not tracing["done"] and not tracing["on"]
                and n == window and not model.stop_training):
            # One window in: trace the next few steps, from a synced start.
            harness.start_trace(env.trace_dir)
            tracing["on"], tracing["start_at"] = True, n
            now = time.perf_counter()
        state["t_window"] = now

    model.fit(x, y, batch_size=batch, epochs=1, steps_per_epoch=1_000_000,
              shuffle=False, verbose=0, seed=env.seed,
              callbacks=[dtpu.callbacks.LambdaCallback(
                  on_batch_end=on_batch_end)])
    if tracing["on"]:
        jax.profiler.stop_trace()
    window_cache = env.cache.since(state.get("cache", env.cache.snapshot()))
    memory_peak = harness.memory_peak_bytes(devices)
    telemetry = dict(model.last_fit_telemetry or {})
    losses = [float(v) for v in jax.device_get(state["all"])]

    # ------------------------------------------------------------- checks --
    windows = state["windows"]
    if env.trace and len(windows) > 1:
        windows = windows[:1] + windows[2:]  # drop the traced window
    steady = (window * batch * seq_len / float(np.median(windows))
              if windows else math.nan)
    rows = fam.vocab_rows(cfg)
    checks = {
        "first_loss": first_loss, "reference_loss": ref_loss,
        "grad_norm": sys_gnorm, "reference_grad_norm": ref_gnorm,
        "loss_tol": LOSS_TOL, "grad_norm_rtol": GRAD_NORM_RTOL,
        "loss_agrees": abs(first_loss - ref_loss) < LOSS_TOL,
        "grad_norm_agrees": (abs(sys_gnorm - ref_gnorm)
                             < GRAD_NORM_RTOL * ref_gnorm),
        "first_loss_near_ln_vocab": abs(first_loss - math.log(rows))
        < FIRST_LOSS_TOL,
        "losses_finite": all(math.isfinite(v) for v in losses),
        "loss_fell": bool(state["losses"])
        and state["losses"][-1] < first_loss,
        "window_losses": state["losses"],
        "no_compile_in_window": window_cache["lookups"] == 0,
        "window_cache": window_cache, "first_step_cache": compile_cache,
        "steps": state["n"] + 1, "windows": len(state["windows"]),
        "window_seconds": state["windows"],
        "reference_s": reference_s, "first_fit_s": first_fit_s,
        "peak_after_reference": peak_after_reference,
        "peak_after_window": memory_peak,
        "params": model.num_params,
    }
    correct = all(checks[k] for k in (
        "loss_agrees", "grad_norm_agrees", "first_loss_near_ln_vocab",
        "losses_finite", "loss_fell", "no_compile_in_window"))
    setup_s = (state["t_first"] - env.t_start - reference_s
               if state["t_first"] else math.nan)
    step_s = float(np.median(windows)) / window if windows else math.nan
    telemetry.update(
        step_seconds=step_s, steps=state["n"] + 1,
        tokens_per_step=batch * seq_len, chips=env.cell["chips"],
        train_flops_per_token=flops.train_flops_per_token(cfg, rows, seq_len),
        setup_compile_s=first_fit_s - step_s,
        rows_per_chip=batch // env.cell["chips"], seq_len=seq_len,
        vocab_rows=rows)
    return {
        "correct": correct, "checks": checks,
        "attempted": state["n"] + 1,
        "failed": sum(not math.isfinite(v) for v in losses),
        "memory_peak_bytes": memory_peak,
        "end_to_end": {"train_tokens_per_s": steady, "setup_s": setup_s},
        "telemetry": telemetry,
    }
