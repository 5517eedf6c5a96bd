"""Driver of training cells whose family is not GPT-2: ``Model.fit`` on
seeded token batches, as ``drivers/train.py`` runs it, with everything that
file reads from GPT-2's keys taken from the family instead:

- ``fam.reference_params(params, state, config)``,
  ``fam.reference_kwargs(config)`` and ``fam.choices(state, config)`` for the
  plain reference's ``compare``;
- ``fam.train_flops_per_token(config, seq_len)`` for ``step_mfu_pct``;
- ``fam.learning_rate(config, peak)``: the family's schedule up to the
  traffic's learning rate;
- ``fam.first_step_checks(...)`` and ``fam.FIRST_STEP_CHECKS``: the first
  step beside the family's limits, written with their reasons where they
  are set;
- ``fam.telemetry(model, config)``: what the family's own per-layer readers
  need of the finished fit.

One run and its timing are ``train.py``'s (its helpers and constants are
imported, its window loop repeated: that file may not change under a
``model_config`` PR). The comparison of the first step goes further than
that file's loss and gradient norm, which a wrong expert layer hardly moves
(PERF.md section 6, PR 28): the reference's gradient is compared with the
program's leaf by leaf, as a difference. For that the reference runs
after the first step, on a copy of the initial leaves and state kept on the
host meanwhile, and is held to the experts the step itself chose; its
seconds, the copy's included, are taken out of ``setup_s``.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import harness, traffic as traffic_lib
from benchmarks.drivers.train import (
    ADAM_B1, FIRST_LOSS_TOL, TRACE_STEPS, WARMUP_STEPS, WINDOW_STEPS,
    find_field, global_norm)


def run(env) -> dict:
    import jax

    import distributed_tpu as dtpu

    cfg, tr, fam = env.config, env.traffic, env.family
    batch, seq_len = int(tr["global_batch"]), int(tr["seq_len"])
    warmup, window = WARMUP_STEPS, WINDOW_STEPS
    x, y = traffic_lib.train_batches(tr, int(cfg["vocab_size"]), env.seed)

    # ------------------------------------------------------------ set-up --
    with getattr(dtpu, tr["strategy"])().scope():
        model = dtpu.Model(fam.build_module(cfg))
        model.compile(
            optimizer=dtpu.optim.Adam(
                fam.learning_rate(cfg, float(tr["learning_rate"])),
                b1=ADAM_B1),
            loss=tr["loss"], metrics=())
    model.build((seq_len,), seed=env.seed)

    # The initial leaves and state, kept on the host until the reference
    # has run.
    t0 = time.perf_counter()
    initial, initial_state = jax.device_get((model.params, model.state))
    reference_s = time.perf_counter() - t0
    devices = jax.devices()[:env.cell["chips"]]

    # First step: compiles the one train program. With Adam, mu after one
    # step is (1 - b1) * gradient, so the system's gradient is read from
    # its own optimizer state, and the experts it chose from its layers'.
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

    # The reference on the first batch, from the leaves as they were before
    # that step, held to the step's own choices of experts where the batch
    # is one sequence. No part of the system's set-up.
    t0 = time.perf_counter()
    forced = fam.choices(model.state, cfg) if batch == 1 else None
    compared = jax.device_get(env.reference.compare(
        fam.reference_params(jax.device_put(initial), initial_state, cfg),
        x[:batch], y[:batch], kw=fam.reference_kwargs(cfg),
        system_grads=fam.reference_params(mu, initial_state, cfg),
        scale=1.0 / (1.0 - ADAM_B1), forced=forced))
    del initial
    first_step = fam.first_step_checks(
        first_loss, sys_gnorm, compared,
        seq_len * int(cfg["num_experts_per_tok"]))
    reference_s += time.perf_counter() - t0
    peak_after_reference = harness.memory_peak_bytes(devices)

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
        **first_step,
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
        "family": fam.telemetry(model, cfg),
    }
    correct = all(checks[k] for k in fam.FIRST_STEP_CHECKS + (
        "first_loss_near_ln_vocab", "losses_finite", "loss_fell",
        "no_compile_in_window"))
    setup_s = (state["t_first"] - env.t_start - reference_s
               if state["t_first"] else math.nan)
    step_s = float(np.median(windows)) / window if windows else math.nan
    telemetry.update(checks["family"])
    telemetry.update(
        step_seconds=step_s, steps=state["n"] + 1,
        tokens_per_step=batch * seq_len, chips=env.cell["chips"],
        train_flops_per_token=fam.train_flops_per_token(cfg, seq_len),
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
