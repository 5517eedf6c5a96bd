"""Driver of serving cells: one closed batch through ``serving.Engine.run``.

``Engine.run`` submits every request at t=0 and runs to completion (the
engine has no arrival seam yet, PERF.md section 7), so a cell is an offline
batch: ``ceil(requests_per_second * --seconds)`` seeded requests, the same
work on the parent and on the change. What the benchmark clocks itself: the
wall of the pass, and the time between consecutive decode steps in the
``on_decode_step`` hook, which is the gap between tokens every running
request sees, other requests' prefill chunks included.

No cell of ``BENCHMARK.json`` uses this driver yet: the mixes it ran on the
chip in PR 22 were too small and too noisy to carry a bound (PERF.md
sections 6 and 7). The rehearsal cell ``gpt2-tiny.serve`` keeps it running.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import flops, harness, traffic as traffic_lib

# The engine computes in bfloat16 from float32 masters, through chunked
# prefill and a paged cache; the reference is one float32 forward pass at
# precision "highest" over prompt + output. At initialisation the logits
# have a standard deviation of about 0.2 and bfloat16 rounding through the
# depth moves a log-probability by up to 0.010 (measured on the chip at
# 36 x 1280 over 13 runs, PR 22: PERF.md section 6). A wrong mask, position, cache row or a lower
# precision moves it by tenths. The chosen token may trail the reference's
# maximum by no more than the same margin (near-ties round either way).
LOGPROB_TOL = 0.04
BUCKET = 64  # the engine rounds a prefill chunk up to a multiple of this
TRACE_SECONDS = 3.0  # a traced run profiles this much of the pass
REFERENCE_REQUESTS = 4
REFERENCE_NEW_TOKENS = 32  # outputs of the compared requests are cut to this


def prefill_buckets(prompt_len: int, chunk: int, max_len: int):
    """Padded lengths of the prefill dispatches a prompt needs: chunks of
    at most ``chunk`` positions, each rounded up to a multiple of 64 and
    capped at the end of the positional table (``Engine._bucket``)."""
    out = []
    for start in range(0, prompt_len, chunk):
        c = min(chunk, prompt_len - start)
        out.append(min(max(BUCKET, -(-c // BUCKET) * BUCKET),
                       max_len - start))
    return out


def run(env) -> dict:
    import jax

    import distributed_tpu as dtpu
    from distributed_tpu import serving
    from distributed_tpu.obs import registry as registry_mod

    cfg, tr, fam = env.config, env.traffic, env.family
    # The mix names its deployment: every key of ``engine`` is an Engine
    # argument, so a later cell turns on the fused decode kernel, the prefix
    # cache or int8 KV from its traffic file alone.
    sv = dict(tr["engine"])
    vocab, rows = int(cfg["vocab_size"]), fam.vocab_rows(cfg)
    n = traffic_lib.num_requests(tr, env.seconds)
    pairs = traffic_lib.serve_requests(tr, vocab, env.seed, n)

    # ------------------------------------------------------------ set-up --
    model = dtpu.Model(fam.build_module(cfg))
    model.build((int(cfg["n_positions"]),), seed=env.seed)
    engine = serving.Engine(model, **sv)
    # Warm every prefill shape this cell's prompts need, and the decode
    # program: one prompt per distinct bucket, two tokens each.
    buckets = sorted({b for p, _ in pairs for b in prefill_buckets(
        p.size, sv["prefill_chunk"], sv["max_len"])})
    # The pass is repeated until one compiles nothing: the engine's first
    # dispatch sees a freshly allocated pool and every later one a pool a
    # program returned, and jit keeps a program for each (seen in PR 22).
    rng = np.random.default_rng([env.seed, 3])
    warm = [(traffic_lib.zipf_tokens(rng, (b,), vocab,
                                     float(tr["zipf_exponent"])), 2)
            for b in buckets]
    snap = env.cache.snapshot()
    t0 = time.perf_counter()
    warm_passes = 0
    while warm_passes < 4:
        before = env.cache.snapshot()
        engine.run(warm)
        warm_passes += 1
        if env.cache.since(before)["lookups"] == 0:
            break
    warm_s = time.perf_counter() - t0
    warm_cache = dict(env.cache.since(snap), passes=warm_passes)

    # ------------------------------------------------------------ window --
    registry = registry_mod.default_registry()
    stamps, ring = [], {}
    tracing = {"on": False, "done": False, "t": 0.0}
    trace_after = 0.3 * env.seconds

    def merge_ring():
        for rec in registry.ring("engine/step_seconds"):
            ring[rec["step"]] = rec

    def on_decode_step(engine, step):
        now = time.perf_counter()
        stamps.append(now)
        if step % 128 == 0:
            merge_ring()
        if not env.trace or tracing["done"]:
            return
        if not tracing["on"] and now - t_run >= trace_after:
            harness.start_trace(env.trace_dir)
            tracing["on"], tracing["t"] = True, time.perf_counter()
        elif tracing["on"] and now - tracing["t"] >= TRACE_SECONDS:
            jax.profiler.stop_trace()
            tracing["on"], tracing["done"] = False, True

    requests = [serving.Request(p, o) for p, o in pairs]
    snap = env.cache.snapshot()
    t_run = time.perf_counter()
    outs = engine.run(requests, on_decode_step=on_decode_step)
    wall = time.perf_counter() - t_run
    if tracing["on"]:
        jax.profiler.stop_trace()
    window_cache = env.cache.since(snap)
    memory_peak = harness.memory_peak_bytes(jax.devices()[:env.cell["chips"]])
    telemetry = dict(engine.last_run_telemetry or {})
    merge_ring()

    # ------------------------------------------------------------ checks --
    failed = 0
    for (prompt, new), out in zip(pairs, outs):
        out = np.asarray(out)
        ok = (out.shape == (prompt.size + new,)
              and np.array_equal(out[:prompt.size], prompt)
              and bool(np.all((out >= 0) & (out < rows))))
        failed += not ok
    ref = _reference_check(env, engine, serving, tr, vocab, cfg, fam)
    generated = int(sum(new for _, new in pairs))
    gaps = np.diff(np.asarray(stamps))
    lo, hi = int(0.1 * len(gaps)), int(math.ceil(0.9 * len(gaps)))
    steady_gaps = gaps[lo:hi] if hi > lo else gaps
    checks = {
        "requests": n, "generated_tokens": generated, "wall_s": wall,
        "requests_per_s_completed": n / wall,
        "decode_steps": len(stamps), "outputs_failed": failed,
        "no_compile_in_window": window_cache["lookups"] == 0,
        "window_cache": window_cache, "warm_cache": warm_cache,
        "warm_s": warm_s, "prefill_buckets": buckets,
        "overran_window": wall > env.seconds,
        "gap_ms": {name: {f"p{q}": 1e3 * harness.percentile(g, q)
                          for q in (50, 75, 90, 95, 99)}
                   for name, g in (("steady", steady_gaps), ("all", gaps))
                   if len(g)},
        "peak_after_window": memory_peak, **ref,
    }
    correct = (failed == 0 and checks["no_compile_in_window"]
               and ref["reference_agrees"])

    # Latency samples leave out the first ``max_slots`` requests, which are
    # all admitted at t=0: the burst is not what the percentiles measure.
    rows_tel = telemetry.get("requests", [])[sv["max_slots"]:]
    steps = [ring[k] for k in sorted(ring)]
    telemetry.update(
        first_token_ms=[1e3 * (r["first_token_s"] - r["admitted_s"])
                        for r in rows_tel],
        decode_ring=steps, max_slots=sv["max_slots"], wall_s=wall,
        pool_positions=sv["max_slots"] * sv["max_len"],
        setup_compile_s=warm_s, vocab_rows=rows,
        decode_step_bytes=flops.decode_step_bytes(
            cfg, rows,
            telemetry.get("kv_utilization", {}).get("mean", 0.0)
            * sv["max_slots"] * sv["max_len"]))
    return {
        "correct": correct, "checks": checks, "attempted": n,
        "failed": failed, "memory_peak_bytes": memory_peak,
        "end_to_end": {
            "gen_tokens_per_s": generated / wall,
            "token_gap_p90_ms": 1e3 * harness.percentile(steady_gaps, 90)
            if len(steady_gaps) else math.nan,
            "setup_s": t_run - env.t_start,
        },
        "telemetry": telemetry,
    }


def _reference_check(env, engine, serving, tr, vocab, cfg, fam) -> dict:
    """A few seeded requests again with ``return_logprobs=True`` (which
    never recompiles): the reference's full forward over prompt + output
    must give each generated token the engine's log-probability, and that
    token must be the reference's maximum, both within LOGPROB_TOL."""
    import jax.numpy as jnp

    count = REFERENCE_REQUESTS
    pairs = [(p, min(o, REFERENCE_NEW_TOKENS))
             for p, o in traffic_lib.serve_requests(
                 tr, vocab, env.seed + 1_000_003, count)]
    requests = [serving.Request(p, o) for p, o in pairs]
    t0 = time.perf_counter()
    outs = engine.run(requests, return_logprobs=True)
    rows = engine.last_run_telemetry["requests"]
    params = fam.reference_params(engine.model.params, cfg)
    n_head, eps = int(cfg["n_head"]), fam.layer_norm_epsilon(cfg)
    longest = max(np.asarray(o).size for o in outs)
    padded = -(-longest // 128) * 128
    worst_lp, worst_gap = 0.0, 0.0
    for (prompt, new), out, row in zip(pairs, outs, rows):
        out = np.asarray(out)
        buf = np.zeros((padded,), np.int32)
        buf[:out.size] = out
        logp = np.asarray(env.reference.log_probs(
            params, jnp.asarray(buf), n_head=n_head, eps=eps))
        at = np.arange(prompt.size - 1, prompt.size + new - 1)
        ref_lp = logp[at, out[prompt.size:]]
        got = np.asarray(row["logprobs"], np.float64)
        worst_lp = max(worst_lp, float(np.max(np.abs(got - ref_lp))))
        worst_gap = max(worst_gap,
                        float(np.max(logp[at].max(axis=1) - ref_lp)))
    return {
        "reference_requests": count, "reference_len": padded,
        "logprob_max_abs_diff": worst_lp, "argmax_max_gap": worst_gap,
        "logprob_tol": LOGPROB_TOL,
        "reference_agrees": worst_lp < LOGPROB_TOL
        and worst_gap < LOGPROB_TOL,
        "reference_s": time.perf_counter() - t0,
    }
