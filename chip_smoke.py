#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

``python chip_smoke.py`` (no arguments, repo root, ONE process) drives the
normal entry points once on a TPU, at the full width of the 136M LM
(the rounds 1-5 configuration, docs/PERF_ROUNDS_1-5.md), on weights and
tokens made from a seed:

- device gate: fails before any leg unless ``jax.default_backend() == "tpu"``;
- sync: what ``jax.block_until_ready`` does, against a scalar host fetch;
- kernels: every Pallas kernel compiled by Mosaic at real tile widths and
  compared with the repo's plain-XLA reference for it;
- train: ``Model.fit`` for a few steps at B=32, T=1024, then one
  ``optim.fused_adam`` update on the LM's parameter tree;
- serve: ``serving.Engine`` answers seeded requests through ``engine.run``
  on both decode kernels;
- multi-chip: with more than one device, the train leg again under
  ``DataParallel()`` and ``FSDP()`` over all chips.

The exit code is the verdict: no leg is wrapped in a handler that lets the
run continue, so any failed check or exception exits non-zero. Each leg
prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}``. The timings are smoke observations
(compile seconds, steady seconds around ``block_until_ready``), not
benchmark metrics. The legs are functions with size parameters so
``tests/test_chip_smoke.py`` runs them on the CPU sim at a toy size.
"""

from __future__ import annotations

import collections
import importlib.metadata
import json
import math
import os
import re
import sys
import tempfile
import time

# The 136M LM of rounds 1-5 (GPT-2-small shape, 32k rows, untied head).
LM = dict(vocab=32768, num_layers=12, d_model=768, num_heads=12,
          seq_len=1024, batch=32)
# serving.Engine shape of the serve leg (ISSUE 21) — and therefore of the
# paged-attention kernel leg, which must compile what the engine will ask.
SERVE = dict(max_slots=8, block_size=16, max_len=1024)
TRAIN_STEPS = 5
# Multi-chip loss trajectories are compared with the one-chip leg: same seed,
# same batches, bf16 compute, only the reduction order differs.
MULTICHIP_LOSS_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(leg: str, dev: dict, **fields) -> None:
    print(json.dumps({"leg": leg, "device": dev, **fields}), flush=True)


class CacheCounter:
    """Persistent-compile-cache lookups and hits since a snapshot, from the
    compile ledger's counters (``distributed_tpu.obs.compile_ledger``: the
    package registers the one listener when it is imported; ``DTPU_OBS=0``
    turns it off, and this reads zeros)."""

    def snapshot(self):
        from distributed_tpu.obs import default_registry

        reg = default_registry()
        return (int(reg.counter_value("compile/cache_lookups")),
                int(reg.counter_value("compile/cache_hits")))

    def since(self, snap) -> dict:
        requests, hits = self.snapshot()
        return {"cache_requests": requests - snap[0],
                "cache_hits": hits - snap[1]}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _rel_err(got, want) -> float:
    """max|got - want| / max|want| in float32 — one number per comparison,
    scale-free, so a bf16 tolerance reads the same for every kernel.
    Reduced on the device: only the scalar crosses to the host."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
    return float(jnp.max(jnp.abs(got - want)) / scale)


# ------------------------------------------------------------------- gate --
def device_gate() -> dict:
    """Refuse to run anywhere but on a TPU; print what was found."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: jax.default_backend() is {backend!r}, not 'tpu' "
            "— this smoke only runs on the chip (no CPU fallback)"
        )
    import jaxlib

    from distributed_tpu.utils import compile_cache

    d0 = jax.devices()[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    emit("device", dev, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, python=sys.version.split()[0],
         process_count=jax.process_count(),
         compile_cache_dir=compile_cache.enable())
    return dev


# ------------------------------------------------------------------- sync --
def leg_sync(dev: dict, *, n: int = 8192, chain: int = 16) -> None:
    """Does ``block_until_ready`` wait for the device? Time one window of a
    matmul chain of known FLOPs three ways: dispatch only, dispatch +
    ``block_until_ready``, dispatch + a scalar host fetch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def window(x, w):
        for _ in range(chain):
            x = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
                x.dtype)
        return x, jnp.sum(x[:8, :8].astype(jnp.float32))

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, n), jnp.bfloat16)
    w = (jax.random.normal(kw, (n, n), jnp.float32) / math.sqrt(n)).astype(
        jnp.bfloat16)
    jax.block_until_ready(window(x, w))  # compile + warm

    t0 = time.perf_counter()
    out = window(x, w)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(out)
    t_block = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = window(x, w)
    scalar = float(np.asarray(jax.device_get(out[1])))
    t_fetch = time.perf_counter() - t0

    check(math.isfinite(scalar), f"sync window produced {scalar}")
    flops = 2.0 * n * n * n * chain
    # A no-op block_until_ready returns in dispatch time, far under the
    # host-fetch time of the same window.
    waits = t_block >= 0.5 * t_fetch
    emit("sync", dev, dispatch_seconds=round(t_dispatch, 5),
         block_until_ready_seconds=round(t_block, 5),
         host_fetch_seconds=round(t_fetch, 5),
         implied_tflops_block=round(flops / t_block / 1e12, 1),
         implied_tflops_fetch=round(flops / t_fetch / 1e12, 1),
         block_until_ready_waits=waits)
    check(waits, "block_until_ready returned before the device finished "
                 f"({t_block:.4f}s vs host fetch {t_fetch:.4f}s)")


# ---------------------------------------------------------------- kernels --
def _compile_and_run(fn, *args, runs: int = 3):
    """(outputs, compile_seconds, steady_seconds) of jit(fn)(*args)."""
    import jax

    compiled, t_compile = _timed(lambda: jax.jit(fn).lower(*args).compile())
    out = jax.block_until_ready(compiled(*args))  # warm
    t0 = time.perf_counter()
    for _ in range(runs):
        out = jax.block_until_ready(compiled(*args))
    return out, t_compile, (time.perf_counter() - t0) / runs


def kernel_flash(dev: dict, *, batch=4, seq_len=LM["seq_len"],
                 num_heads=LM["num_heads"], head_dim=64, tol=5e-2) -> None:
    """Flash attention forward + backward (the lane-packed path at H=12,
    hd=64) against ``dense_attention`` and its autodiff."""
    import jax
    import jax.numpy as jnp

    from distributed_tpu.ops._pallas_common import packed_supported
    from distributed_tpu.ops.flash_attention import (
        dense_attention, flash_attention)

    shape = (batch, seq_len, num_heads, head_dim)
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    cot = jax.random.normal(kc, shape, jnp.float32)

    def fwd_bwd(attn):
        def f(q, k, v):
            loss = lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * cot)
            return attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return f

    flash = fwd_bwd(lambda q, k, v: flash_attention(q, k, v, causal=True))
    dense = fwd_bwd(lambda q, k, v: dense_attention(q, k, v, True))
    (out, grads), t_compile, t_run = _compile_and_run(flash, q, k, v)
    want_out, want_grads = jax.jit(dense)(q, k, v)
    errs = {"out": _rel_err(out, want_out)}
    for name, g, w in zip(("dq", "dk", "dv"), grads, want_grads):
        errs[name] = _rel_err(g, w)
    emit("kernel:flash", dev, shape=list(shape), dtype="bfloat16",
         packed=packed_supported(num_heads, head_dim),
         compile_seconds=round(t_compile, 3), steady_seconds=round(t_run, 5),
         rel_err={k: round(e, 5) for k, e in errs.items()})
    check(all(e < tol for e in errs.values()),
          f"flash attention disagrees with dense attention: {errs}")


def kernel_xent(dev: dict, *, rows=4096, classes=LM["vocab"],
                tol=2e-2) -> None:
    """Fused softmax cross-entropy forward + backward at C=32768 against the
    stock XLA loss."""
    import jax
    import jax.numpy as jnp

    from distributed_tpu.ops import losses, pallas_kernels

    kl, ky = jax.random.split(jax.random.PRNGKey(2))
    logits = (2.0 * jax.random.normal(kl, (rows, classes))).astype(
        jnp.bfloat16)
    labels = jax.random.randint(ky, (rows,), 0, classes, jnp.int32)

    def fwd_bwd(per_example):
        def f(logits, labels):
            mean = lambda lg: jnp.mean(per_example(lg, labels))
            return per_example(logits, labels), jax.grad(mean)(logits)
        return f

    (loss, dlogits), t_compile, t_run = _compile_and_run(
        fwd_bwd(pallas_kernels.fused_softmax_xent), logits, labels)
    want_loss, want_d = jax.jit(
        fwd_bwd(losses._per_example_sparse_cce))(logits, labels)
    errs = {"loss": _rel_err(loss, want_loss),
            "dlogits": _rel_err(dlogits, want_d)}
    emit("kernel:xent", dev, shape=[rows, classes], dtype="bfloat16",
         loss_path=pallas_kernels.loss_path(classes),
         compile_seconds=round(t_compile, 3), steady_seconds=round(t_run, 5),
         rel_err={k: round(e, 6) for k, e in errs.items()})
    check(pallas_kernels.loss_path(classes) == "fused",
          f"{classes} classes takes the stock loss, not the fused kernel")
    check(all(e < tol for e in errs.values()),
          f"fused cross-entropy disagrees with the stock loss: {errs}")


def _paged_reference(q, k_pool, v_pool, tables, positions):
    """Gather-then-dense attention in float32: what ``paged_attention`` must
    reproduce (the ``_paged_view`` reference path's arithmetic)."""
    import jax
    import jax.numpy as jnp

    from distributed_tpu.quant import dequantize

    if isinstance(k_pool, dict):
        k_pool = dequantize(k_pool, q.dtype)
        v_pool = dequantize(v_pool, q.dtype)
    s, kw, h, hd = q.shape
    k = k_pool[tables].reshape(s, -1, h, hd).astype(jnp.float32)
    v = v_pool[tables].reshape(s, -1, h, hd).astype(jnp.float32)
    col = jnp.arange(k.shape[1])[None, None, :]
    row = (positions[:, None] + jnp.arange(kw)[None, :])[..., None]
    sc = jnp.einsum("skhd,slhd->skhl", q.astype(jnp.float32), k)
    sc = jnp.where((col <= row)[:, :, None, :], sc / math.sqrt(hd), -1e30)
    return jnp.einsum("skhl,slhd->skhd", jax.nn.softmax(sc, axis=-1), v)


def kernel_paged(dev: dict, *, kw: int, int8: bool,
                 slots=SERVE["max_slots"], block_size=SERVE["block_size"],
                 max_len=SERVE["max_len"], num_heads=LM["num_heads"],
                 head_dim=64, tol=5e-2) -> None:
    """Fused paged-attention decode (K=1) / verify (K>1) kernel over bf16 or
    int8 ``{"q","scale"}`` pools, at the serve leg's engine shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tpu.ops.paged_attention import paged_attention
    from distributed_tpu.quant import QKEY, SKEY

    nb = -(-max_len // block_size)
    nblocks = slots * nb + 1
    pool_shape = (nblocks, block_size, num_heads, head_dim)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3 + kw), 3)
    q = jax.random.normal(kq, (slots, kw, num_heads, head_dim), jnp.bfloat16)

    def pool(key):
        x = jax.random.normal(key, pool_shape, jnp.float32)
        if not int8:
            return x.astype(jnp.bfloat16)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return {QKEY: jnp.clip(jnp.round(x / scale), -127, 127).astype(
                    jnp.int8),
                SKEY: scale}

    k_pool, v_pool = pool(kk), pool(kv)
    rng = np.random.default_rng(kw)
    tables = jnp.asarray(
        1 + rng.permutation(slots * nb).reshape(slots, nb), jnp.int32)
    positions = jnp.asarray(
        rng.integers(0, nb * block_size - kw + 1, (slots,)), jnp.int32)
    got, t_compile, t_run = _compile_and_run(
        paged_attention, q, k_pool, v_pool, tables, positions)
    want = jax.jit(_paged_reference)(q, k_pool, v_pool, tables, positions)
    err = _rel_err(got, want)
    emit(f"kernel:paged_attention:k{kw}:{'int8' if int8 else 'bf16'}", dev,
         q_shape=list(q.shape), pool_shape=list(pool_shape),
         table_width=nb, compile_seconds=round(t_compile, 3),
         steady_seconds=round(t_run, 5), rel_err=round(err, 5))
    check(err < tol, f"paged_attention(kw={kw}, int8={int8}) disagrees with "
                     f"the gathered reference: rel err {err}")


def leg_kernels(dev: dict) -> None:
    kernel_flash(dev)
    kernel_xent(dev)
    for kw in (1, 4):
        for int8 in (False, True):
            kernel_paged(dev, kw=kw, int8=int8)


def kernel_fused_adam(dev: dict, params, *, tol=1e-5) -> None:
    """One ``optim.fused_adam`` update on a real parameter tree against the
    stock optax Adam on the same inputs."""
    import jax

    import distributed_tpu as dtpu

    grads = jax.tree_util.tree_map(lambda p: 0.5 * p + 0.01, params)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))

    def update(tx):
        def f(grads, params):
            updates, _ = tx.update(grads, tx.init(params), params)
            return updates
        return f

    got, t_compile, t_run = _compile_and_run(
        update(dtpu.optim.fused_adam(1e-3)), grads, params, runs=1)
    want = jax.jit(update(dtpu.optim.Adam(1e-3)))(grads, params)
    err = max(
        _rel_err(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                       jax.tree_util.tree_leaves(want))
    )
    emit("kernel:fused_adam", dev, params=int(n_params),
         leaves=len(jax.tree_util.tree_leaves(params)),
         compile_seconds=round(t_compile, 3), steady_seconds=round(t_run, 5),
         rel_err=float(f"{err:.3g}"))
    check(err < tol, f"fused_adam disagrees with optax adam: rel err {err}")


# ------------------------------------------------------------------ train --
def mosaic_calls(compiled_hlo: str) -> list:
    """``(kernel name, first-operand dims)`` of every Mosaic custom call in
    a compiled program's HLO text. The compiled module is the per-device
    program, so under a mesh the dims are the per-chip shard's."""
    calls = []
    for line in compiled_hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*?(dtpu_[a-z0-9_]+)', line)
        dims = re.search(r"operand_layout_constraints=\{\w+\[([0-9,]+)\]",
                         line)
        calls.append((
            name.group(1) if name else "?",
            [int(d) for d in dims.group(1).split(",")] if dims else [],
        ))
    return calls


def build_lm(cfg: dict, strategy=None):
    """The compiled (not yet built) LM of ``cfg`` under ``strategy``."""
    import jax.numpy as jnp

    import distributed_tpu as dtpu

    with (strategy or dtpu.SingleDevice()).scope():
        model = dtpu.Model(dtpu.models.transformer_lm(
            cfg["vocab"], num_layers=cfg["num_layers"],
            d_model=cfg["d_model"], num_heads=cfg["num_heads"],
            max_len=cfg["seq_len"], dtype=jnp.bfloat16,
        ))
        model.compile(
            optimizer=dtpu.optim.Adam(1e-4),
            loss="pallas_sparse_categorical_crossentropy",
            metrics=["accuracy"],
        )
    return model


def leg_train(dev: dict, cfg: dict, cache: CacheCounter, *, name="train",
              strategy=None, steps=TRAIN_STEPS, require_mosaic=True):
    """``Model.fit`` for ``steps`` steps on seeded tokens. Returns
    ``(model, losses, the compiled step's Mosaic calls)``."""
    import jax
    import numpy as np

    import distributed_tpu as dtpu
    from distributed_tpu.ops import pallas_kernels

    check(steps >= 3, "the train leg needs a compile step and a steady window")
    batch, seq_len, vocab = cfg["batch"], cfg["seq_len"], cfg["vocab"]
    tok = np.random.default_rng(0).integers(
        0, vocab, (batch * steps, seq_len + 1)).astype(np.int32)
    x, y = tok[:, :-1], tok[:, 1:]
    snap = cache.snapshot()
    model = build_lm(cfg, strategy)

    losses, stamps = [], [time.perf_counter()]

    def on_batch_end(model, step, logs):
        losses.append(float(jax.block_until_ready(logs["loss"])))
        stamps.append(time.perf_counter())

    model.fit(x, y, batch_size=batch, epochs=1, steps_per_epoch=steps,
              shuffle=False, seed=0, verbose=0,
              callbacks=[dtpu.callbacks.LambdaCallback(
                  on_batch_end=on_batch_end)])
    step_seconds = np.diff(stamps)
    steady = float(np.median(step_seconds[2:]))
    fit_cache = cache.since(snap)

    check(len(losses) == steps, f"fit ran {len(losses)} of {steps} steps")
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(vocab)) < 0.2,
          f"first-step loss {losses[0]:.4f} is not ln({vocab}) = "
          f"{math.log(vocab):.4f}")
    for tree_name in ("params", "opt_state"):
        for leaf in jax.tree_util.tree_leaves(getattr(model, tree_name)):
            check(all(d.platform == dev["platform"] for d in leaf.devices()),
                  f"{tree_name} leaf lives on {leaf.devices()}, not on "
                  f"{dev['platform']} devices")

    # The program fit ran, lowered and compiled again — a persistent-cache
    # hit — to read its HLO: every Mosaic kernel by name, with the shapes
    # the device actually sees.
    snap = cache.snapshot()
    compiled, t_recompile = _timed(
        model.lower_train_step(x[:batch], y[:batch]).compile)
    recompile_cache = cache.since(snap)
    calls = mosaic_calls(compiled.as_text())
    kinds = dict(collections.Counter(kernel for kernel, _ in calls))
    emit(name, dev, strategy=type(model.strategy).__name__,
         params=model.num_params, batch=batch, seq_len=seq_len,
         steps=steps, losses=[round(v, 4) for v in losses],
         loss_path=pallas_kernels.loss_path(vocab),
         compile_seconds=round(float(step_seconds[0]) - steady, 3),
         steady_seconds=round(steady, 5),
         step_seconds=[round(float(s), 4) for s in step_seconds],
         fit_cache=fit_cache, recompile_seconds=round(t_recompile, 3),
         recompile_cache=recompile_cache, mosaic_kernels=kinds)
    if require_mosaic:
        # At the real size fit's compile takes far longer than the cache's
        # one-second entry threshold, so the same program must be found.
        check(recompile_cache["cache_hits"] == 1,
              "the train step fit compiled was not found again in the "
              f"persistent compile cache: {recompile_cache}")
        for prefix in ("dtpu_flash_fwd", "dtpu_flash_dq", "dtpu_flash_dkv",
                       "dtpu_xent_fwd", "dtpu_xent_bwd"):
            check(any(k.startswith(prefix) for k in kinds),
                  f"no {prefix}* Mosaic custom call in the compiled train "
                  f"step: {kinds}")
    return model, losses, calls


# ------------------------------------------------------------------ serve --
def serve_requests(vocab: int, max_len: int, n: int = 12):
    """Seeded (prompt, max_new_tokens) pairs of mixed length; prompt lengths
    fall into three of the engine's 64-wide prefill buckets."""
    import numpy as np

    rng = np.random.default_rng(7)
    max_new = 32
    reqs = []
    for i in range(n):
        lo, hi = ((4, 64), (65, 128), (200, 256))[i % 3]
        hi = min(hi, max_len - max_new - 1)
        plen = int(rng.integers(min(lo, hi), hi + 1))
        new = int(rng.integers(4, max_new + 1))
        reqs.append((rng.integers(0, vocab, (plen,)).astype(np.int32), new))
    return reqs


def leg_serve(dev: dict, model, cfg: dict, cache: CacheCounter, *,
              serve=SERVE, n_requests=12, interpret=False) -> None:
    """``serving.Engine`` over the trained model answers the same seeded
    requests on the reference and the fused decode kernel."""
    import numpy as np

    from distributed_tpu import serving
    from distributed_tpu.utils import event_schema, events

    reqs = serve_requests(cfg["vocab"], serve["max_len"], n_requests)
    outputs = {}
    with tempfile.TemporaryDirectory() as tmp:
        log_path = os.path.join(tmp, "events.jsonl")
        prev_log = os.environ.get(events.ENV_VAR)
        os.environ[events.ENV_VAR] = log_path
        try:
            for kind in ("reference", "fused"):
                snap = cache.snapshot()
                engine = serving.Engine(model, **serve, decode_kernel=kind)
                outs, t_cold = _timed(lambda: engine.run(reqs))
                outs, t_warm = _timed(lambda: engine.run(reqs))
                outputs[kind] = outs
                generated = 0
                for (prompt, new), out in zip(reqs, outs):
                    out = np.asarray(out)
                    check(out.shape == (prompt.size + new,),
                          f"{kind}: asked {prompt.size}+{new} tokens, got "
                          f"{out.shape}")
                    check(np.array_equal(out[:prompt.size], prompt),
                          f"{kind}: the prompt did not come back unchanged")
                    check(bool(np.all((out >= 0) & (out < cfg["vocab"]))),
                          f"{kind}: token outside the vocabulary")
                    generated += new
                tel = engine.last_run_telemetry
                emit(f"serve:{kind}", dev, requests=len(reqs),
                     generated_tokens=generated, **serve,
                     compile_seconds=round(t_cold - t_warm, 3),
                     steady_seconds=round(t_warm, 4),
                     request_seconds=round(t_warm / len(reqs), 5),
                     decode_steps=tel.get("decode_steps"),
                     **cache.since(snap))
        finally:
            if prev_log is None:
                del os.environ[events.ENV_VAR]
            else:
                os.environ[events.ENV_VAR] = prev_log
        selected = [e for e in events.read_events(log_path)
                    if e["event"] == event_schema.DECODE_KERNEL_SELECTED]
    check([e["kernel"] for e in selected] == ["reference", "fused"],
          f"decode_kernel_selected events: {selected}")
    for e in selected:
        check(e["interpret"] is interpret and e["backend"] == dev["platform"],
              f"decode kernel event says {e}, expected interpret={interpret} "
              f"on {dev['platform']}")
    same = [bool(np.array_equal(a, b))
            for a, b in zip(outputs["reference"], outputs["fused"])]
    tok_same = sum(
        int(np.sum(np.asarray(a)[p.size:] == np.asarray(b)[p.size:]))
        for (p, _), a, b in zip(reqs, outputs["reference"],
                                outputs["fused"]))
    tok_all = sum(new for _, new in reqs)
    # Reported, not asserted: token-exactness is pinned on CPU in f32; in
    # bf16 on the MXU the two paths may legitimately round a near-tie apart.
    emit("serve:agreement", dev, requests_token_exact=sum(same),
         requests=len(reqs), tokens_equal=tok_same, tokens=tok_all)


# ------------------------------------------------------------- multi-chip --
def leg_multichip(dev: dict, cfg: dict, cache: CacheCounter, ref_losses,
                  *, steps=TRAIN_STEPS, require_mosaic=True) -> None:
    """The train leg under ``DataParallel()`` and ``FSDP()`` over every
    device: shards and memory on every chip, the one-chip loss trajectory,
    and Mosaic custom calls that see the per-chip batch."""
    import jax

    import distributed_tpu as dtpu

    devices = jax.devices()
    n = len(devices)
    if n == 1:
        print("multi-chip: skipped (1 device)", flush=True)
        return
    check(cfg["batch"] % n == 0, f"batch {cfg['batch']} over {n} devices")
    for make in (dtpu.DataParallel, dtpu.FSDP):
        strategy = make()
        name = f"multichip:{make.__name__}"
        check(set(strategy.mesh.devices.flat) == set(devices),
              f"{name} mesh covers {strategy.mesh.devices.size} of {n} "
              "devices")
        model, losses, calls = leg_train(
            dev, cfg, cache, name=name, strategy=strategy, steps=steps,
            require_mosaic=require_mosaic)
        leaves = jax.tree_util.tree_leaves(model.params)
        for leaf in leaves:
            check({s.device for s in leaf.addressable_shards} == set(devices),
                  f"{name}: a param leaf holds shards on "
                  f"{len(leaf.addressable_shards)} of {n} devices")
        sharded = sum(
            leaf.addressable_shards[0].data.shape != leaf.shape
            for leaf in leaves)
        if make is dtpu.FSDP:
            check(sharded > 0, "FSDP left every parameter replicated")
        else:
            check(sharded == 0, "DataParallel sharded a parameter")
        in_use = []
        for d in devices:
            stats = d.memory_stats()
            if stats is not None:  # XLA:CPU (the test sim) reports none
                check(stats["bytes_in_use"] > 0, f"{d} holds no memory")
                in_use.append(int(stats["bytes_in_use"]))
        check(bool(in_use) or dev["platform"] == "cpu",
              "no device reports memory_stats()")
        drift = max(abs(a - b) for a, b in zip(losses, ref_losses))
        check(drift < MULTICHIP_LOSS_TOL,
              f"{name} losses {losses} drift {drift:.4f} from the one-chip "
              f"leg {ref_losses} (tolerance {MULTICHIP_LOSS_TOL})")
        per_chip = cfg["batch"] // n
        if require_mosaic:
            check(bool(calls), f"{name}: no Mosaic custom call lowered")
        for kernel, dims in calls:
            rows = per_chip * (cfg["seq_len"] if "xent" in kernel else 1)
            check(dims[0] == rows,
                  f"{name}: {kernel} sees leading dim {dims[0]}, not the "
                  f"per-chip {rows} (global batch {cfg['batch']} over {n})")
        emit(f"{name}:checks", dev, devices=n, sharded_param_leaves=sharded,
             param_leaves=len(leaves), bytes_in_use=in_use,
             max_loss_drift=round(drift, 5), loss_tolerance=MULTICHIP_LOSS_TOL,
             per_chip_batch=per_chip,
             custom_call_leading_dims=sorted({(k, d[0]) for k, d in calls}))
        del model


# ------------------------------------------------------------------- main --
def run(dev: dict) -> None:
    cache = CacheCounter()
    leg_sync(dev)
    leg_kernels(dev)
    model, losses, _ = leg_train(dev, LM, cache)
    kernel_fused_adam(dev, model.params)
    leg_serve(dev, model, LM, cache)
    del model
    leg_multichip(dev, LM, cache, losses)


def main() -> int:
    dev = device_gate()
    run(dev)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
