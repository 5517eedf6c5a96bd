"""Serving runtime: continuous batching, paged KV cache, prefill/decode.

The decisive test is greedy-parity: every request served through the
engine — whatever the batch composition, block size, prefill chunking, or
preemption pressure around it — must produce exactly the tokens a
sequential per-request ``generate()`` produces. That pins the paged
attention read/write path, the per-slot position masking, the
prefill/decode handoff, and the scheduler's bookkeeping all at once.

Kept lean (tier-1 runs on a 1-core box): one tiny LM fixture shared
across the module, and each property tested at the smallest shape that
can catch its failure mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_recompile

import distributed_tpu as dtpu
from distributed_tpu.serving import (
    BlockAllocator, Engine, PagedKVCache, Request,
)


@pytest.fixture(scope="module")
def lm():
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=64))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((16,))
    return model


def _requests(seed=0, n=3, vocab=32, p_range=(1, 9), m_range=(3, 9)):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (int(t),)).astype(np.int32)
               for t in rng.integers(*p_range, n)]
    news = [int(m) for m in rng.integers(*m_range, n)]
    return prompts, news


def _sequential_generate(model, prompts, news):
    return [model.generate(p[None], m, temperature=0.0)[0]
            for p, m in zip(prompts, news)]


# ------------------------------------------------------------------ parity --
def test_continuous_batching_matches_sequential_generate(lm):
    """More requests than slots, heterogeneous prompt/response lengths:
    admit-mid-decode (a finished sequence's slot is refilled while others
    keep decoding) must leave every request's greedy tokens identical to
    its solo generate()."""
    prompts, news = _requests(seed=0, n=5)
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    t = engine.last_run_telemetry
    # 5 requests over 2 slots: later requests were admitted mid-decode.
    assert t["prefill_dispatches"] == 5
    assert t["decode_steps"] >= max(news) - 1
    assert 0.0 < t["kv_utilization"]["peak"] <= 1.0


def test_freed_slots_refill_before_the_batch_drains(lm):
    """What continuous batching is for, as a count: a static-batch server
    takes requests two at a time and decodes each pair until its longer
    answer is done, 12 + 12 + 2 steps here. The engine hands a finished
    sequence's slot to the next request, so the same work fits in fewer
    decode dispatches, though never fewer than the tokens over the
    slots."""
    rng = np.random.default_rng(5)
    news = [12, 2, 2, 12, 2, 2]
    prompts = [rng.integers(0, 32, (3,)).astype(np.int32) for _ in news]
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64)
    engine.run([Request(p, m) for p, m in zip(prompts, news)])
    steps = engine.last_run_telemetry["decode_steps"]
    static = sum(max(news[i:i + 2]) for i in range(0, len(news), 2))
    # a request's first token comes from its prefill
    floor = -(-sum(m - 1 for m in news) // 2)
    assert floor <= steps < static - len(news) // 2, (steps, static)


# @slow (tier-1 budget, PR 10): 11s; run it with -m slow when touching
# prefill.
@pytest.mark.slow
def test_prefill_chunking_matches_whole_prompt(lm):
    """The prefill/decode split at its sharpest: a chunked prefill (chunks
    attending to earlier chunks through the pool) must equal both the
    one-dispatch prefill and sequential generate()."""
    prompts = [np.arange(1, 14, dtype=np.int32) % 31]  # 13 tokens
    news = [6]
    want = _sequential_generate(lm, prompts, news)
    for chunk in (None, 4, 5):
        engine = Engine(lm, max_slots=1, block_size=4, max_len=64,
                        prefill_chunk=chunk)
        got = engine.run([Request(prompts[0], news[0])])
        np.testing.assert_array_equal(want[0], got[0],
                                      err_msg=f"prefill_chunk={chunk}")


def test_preemption_under_pool_pressure_keeps_parity(lm):
    """A pool too small for both runners forces a mid-decode preemption
    (youngest evicted, re-prefilled later); tokens must still match."""
    prompts, news = _requests(seed=3, n=2, p_range=(3, 5),
                              m_range=(24, 26))
    want = _sequential_generate(lm, prompts, news)
    # Each sequence needs up to ceil(30/4) = 8 blocks; 11 allocatable.
    engine = Engine(lm, max_slots=2, block_size=4, max_len=32,
                    num_blocks=12)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert engine.last_run_telemetry["preemptions"] >= 1
    assert engine.kv.live_blocks == 0  # everything returned to the pool


def test_eos_stops_a_sequence_early(lm):
    prompts, news = _requests(seed=1, n=1, m_range=(8, 9))
    full = _sequential_generate(lm, prompts, news)[0]
    t_p = prompts[0].size
    eos = int(full[t_p + 2])  # third generated token
    engine = Engine(lm, max_slots=1, block_size=4, max_len=64, eos_id=eos)
    out = engine.run([Request(prompts[0], news[0])])[0]
    # Stops at (and includes) the FIRST eos occurrence.
    stop = int(np.argmax(full[t_p:] == eos))
    np.testing.assert_array_equal(out, full[: t_p + stop + 1])


def test_engine_per_request_lifecycle_rows(lm):
    """The telemetry the fleet router/autoscaler consume: per-request
    lifecycle timestamps, tail TTFT percentiles, and live queue/pool
    signals — not just run-level means."""
    prompts, news = _requests(seed=5, n=5)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64)
    assert engine.queue_depth == 0  # idle: live signals read clean
    assert engine.free_blocks == engine.kv.allocator.num_allocatable
    engine.run([Request(p, m) for p, m in zip(prompts, news)])
    t = engine.last_run_telemetry
    rows = t["requests"]
    assert len(rows) == 5
    for row in rows:
        assert row["enqueued_s"] <= row["admitted_s"] <= \
            row["first_token_s"] <= row["finished_s"]
    ttft = t["time_to_first_token"]
    assert ttft["p50"] <= ttft["p99"] <= ttft["max"]
    assert ttft["mean"] > 0
    # 5 requests over 2 slots: a queue existed at some decode step.
    assert t["queue_depth"]["peak"] >= 1
    assert 0 <= t["free_blocks_min"] <= engine.kv.allocator.num_allocatable
    assert engine.free_blocks == engine.kv.allocator.num_allocatable


# ------------------------------------------------------- block accounting --
def test_block_allocator_accounting():
    alloc = BlockAllocator(8)  # block 0 reserved: 7 allocatable
    assert alloc.num_allocatable == 7
    a = alloc.allocate(3)
    b = alloc.allocate(4)
    assert len(a) == 3 and len(b) == 4 and not (set(a) & set(b))
    assert 0 not in a + b  # the trash block is never granted
    assert alloc.allocate(1) is None  # exhausted: all-or-nothing
    assert alloc.utilization() == 1.0
    alloc.free(a)
    assert alloc.num_free == 3
    assert alloc.utilization() == pytest.approx(4 / 7)
    with pytest.raises(ValueError, match="double free"):
        alloc.free([a[0]])
    c = alloc.allocate(3)
    assert sorted(c) == sorted(a)  # freed blocks are reused


def test_paged_cache_reserve_release_no_leaks(lm):
    kv = PagedKVCache(lm.module, lm.params, max_slots=2, block_size=4,
                      max_blocks_per_seq=5, num_blocks=8,
                      dtype=jnp.float32)
    assert kv.reserve(0, 5)  # 2 blocks
    assert kv.reserve(0, 6)  # still 2: no-op growth
    assert kv.reserve(1, 9)  # 3 blocks
    assert kv.live_blocks == 5 and kv.allocator.num_free == 2
    assert kv.utilization() == pytest.approx(5 / 7)
    # Slot 0 asking for 5 blocks total = 3 more; only 2 free: all-or-
    # nothing refusal, and the partial grant must NOT have happened.
    assert not kv.reserve(0, 20)
    assert kv.live_blocks == 5 and kv.allocator.num_free == 2
    kv.release(1)
    assert kv.live_blocks == 2 and (kv.block_tables[1] == 0).all()
    assert kv.positions[1] == 0
    assert kv.reserve(0, 20)  # now it fits
    kv.release(0)
    assert kv.live_blocks == 0 and kv.allocator.num_free == 7
    with pytest.raises(ValueError, match="per-sequence cap"):
        kv.reserve(0, 21)


def test_engine_rejects_oversized_and_impossible_requests(lm):
    engine = Engine(lm, max_slots=1, block_size=4, max_len=16)
    with pytest.raises(ValueError, match="max_len"):
        engine.run([Request(np.arange(10, dtype=np.int32) % 31, 12)])
    # Context that fits max_len but not the (tiny) pool: loud, not a hang.
    small = Engine(lm, max_slots=1, block_size=4, max_len=32, num_blocks=3)
    with pytest.raises(RuntimeError, match="pool"):
        small.run([Request(np.arange(20, dtype=np.int32) % 31, 4)])
    with pytest.raises(ValueError, match="max_len"):
        # Engine cap above the model's positional table must fail at
        # construction, not silently clamp rows mid-serve.
        Engine(lm, max_slots=1, block_size=4, max_len=128)


# ------------------------------------------------------------- precision --
def test_kv_cache_dtype_follows_precision_policy():
    """The paged pool dtype derives from the PR 5 policy exactly like
    generate()'s dense cache (Model.decode_dtype)."""
    def build(precision):
        m = dtpu.Model(dtpu.models.transformer_lm(
            32, num_layers=1, d_model=16, num_heads=2, max_len=32))
        m.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy",
                  precision=precision)
        m.build((16,))
        return m

    m32 = build(None)
    e32 = Engine(m32, max_slots=1, block_size=4, max_len=32)
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(e32.kv.caches))

    mbf = build("mixed_bfloat16")
    ebf = Engine(mbf, max_slots=1, block_size=4, max_len=32)
    assert all(l.dtype == jnp.bfloat16
               for l in jax.tree_util.tree_leaves(ebf.kv.caches))
    # And the policy engine still serves end-to-end.
    out = ebf.run([Request(np.array([1, 2, 3], np.int32), 3)])[0]
    assert out.shape == (6,) and out.dtype == np.int32


# ------------------------------------------- logprobs, RNG, weight swaps --
@pytest.fixture(scope="module")
def sampler(lm):
    """One shared SAMPLING engine (temperature 1): every fresh Engine
    pays its own prefill/decode compile, so the logprob/RNG tests reuse
    this one — per-request seeds make their streams independent anyway
    (that independence is exactly what the tests pin)."""
    return Engine(lm, max_slots=2, block_size=4, max_len=64,
                  temperature=1.0, seed=5)


def test_logprob_capture_rides_fixed_dispatch_no_recompile(lm, sampler):
    """return_logprobs toggling is pure host bookkeeping: the logprobs
    are computed inside the fixed-shape dispatches either way, so the
    decode/prefill jit caches must not grow across the toggle — and the
    captured values must equal teacher-forced log-softmax scores of the
    served tokens (the trainer's recomputation, see rl.PostTrainer)."""
    prompts, news = _requests(seed=7, n=2, m_range=(4, 6))
    reqs = lambda: [Request(p, m, seed=i)
                    for i, (p, m) in enumerate(zip(prompts, news))]
    outs = sampler.run(reqs(), return_logprobs=True)
    rows_by_order = sampler.last_run_telemetry["requests"]  # submit order
    # Teacher-force both served rows in ONE padded predict (one compile):
    # captured logprob == log_softmax of the model's logits at the
    # sampled token (temperature 1).
    pad_to = max(o.size for o in outs)
    batch = np.zeros((len(outs), pad_to - 1), np.int32)
    for i, o in enumerate(outs):
        batch[i, : o.size - 1] = o[:-1]
    logits = lm.predict(batch, batch_size=len(outs))
    refs = jax.nn.log_softmax(jnp.asarray(logits, jnp.float32), -1)
    for i, (p, out, row) in enumerate(zip(prompts, outs, rows_by_order)):
        lps = row["logprobs"]
        assert len(lps) == out.size - p.size
        for t in range(p.size - 1, out.size - 1):
            want = float(refs[i, t, out[t + 1]])
            got = lps[t - (p.size - 1)]
            assert abs(want - got) < 1e-4, (t, want, got)
    # Toggling capture OFF reuses the exact same compiled programs.
    with assert_no_recompile(sampler._decode_jit, sampler._prefill_jit):
        sampler.run(reqs())
    assert "logprobs" not in sampler.last_run_telemetry["requests"][0]


def test_sampled_decode_deterministic_across_slots_and_runs(lm, sampler):
    """The serving analogue of the greedy token-exact discipline: with
    per-request seeds, sampled rollouts are bit-identical across engine
    shapes (a different max_slots changes scheduling entirely), across
    repeat runs, and sensitive to the request seed (distinct streams)."""
    prompts, news = _requests(seed=9, n=4, m_range=(5, 8))

    def serve(engine, base_seed=100):
        return engine.run([Request(p, m, seed=base_seed + i)
                           for i, (p, m) in enumerate(zip(prompts, news))])

    narrow = Engine(lm, max_slots=1, block_size=4, max_len=64,
                    temperature=1.0, seed=5)
    a, b, c = serve(narrow), serve(sampler), serve(sampler)
    for i, (x, y, z) in enumerate(zip(a, b, c)):
        np.testing.assert_array_equal(x, y, err_msg=f"slots 1 vs 2, req {i}")
        np.testing.assert_array_equal(y, z, err_msg=f"rerun, req {i}")
    # Different request seeds are different sampling streams.
    d = serve(sampler, base_seed=900)
    assert any(not np.array_equal(x, y) for x, y in zip(a, d))


def test_update_weights_staleness_contract(lm):
    """A sequence straddling a hot-swap keeps its KV and finishes, with
    the weights_version boundary recorded per token row. Swapping in
    value-identical params mid-run must leave greedy tokens exactly equal
    to the unswapped run (KV retained, no hidden reset); the jit cache
    must not grow (same shapes/dtypes => no retrace)."""
    prompts, news = _requests(seed=4, n=1, p_range=(4, 5), m_range=(8, 9))
    engine = Engine(lm, max_slots=1, block_size=4, max_len=64)
    base = engine.run([Request(prompts[0], news[0])])[0]
    same = jax.tree_util.tree_map(lambda a: a, lm.params)

    def swap(eng, step):
        if step == 3:
            eng.update_weights(same)

    with assert_no_recompile(engine._decode_jit):
        out = engine.run([Request(prompts[0], news[0])],
                         on_decode_step=swap)[0]
    np.testing.assert_array_equal(base, out)
    row = engine.last_run_telemetry["requests"][0]
    # Prefill token + 3 decode tokens under v0, the rest under v1.
    assert row["weights_versions"] == [
        {"version": 0, "tokens": 4},
        {"version": 1, "tokens": news[0] - 4},
    ]
    assert engine.last_run_telemetry["weight_swaps"] == 1
    assert engine.weights_version == 1
    assert engine.kv.live_blocks == 0  # the straddler finished cleanly
    # Genuinely new weights mid-run: sequence still completes, and the
    # engine keeps serving them (version sticks) on the next run.
    bumped = jax.tree_util.tree_map(
        lambda a: a + 0.05 * jnp.ones_like(a), lm.params
    )

    def swap2(eng, step):
        if step == 2:
            eng.update_weights(bumped)

    out2 = engine.run([Request(prompts[0], news[0])], on_decode_step=swap2)[0]
    assert out2.shape == base.shape
    assert engine.weights_version == 2
    after = engine.run([Request(prompts[0], news[0])])[0]
    spans = engine.last_run_telemetry["requests"][0]["weights_versions"]
    assert spans == [{"version": 2, "tokens": news[0]}]
    assert not np.array_equal(after, base)  # bumped weights really serve


def test_update_weights_validates_loudly(lm):
    engine = Engine(lm, max_slots=1, block_size=4, max_len=64)
    with pytest.raises(ValueError, match="structure"):
        engine.update_weights({"bogus": np.zeros((2, 2), np.float32)})
    wrong_shape = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape + (1,), np.float32), lm.params
    )
    with pytest.raises(ValueError, match="shape mismatch"):
        engine.update_weights(wrong_shape)
    wrong_dtype = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, np.float16), lm.params
    )
    with pytest.raises(ValueError, match="dtype mismatch"):
        engine.update_weights(wrong_dtype)
    assert engine.weights_version == 0  # failed swaps change nothing


# -------------------------------------------------- stacked-block serving --
@pytest.fixture(scope="module")
def scanned_lm():
    """ScannedBlocks LM: one weight-stacked block, paged pools carried
    under the reserved 'stacked' key with a leading (S, ...) stage dim."""
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=3, d_model=16, num_heads=2, max_len=64, scan=True))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((16,))
    return model


def test_scanned_stack_paged_parity_and_batch_churn(scanned_lm):
    """The tentpole's serving leg: a ScannedBlocks LM served through the
    paged engine is token-exact against its own dense generate(), and a
    second run with a different batch composition reuses the exact same
    compiled prefill/decode programs (the stacked pool rides the fixed
    dispatch shapes)."""
    prompts, news = _requests(seed=11, n=4)
    want = _sequential_generate(scanned_lm, prompts, news)
    engine = Engine(scanned_lm, max_slots=2, block_size=4, max_len=64)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert engine.kv.live_blocks == 0
    # Batch churn: different request count/lengths, zero new compiles.
    prompts2, news2 = _requests(seed=12, n=3, p_range=(2, 7),
                                m_range=(4, 8))
    want2 = _sequential_generate(scanned_lm, prompts2, news2)
    with assert_no_recompile(engine._decode_jit, engine._prefill_jit):
        got2 = engine.run([Request(p, m)
                           for p, m in zip(prompts2, news2)])
    for w, g in zip(want2, got2):
        np.testing.assert_array_equal(w, g)


def test_scanned_stack_composes_fused_and_prefix(scanned_lm):
    """PR 18's fused decode kernel and PR 16's prefix cache both reach
    the stacked pool through the same hooks: parity must hold with the
    fused kernel selected, and again with the prefix store sharing a
    common prompt head across requests."""
    rng = np.random.default_rng(5)
    common = rng.integers(0, 31, (16,)).astype(np.int32)
    prompts = [np.concatenate([common, np.array([t], np.int32)])
               for t in (3, 9, 17, 26)]
    news = [6, 7, 5, 6]
    want = _sequential_generate(scanned_lm, prompts, news)
    fused = Engine(scanned_lm, max_slots=2, block_size=4, max_len=64,
                   decode_kernel="fused")
    got = fused.run([Request(p, m) for p, m in zip(prompts, news)])
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    both = Engine(scanned_lm, max_slots=2, block_size=4, max_len=64,
                  decode_kernel="fused", prefix_cache=True)
    got2 = both.run([Request(p, m) for p, m in zip(prompts, news)])
    for w, g in zip(want, got2):
        np.testing.assert_array_equal(w, g)
    # The waves behind the first two slots re-read the shared 16-token
    # head (4 full blocks) from the store instead of recomputing it.
    rep = both.last_run_telemetry["prefix_cache"]
    assert rep["hit_blocks"] > 0 and rep["hit_tokens"] > 0


def test_pipelined_blocks_serve_paged_off_pipe_mesh():
    """PipelinedBlocks serves through the same stacked hooks on its
    sequential single-device path — training topology (pipe mesh) and
    serving topology are independent choices."""
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=64,
        pipeline=True))
    model.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy")
    model.build((16,))
    prompts, news = _requests(seed=13, n=2)
    want = _sequential_generate(model, prompts, news)
    engine = Engine(model, max_slots=2, block_size=4, max_len=64)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_pipelined_paged_on_live_pipe_mesh_raises(devices):
    """On a live pipe mesh the paged pool would split across ranks while
    the allocator/prefix state assumes one address space — a loud raise,
    not a silent gather."""
    from distributed_tpu import nn

    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=64,
        pipeline=True))
    model.compile(optimizer="adam",
                  loss="sparse_categorical_crossentropy")
    model.build((16,))
    pb = next(l for l in model.module.layers
              if isinstance(l, nn.PipelinedBlocks))

    def subtree(p):  # the layer's own params ({"blocks": ...})
        if isinstance(p, dict):
            if "blocks" in p:
                return p
            for v in p.values():
                found = subtree(v)
                if found is not None:
                    return found
        return None

    strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
    with strategy.scope():
        with pytest.raises(NotImplementedError, match="single-device"):
            pb.init_paged_cache(subtree(model.params), 8, 4, jnp.float32)
        with pytest.raises(NotImplementedError, match="single-device"):
            pb.paged_decode(subtree(model.params), {}, {},
                            jnp.zeros((1, 1, 16)),
                            block_tables=jnp.zeros((1, 8), jnp.int32),
                            positions=jnp.zeros((1,), jnp.int32))
