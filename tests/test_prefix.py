"""Serving memory economy: prefix KV sharing, int8 KV, speculative decode.

Three levers, one correctness bar: the engine's greedy token stream must
stay EXACTLY ``generate()``'s whatever blocks are shared (prefix store,
copy-on-write), however the verify dispatch batches candidates
(speculative decoding), and across preemption/requeue and weight swaps.
int8 KV is the one deliberate exception — quantized storage is
fidelity-GATED, not bit-exact, and its test pins the agreement level and
the byte ratio instead.

Kept lean (tier-1 runs on a 1-core box): one tiny LM fixture shared
across the module, every property at the smallest shape that can catch
its failure mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_no_recompile

import distributed_tpu as dtpu
from distributed_tpu.serving import Engine, PagedKVCache, Request
from distributed_tpu.serving.kv_cache import (
    BlockAllocator, PrefixStore, _chain_hashes,
)


@pytest.fixture(scope="module")
def lm():
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=64))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((16,))
    return model


@pytest.fixture(scope="module")
def draft_lm(lm):
    """A 1-layer draft with the target's embedding/head: cheap, wrong
    often — exactly what the exactness contract must survive."""
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=1, d_model=16, num_heads=2, max_len=64))
    model.build((16,))
    for name in ("embedding", "positional_embedding", "dense",
                 "layer_norm"):
        if name in model.params and name in lm.params:
            model.params[name] = lm.params[name]
    return model


def _shared_prefix_requests(rng, shared_len=16, n=4, tail=(1, 5),
                            news=(4, 8), vocab=32):
    shared = rng.integers(0, vocab, (shared_len,)).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(
            0, vocab, (int(t),)).astype(np.int32)])
        for t in rng.integers(*tail, n)
    ]
    return prompts, [int(m) for m in rng.integers(*news, n)]


def _sequential_generate(model, prompts, news):
    return [model.generate(p[None], m, temperature=0.0)[0]
            for p, m in zip(prompts, news)]


# ------------------------------------------------------------- allocator --
def test_allocator_refcounts_and_loud_misuse():
    """allocate -> refcount 1; incref/decref move it; ``free`` refuses
    both double-frees and shared blocks (a freed-while-shared block
    would hand storage still being read to the next allocation)."""
    a = BlockAllocator(8)
    (b,) = a.allocate(1)
    assert a.refcount(b) == 1
    a.incref([b])
    assert a.refcount(b) == 2
    with pytest.raises(ValueError, match="shared block"):
        a.free([b])
    assert a.decref([b]) == 0  # drops to refcount 1, nothing freed
    assert a.decref([b]) == 1  # frees
    with pytest.raises(ValueError, match="double free"):
        a.decref([b])
    with pytest.raises(ValueError, match="double free"):
        a.free([b])
    with pytest.raises(ValueError, match="unallocated"):
        a.incref([b])


def test_chain_hashes_prefix_property():
    toks = list(range(20))
    h8 = _chain_hashes(toks, 8)
    assert len(h8) == 2  # full blocks only
    # Chain keys: block i's key names the WHOLE prefix through block i.
    assert _chain_hashes(toks[:16], 8) == h8
    assert _chain_hashes(toks[:8] + [99] * 8, 8)[1] != h8[1]
    assert _chain_hashes([99] + toks[1:], 8)[0] != h8[0]
    # Seeded by block size: same tokens, different granularity, no alias.
    assert _chain_hashes(toks[:16], 4)[0] != h8[0]


def test_prefix_store_lru_and_refcount_pinned_eviction():
    a = BlockAllocator(8)
    store = PrefixStore()
    b1, b2, b3 = a.allocate(3)
    a.incref([b1, b2, b3])  # the store's references
    store.insert("k1", b1), store.insert("k2", b2), store.insert("k3", b3)
    a.decref([b1, b2, b3])  # the owning sequence finished
    assert store.lookup(["k1", "k2", "miss"]) == [b1, b2]
    a.incref([b1])  # a live sequence adopts k1: pinned against eviction
    freed = store.evict(a, need=2)
    # LRU order after the lookup refresh is k3, k1, k2 — k1 is pinned,
    # so k3 and k2 go.
    assert freed == 2 and "k1" in store and len(store) == 1
    a.decref([b1])
    assert store.flush(a) == 1
    assert a.num_free == a.num_allocatable


# ---------------------------------------------------------------- prefix --
def test_shared_prefix_parity_and_hit_rate(lm):
    """Shared-prefix batch through the prefix-caching engine must equal
    per-request generate(), with real cache hits and no block leaks."""
    rng = np.random.default_rng(0)
    prompts, news = _shared_prefix_requests(rng)
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    prefix_cache=True)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    rep = engine.last_run_telemetry["prefix_cache"]
    assert rep["hit_rate"] > 0 and rep["hit_blocks"] > 0
    assert rep["insertions"] > 0
    assert rep["kv_bytes_saved"] > 0
    # Every surviving allocator reference is the store's (slots drained):
    # anything else is a leak.
    alloc = engine.kv.allocator
    assert set(alloc._refs) == set(engine.kv.prefix.blocks)
    assert all(alloc.refcount(b) == 1 for b in engine.kv.prefix.blocks)


def test_system_prompt_groups_hit_accounting(lm):
    """Two system prompts of four blocks, two requests each, and two
    requests that share nothing, over two slots. Cold, the first request
    of a group fills its blocks and the second adopts them; warm, all four
    adopt. The bytes saved are the adopted blocks' pool bytes: prefill
    never wrote them."""
    rng = np.random.default_rng(0)
    groups = [rng.integers(0, 32, (16,)).astype(np.int32) for _ in range(2)]
    prompts = []
    for i in range(6):
        tail = rng.integers(0, 32, (int(rng.integers(1, 4)),)).astype(
            np.int32)  # shorter than a block: no request caches its tail
        prompts.append(tail if i % 3 == 2
                       else np.concatenate([groups[i % 2], tail]))
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    prefix_cache=True)
    cold_out = engine.run([Request(p, 4) for p in prompts])
    cold = engine.last_run_telemetry["prefix_cache"]
    assert cold["insertions"] == 8 and cold["hit_blocks"] == 8
    assert cold["hit_rate"] == 0.5 and cold["hit_tokens"] == 2 * 16
    assert cold["kv_bytes_saved"] == 8 * engine.kv.bytes_per_block()
    warm_out = engine.run([Request(p, 4) for p in prompts])
    warm = engine.last_run_telemetry["prefix_cache"]
    assert warm["hit_tokens"] == 4 * 16
    assert warm["insertions"] == 8  # the store's counters run on
    assert warm["hit_blocks"] == 8 + 16
    for c, w in zip(cold_out, warm_out):
        np.testing.assert_array_equal(c, w)


def test_cow_on_fully_cached_prompt(lm):
    """Re-serving an identical prompt finds its blocks fully cached; the
    admission cap (always recompute the last position) forces a write
    into a SHARED block, which must copy-on-write — bit-exact output,
    peers untouched."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 32, (12,)).astype(np.int32)  # 3 full blocks
    want = lm.generate(prompt[None], 6, temperature=0.0)[0]
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    prefix_cache=True)
    first = engine.run([Request(prompt, 6)])
    second = engine.run([Request(prompt, 6)])
    np.testing.assert_array_equal(want, first[0])
    np.testing.assert_array_equal(want, second[0])
    assert engine.kv.cow_copies >= 1
    assert engine.last_run_telemetry["prefix_cache"]["hit_tokens"] > 0


# @slow (tier-1 budget): the decref-not-free invariant is unit-covered
# in-tier by the allocator/store tests above; this is the e2e drive.
@pytest.mark.slow
def test_preempt_shared_blocks_decrefs_not_frees(lm):
    """Preemption under pool pressure with shared prefixes: victims hold
    refcount>1 blocks, and release must DECREF them — afterwards the
    store's entries are intact and accounting balances to zero leaks."""
    rng = np.random.default_rng(2)
    prompts, news = _shared_prefix_requests(rng, shared_len=12, n=5,
                                            news=(6, 10))
    want = _sequential_generate(lm, prompts, news)
    # Starve the pool: enough for ~2.5 worst-case sequences.
    engine = Engine(lm, max_slots=3, block_size=4, max_len=64,
                    num_blocks=16, prefix_cache=True)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert engine.last_run_telemetry["preemptions"] > 0
    alloc = engine.kv.allocator
    assert set(alloc._refs) == set(engine.kv.prefix.blocks)


# @slow (tier-1 budget): refcount-aware LRU eviction is unit-covered
# in-tier above; this drives it under real allocation pressure.
@pytest.mark.slow
def test_store_eviction_under_distinct_prompt_pressure(lm):
    """Distinct prompts fill the store until allocation pressure forces
    refcount-aware LRU eviction; serving still completes exactly."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 32, (12,)).astype(np.int32)
               for _ in range(6)]
    news = [4] * 6
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    num_blocks=13, prefix_cache=True)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert engine.kv.prefix.evictions > 0


# ------------------------------------------------------------------ int8 --
def test_int8_kv_pools_shapes_ratio_and_fidelity(lm):
    """int8 KV pools store {q, scale} per block; the byte ratio over f32
    matches 4*hd/(hd+4) exactly, and greedy decode stays high-agreement
    with the f32 engine (fidelity-gated, NOT bit-exact: docs/SERVING.md)."""
    rng = np.random.default_rng(4)
    prompts, news = _shared_prefix_requests(rng, shared_len=8, n=4)
    f32 = Engine(lm, max_slots=2, block_size=4, max_len=64)
    q8 = Engine(lm, max_slots=2, block_size=4, max_len=64,
                kv_dtype="int8")
    leaves = jax.tree_util.tree_leaves(
        q8.kv.caches,
        is_leaf=lambda x: isinstance(x, dict) and "q" in x)
    assert leaves and all(isinstance(l, dict) for l in leaves)
    assert all(l["q"].dtype == np.int8 and l["scale"].dtype == np.float32
               for l in leaves)
    hd = 16 // 2  # d_model / num_heads
    want_ratio = 4 * hd / (hd + 4)
    got_ratio = f32.kv.bytes_per_block() / q8.kv.bytes_per_block()
    assert got_ratio == pytest.approx(want_ratio)
    reqs = [Request(p, m) for p, m in zip(prompts, news)]
    a = f32.run(list(reqs))
    b = q8.run(list(reqs))
    agree = total = 0
    for x, y, p in zip(a, b, prompts):
        gx, gy = x[len(p):], y[len(p):]
        agree += int(np.sum(gx == gy))
        total += len(gx)
    assert agree / total >= 0.5, f"int8 KV agreement {agree}/{total}"


@pytest.mark.parametrize("d_model,num_heads", [(64, 2), (128, 1)])
def test_int8_pool_holds_more_slots_a_byte(d_model, num_heads):
    """An int8 pool stores a byte an element and one f32 scale a (position,
    head), so a pool byte holds 4 hd / (hd + 4) times the slots f32 does:
    3.56 at head size 32, 3.88 at 128."""
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=1, d_model=d_model, num_heads=num_heads, max_len=16))
    model.build((8,))
    pool = dict(max_slots=1, block_size=4, max_blocks_per_seq=2,
                num_blocks=3)
    f32 = PagedKVCache(model.module, model.params, dtype=jnp.float32,
                       **pool)
    int8 = PagedKVCache(model.module, model.params, dtype=jnp.int8, **pool)
    hd = d_model // num_heads
    assert f32.bytes_per_block() * (hd + 4) == int8.bytes_per_block() * 4 * hd


# ------------------------------------------------------------ speculative --
def test_spec_decode_token_exact_selfdraft(lm):
    """Draft == target: near-every proposal accepted, and the output is
    exactly generate()'s — the verify dispatch IS the decode step."""
    rng = np.random.default_rng(5)
    prompts, news = _shared_prefix_requests(rng, shared_len=8, n=4,
                                            news=(8, 12))
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    draft_model=lm, spec_k=3)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    spec = engine.last_run_telemetry["speculative"]
    assert spec["k"] == 3 and spec["rounds"] > 0
    assert spec["tokens_per_dispatch"] > 1.0  # a self-draft must win
    assert spec["accept_rate"] > 0.0


# @slow (tier-1 budget): greedy spec exactness stays in-tier via the
# self-draft test; this adds the disagreeing-draft (low-accept) angle.
@pytest.mark.slow
def test_spec_decode_token_exact_cold_draft(lm, draft_lm):
    """A barely-trained draft proposes garbage; acceptance collapses but
    the token stream must STILL be exactly generate()'s — rejection
    replays the target's own sampled token."""
    rng = np.random.default_rng(6)
    prompts, news = _shared_prefix_requests(rng, shared_len=8, n=3)
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    draft_model=draft_lm, spec_k=3)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")


@pytest.mark.slow
def test_spec_decode_sampled_bit_exact(lm, draft_lm):
    """Sampled serving: the verify path reuses the engine's per-token
    key derivation, so speculative output is bit-identical to the
    vanilla engine's for pinned request seeds."""
    rng = np.random.default_rng(7)
    prompts, news = _shared_prefix_requests(rng, shared_len=8, n=3)
    reqs = lambda: [Request(p, m, seed=100 + i)
                    for i, (p, m) in enumerate(zip(prompts, news))]
    vanilla = Engine(lm, max_slots=2, block_size=4, max_len=64,
                     temperature=1.0, top_k=8)
    spec = Engine(lm, max_slots=2, block_size=4, max_len=64,
                  temperature=1.0, top_k=8, draft_model=draft_lm,
                  spec_k=3)
    a = vanilla.run(reqs())
    b = spec.run(reqs())
    for i, (w, g) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")


@pytest.mark.slow
def test_spec_decode_exact_across_preemption(lm):
    """Pool pressure preempts mid-spec; requeued sequences re-prefill
    and keep speculating — still exactly generate()."""
    rng = np.random.default_rng(8)
    prompts, news = _shared_prefix_requests(rng, shared_len=12, n=5,
                                            news=(6, 10))
    want = _sequential_generate(lm, prompts, news)
    engine = Engine(lm, max_slots=3, block_size=4, max_len=64,
                    num_blocks=14, draft_model=lm, spec_k=3)
    got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    assert engine.last_run_telemetry["preemptions"] > 0


@pytest.mark.slow
def test_spec_update_weights_flushes_prefix_and_stays_exact(lm):
    """Weight hot-swap between runs: the prefix store is FLUSHED (cached
    KV under old weights must not seed new requests), and the
    speculative engine's post-swap output equals post-swap generate()
    even though the draft still runs the old weights (stale drafts only
    lower acceptance, never change tokens)."""
    rng = np.random.default_rng(9)
    prompts, news = _shared_prefix_requests(rng, shared_len=8, n=3)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    prefix_cache=True, draft_model=lm, spec_k=3)
    engine.run([Request(p, m) for p, m in zip(prompts, news)])
    assert len(engine.kv.prefix) > 0
    new_params = jax.tree_util.tree_map(lambda x: x * 1.05, lm.params)
    old_params = lm.params
    engine.update_weights(new_params)
    assert len(engine.kv.prefix) == 0  # staleness contract
    try:
        lm.params = new_params
        want = _sequential_generate(lm, prompts, news)
        got = engine.run([Request(p, m) for p, m in zip(prompts, news)])
        for i, (w, g) in enumerate(zip(want, got)):
            np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    finally:
        lm.params = old_params
        engine.update_weights(old_params)


@pytest.mark.slow
def test_fixed_shape_dispatches_never_recompile(lm, draft_lm):
    """Batch churn — different tails, hit patterns, acceptance runs —
    must ride the warm fixed-shape programs: decode, verify and draft
    decode compile exactly once. (Prefill is excluded: its bucketed
    shape legitimately varies with the cached-prefix offset.)"""
    rng = np.random.default_rng(11)
    p1, n1 = _shared_prefix_requests(rng, shared_len=8, n=3)
    p2, n2 = _shared_prefix_requests(rng, shared_len=12, n=4)
    engine = Engine(lm, max_slots=2, block_size=4, max_len=64,
                    prefix_cache=True)
    engine.run([Request(p, m) for p, m in zip(p1, n1)])  # warm
    with assert_no_recompile(engine._decode_jit):
        engine.run([Request(p, m) for p, m in zip(p2, n2)])
    spec = Engine(lm, max_slots=2, block_size=4, max_len=64,
                  prefix_cache=True, draft_model=draft_lm, spec_k=3)
    spec.run([Request(p, m) for p, m in zip(p1, n1)])  # warm
    with assert_no_recompile(spec._verify_jit, spec._draft_decode_jit):
        spec.run([Request(p, m) for p, m in zip(p2, n2)])


def test_spec_headroom_request_validation(lm):
    engine = Engine(lm, max_slots=1, block_size=4, max_len=16,
                    draft_model=lm, spec_k=4)
    with pytest.raises(ValueError, match="speculative headroom"):
        engine.run([Request(np.arange(8, dtype=np.int32), 8)])


def test_spec_k_validation(lm):
    with pytest.raises(ValueError, match="spec_k"):
        Engine(lm, max_slots=1, block_size=4, max_len=32,
               draft_model=lm, spec_k=1)


# ----------------------------------------------------------------- fleet --
def test_fleet_suffix_only_handoff(lm):
    """Prefix-caching fleet: the router places by prefix affinity and
    payloads ship ONLY the non-cached suffix — fewer bytes than full
    handoffs, token streams unchanged."""
    from distributed_tpu.fleet import ServingFleet

    rng = np.random.default_rng(10)
    prompts, news = _shared_prefix_requests(rng, shared_len=16, n=5)
    want = _sequential_generate(lm, prompts, news)
    fleet = ServingFleet(lm, decode_replicas=2, prefill_replicas=1,
                         max_slots=4, block_size=4, max_len=64,
                         prefix_cache=True)
    outs = fleet.run([Request(p, m) for p, m in zip(prompts, news)])
    for i, (w, g) in enumerate(zip(want, outs)):
        np.testing.assert_array_equal(w, g, err_msg=f"request {i}")
    h = fleet.last_run_telemetry["handoffs"]
    assert h["suffix_trims"] > 0
    assert 0 < h["bytes_shipped"] < h["bytes_full"]
    assert h["bytes_saved"] == h["bytes_full"] - h["bytes_shipped"]
    assert h["trim_stale"] == 0


def test_trim_kv_unit(lm):
    """trim_kv drops exactly the leading store-hit blocks and re-keys
    the runs; an empty/missing store is a no-op."""
    from distributed_tpu.fleet.handoff import pack_kv, trim_kv

    kv = PagedKVCache(lm.module, lm.params, max_slots=1, block_size=4,
                      max_blocks_per_seq=8, num_blocks=9,
                      dtype=np.float32)
    assert kv.reserve(0, 12)  # 3 blocks
    toks = list(range(12))
    payload = pack_kv(kv, 0, 12, tokens=toks)
    assert len(payload.prefix_hashes) == 3
    same, skipped = trim_kv(payload, None)
    assert skipped == 0 and same is payload
    store = PrefixStore()
    alloc = BlockAllocator(4)
    (b,) = alloc.allocate(1)
    store.insert(payload.prefix_hashes[0], b)
    trimmed, skipped = trim_kv(payload, store)
    assert skipped == 1 and trimmed.skip_blocks == 1
    for key, data in trimmed.blocks.items():
        assert key.split("@")[-2].startswith("1,") and data.shape[0] == 2
    # Non-contiguous hit (block 2 cached, block 1 not): the walk stops
    # at the first miss, so nothing past block 0 is dropped.
    store2 = PrefixStore()
    store2.insert(payload.prefix_hashes[2], b)
    _, skipped2 = trim_kv(payload, store2)
    assert skipped2 == 0
