"""Cross-replica prefix gossip: the fleet-wide chain-hash index.

A cold replica re-earning a prefix the warm one already computed is the
gap gossip closes: replicas advertise their ``PrefixStore`` keys, the
router treats gossip-adoptable replicas as warm at placement, and the
fleet moves the blocks (``pack_prefix`` / ``adopt_prefix``) — stamped
with ``weights_version`` so stale-weights KV can NEVER travel (the
``update_weights`` flush discipline, extended fleet-wide).

Correctness bar, as everywhere in serving: whatever blocks travel, the
greedy token stream must be exactly what the gossip-off fleet computes.
The fleet tests use a TRAINED tiny model — untrained d_model=16 logits
are near-tied and their argmax flips between dispatch shapes, which
would turn placement differences into token noise.
"""

import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.fleet import ServingFleet
from distributed_tpu.fleet.gossip import PrefixGossipIndex
from distributed_tpu.fleet.handoff import (
    HandoffIncompatible, adopt_prefix, pack_prefix,
)
from distributed_tpu.serve_service import transport as tr
from distributed_tpu.serving import Engine, Request
from distributed_tpu.serving.kv_cache import _chain_hashes
from distributed_tpu.utils import event_schema as evs
from distributed_tpu.utils.events import read_events


@pytest.fixture(scope="module")
def lm():
    rng = np.random.default_rng(0)
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=2, d_model=16, num_heads=2, max_len=128))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((16,))
    xs = rng.integers(0, 32, size=(32, 16)).astype(np.int32)
    model.fit(xs, np.roll(xs, -1, axis=1), batch_size=32, epochs=25,
              verbose=0)
    return model


def _shared_requests(rng, n=3, shared_blocks=2, block=16, new=24, seed0=0,
                     shared=None):
    """``n`` requests over one shared full-block prefix + distinct
    tails. Pass ``shared`` to reuse a prefix across calls (warm-up run
    then wave) — a fresh one is drawn otherwise."""
    if shared is None:
        shared = rng.integers(0, 32,
                              size=shared_blocks * block).astype(np.int32)
    return [
        Request(np.concatenate([
            shared, rng.integers(0, 32, size=3 + i).astype(np.int32)
        ]), new, seed=seed0 + i)
        for i in range(n)
    ]


# ------------------------------------------------------------------ index --
def test_gossip_index_protocol():
    """Advertise is REPLACE (eviction propagates), withdraw drops the
    replica, best_peer returns the longest LEADING run filtered by the
    weights-version stamp, ties break by name."""
    g = PrefixGossipIndex()
    assert g.advertise("r0", ["a", "b", "c"], weights_version=0) == 3
    assert g.advertise("r1", ["a", "b"], weights_version=0) == 2
    assert g.best_peer(["a", "b", "c", "d"], weights_version=0) == ("r0", 3)
    # leading-run semantics: a miss at key 0 means nothing is adoptable
    assert g.best_peer(["x", "a"], weights_version=0) == (None, 0)
    # tie on run length breaks by name
    assert g.best_peer(["a", "b"], weights_version=0) == ("r0", 2)
    assert g.best_peer(["a", "b"], weights_version=0,
                       exclude=("r0",)) == ("r1", 2)
    # REPLACE semantics: r0's eviction of "c" propagates on re-advertise
    assert g.advertise("r0", ["a", "b"], weights_version=0) == 0
    assert g.best_peer(["a", "b", "c"], weights_version=0)[1] == 2
    # the stamp: advertisements at the wrong version are invisible
    g.advertise("r0", ["a", "b"], weights_version=1)
    assert g.best_peer(["a", "b"], weights_version=1) == ("r0", 2)
    assert g.best_peer(["a", "b"], weights_version=2)[1] == 0
    assert g.holders("a", weights_version=1) == ["r0"]
    assert g.withdraw("r0") == 2
    assert g.telemetry()["keys_live"] == 2  # r1's advertisement remains
    assert g.telemetry()["withdrawals"] == 1


# ----------------------------------------------------------- pack / adopt --
def test_pack_adopt_roundtrip_token_exact_and_stamp(lm):
    """A warm engine's prefix blocks, adopted into a cold engine's
    store, make the cold engine admit with cached_len > 0 and decode
    exactly the same tokens; a weights-version mismatch at adoption is
    HandoffIncompatible — the satellite regression for 'flush must also
    invalidate the advertised index': even a payload packed before a
    swap dies at the stamp check."""
    rng = np.random.default_rng(1)
    reqs = _shared_requests(rng)
    prompts = [r.prompt for r in reqs]
    news = [r.max_new_tokens for r in reqs]

    warm = Engine(lm, max_slots=4, block_size=16, max_len=128,
                  prefix_cache=True)
    outs_warm = [np.asarray(o) for o in warm.run(
        [Request(p, n, seed=i) for i, (p, n) in
         enumerate(zip(prompts, news))])]
    keys = _chain_hashes(list(prompts[0][:32]), 16)
    assert len(keys) == 2 and warm.kv.prefix.peek_run(keys) != []

    payload = pack_prefix(warm.kv, keys, weights_version=0)
    assert payload is not None and payload.weights_version == 0
    assert payload.cached_len == 32

    cold = Engine(lm, max_slots=4, block_size=16, max_len=128,
                  prefix_cache=True)
    with pytest.raises(HandoffIncompatible, match="stale gossip"):
        adopt_prefix(cold.kv, payload, weights_version=1)
    assert len(cold.kv.prefix) == 0  # nothing leaked past the stamp

    assert adopt_prefix(cold.kv, payload, weights_version=0) == 2
    assert cold.kv.prefix.peek_run(keys) != []
    outs_cold = [np.asarray(o) for o in cold.run(
        [Request(p, n, seed=i) for i, (p, n) in
         enumerate(zip(prompts, news))])]
    for a, b in zip(outs_cold, outs_warm):
        assert np.array_equal(a, b)
    # the adopted blocks were USED: admissions hit the store
    assert cold.kv.prefix.hits > 0
    # adopting the same run again is a no-op (first writer wins)
    assert adopt_prefix(cold.kv, payload, weights_version=0) == 0


# -------------------------------------------------------------- transport --
def test_transport_carries_weights_version(tmp_path, lm):
    """The stamp rides both encodings (inline frame bytes and shm
    ``.npy`` dirs); manifests written before the stamp existed decode
    to None (adoption then skips the check instead of crashing)."""
    rng = np.random.default_rng(2)
    warm = Engine(lm, max_slots=2, block_size=16, max_len=128,
                  prefix_cache=True)
    reqs = _shared_requests(rng, n=2)
    warm.run(reqs)
    keys = _chain_hashes(list(reqs[0].prompt[:32]), 16)
    payload = pack_prefix(warm.kv, keys, weights_version=3)

    d = tr.handoff_to_payload(payload)
    assert d["weights_version"] == 3
    meta, blobs = tr.encode_payload(d)
    assert tr.payload_to_handoff(
        tr.decode_payload(meta, blobs)).weights_version == 3

    shm = tr.ShmTransport(tmp_path / "shm")
    ref = shm.put(d)
    got = shm.get(ref)
    assert got["weights_version"] == 3
    handoff = tr.payload_to_handoff(got)
    assert handoff.weights_version == 3
    # pre-stamp manifest: strip the field, decode must yield None
    import json
    from pathlib import Path
    mpath = Path(ref["path"]) / tr.MANIFEST
    m = json.loads(mpath.read_text())
    del m["weights_version"]
    mpath.write_text(json.dumps(m))
    assert shm.get(ref)["weights_version"] is None
    shm.close()


# ------------------------------------------------------------------ fleet --
def _warm_then_wave(lm, rng_seed, gossip, programs=None):
    """One request warms decode-0; a 3-request shared-prefix wave then
    arrives at the same instant. With gossip, the router spreads the
    wave (adoptable replicas count as warm) and the cold replica adopts
    instead of re-prefilling. Pass a shared ``programs`` when comparing
    fleets on TIME: compiled dispatches are then identical and warm, so
    TTFT differences measure scheduling, not jit tracing."""
    rng = np.random.default_rng(rng_seed)
    fl = ServingFleet(lm, decode_replicas=2, prefill_replicas=0,
                      max_slots=2, block_size=16, max_len=128,
                      prefix_cache=True, prefix_gossip=gossip,
                      programs=programs)
    shared = rng.integers(0, 32, size=32).astype(np.int32)
    warmup = _shared_requests(rng, n=1, seed0=100, shared=shared)
    wave = _shared_requests(rng, n=3, shared=shared)
    fl.run(warmup)
    out = fl.run(wave)
    return fl, out


def test_fleet_gossip_adopt_token_exact_and_wave_spread(lm, tmp_path,
                                                 monkeypatch):
    """The tentpole gate, in-process: the gossiping fleet adopts the
    warm replica's prefix onto the cold one (zero full re-prefills in
    the wave), spreads the wave over both replicas where the
    gossip-off fleet serializes it on the one warm replica, and the
    token streams are identical. Adopt/advertise
    events land in the log."""
    monkeypatch.setenv("DTPU_EVENT_LOG", str(tmp_path / "ev.jsonl"))
    # Same rng seed both runs: identical prompts, or token comparison
    # is meaningless. Shared programs: both fleets run the same warm
    # compiles, so the TTFT comparison measures scheduling.
    from distributed_tpu.fleet import EnginePrograms

    programs = EnginePrograms(lm)
    # Throwaway gossiping fleet first: the adoption path's gather/
    # scatter ops trace on their first dispatch, and that one-time wall
    # cost would be charged into the measured fleet's virtual timeline
    # (the virtual clock times REAL dispatch walls — docs/SERVING.md).
    _warm_then_wave(lm, 5, gossip=True, programs=programs)
    fl_on, out_on = _warm_then_wave(lm, 7, gossip=True,
                                    programs=programs)
    fl_off, out_off = _warm_then_wave(lm, 7, gossip=False,
                                      programs=programs)

    tel = fl_on.last_run_telemetry
    assert tel["gossip"]["adoptions"] >= 1
    assert tel["gossip"]["adopted_blocks"] >= 2
    assert tel["gossip"]["stale_rejected"] == 0
    # the wave's shared prefixes never re-prefilled from position 0:
    # the only full prefill ever was the warm-up request's first-compute
    rows = tel["decode_pool"]["replicas"]
    assert sum(r["prefills_full"] for r in rows.values()) == 1
    assert sum(r["gossip_adopts"] for r in rows.values()) >= 1
    assert sum(r["gossip_serves"] for r in rows.values()) >= 1
    # the gossip-off fleet pins the whole wave on the warm replica
    # (affinity), so its third request waits for a slot behind two
    # predecessors; gossip spreads the wave over both replicas. (The
    # first-token times that follow from it are measured walls on a
    # virtual clock: compared, they failed under a loaded box.)
    served_on = {r["replica"] for r in tel["requests"]}
    served_off = {r["replica"]
                  for r in fl_off.last_run_telemetry["requests"]}
    assert len(served_off) == 1 < len(served_on)
    for a, b in zip(out_on, out_off):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    events = read_events(tmp_path / "ev.jsonl")
    adopts = [e for e in events if e["event"] == evs.PREFIX_GOSSIP_ADOPT]
    assert adopts and adopts[0]["blocks"] >= 2
    assert adopts[0]["transport"] == "inproc"
    assert any(e["event"] == evs.PREFIX_GOSSIP_ADVERTISE for e in events)


def test_fleet_update_weights_invalidates_gossip(lm):
    """The satellite fix, fleet-wide: a weight swap flushes every
    replica's prefix store AND withdraws every advertisement, and bumps
    the version — so post-swap traffic re-earns its prefixes instead of
    adopting one-update-old KV."""
    fl, _ = _warm_then_wave(lm, 9, gossip=True)
    assert fl.gossip.telemetry()["keys_live"] > 0
    same = jax.tree_util.tree_map(lambda x: x, lm.params)
    assert fl.update_weights(same) == 1
    assert fl.weights_version == 1
    assert fl.gossip.telemetry()["keys_live"] == 0
    for rep in fl.decode_pool.values():
        assert len(rep.kv.prefix) == 0
    # a shape-mismatched tree fails loud, version unmoved
    bad = jax.tree_util.tree_map(
        lambda x: np.zeros((2, 2), np.float32), lm.params
    )
    with pytest.raises(ValueError):
        fl.update_weights(bad)
    assert fl.weights_version == 1
    # post-swap traffic runs clean at the new version: full re-prefill
    # once, then advertisements resume at version 1
    rng = np.random.default_rng(10)
    fl.run(_shared_requests(rng, n=2, seed0=50))
    tel = fl.last_run_telemetry
    assert tel["gossip"]["weights_version"] == 1
    assert tel["gossip"]["stale_rejected"] == 0
    assert fl.gossip.telemetry()["keys_live"] > 0


# ------------------------------------------------------- real process @slow --
@pytest.mark.slow
def test_shm_payload_crosses_a_real_process(tmp_path, lm):
    """The same-host deployment shape: the warm side commits the payload
    to tmpfs (atomic rename), a SEPARATE process (jax-free, like the
    router) opens it and validates manifest + blocks, and the local
    adopter installs from the committed dir token-exactly."""
    rng = np.random.default_rng(3)
    warm = Engine(lm, max_slots=4, block_size=16, max_len=128,
                  prefix_cache=True)
    reqs = _shared_requests(rng)
    outs_warm = [np.asarray(o) for o in warm.run(reqs)]
    keys = _chain_hashes(list(reqs[0].prompt[:32]), 16)
    payload = pack_prefix(warm.kv, keys, weights_version=5)
    shm = tr.ShmTransport(tmp_path / "shm")
    ref = shm.put(tr.handoff_to_payload(payload))

    # The child loads transport.py by FILE PATH: the module itself is
    # jax-free (the dtpu-lint rule), and a router-style process that
    # avoids the package __init__ chain never pays the jax import.
    tpath = tr.__file__

    child = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("t", {tpath!r})
        tr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tr)
        assert "jax" not in sys.modules  # the router process stays jax-free
        p = tr.ShmTransport({str(tmp_path / "shm")!r}, owner=False).get(
            {ref!r})
        assert p["weights_version"] == 5
        assert p["cached_len"] == 32 and p["block_size"] == 16
        assert len(p["blocks"]) > 0
        for a in p["blocks"].values():
            assert a.size > 0
        assert "jax" not in sys.modules
        print("CHILD_OK")
    """)
    proc = subprocess.run([sys.executable, "-c", child],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "CHILD_OK" in proc.stdout

    cold = Engine(lm, max_slots=4, block_size=16, max_len=128,
                  prefix_cache=True)
    got = tr.payload_to_handoff(shm.get(ref))
    assert adopt_prefix(cold.kv, got, weights_version=5) == 2
    outs_cold = [np.asarray(o) for o in cold.run(
        [Request(r.prompt, r.max_new_tokens, seed=r.seed) for r in reqs])]
    for a, b in zip(outs_cold, outs_warm):
        assert np.array_equal(a, b)
    shm.close()
