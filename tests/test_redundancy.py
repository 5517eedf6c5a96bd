"""Diskless recovery (ISSUE 13): ring buddy assignment, the RAM-backed
mirror store's commit/coverage/invalidation protocol, restore-tier
selection, the in-process recovery paths (buddy restore with ZERO disk
block reads, disk fallback on redundancy loss, loss-trajectory parity),
the new fault-injection modes, and the supervisor's MTTR breakdown.

The real supervised-gang fault matrix (lose one worker -> buddy restore,
lose a buddy pair -> disk fallback, kill during refresh -> stale-mirror
rejection -> disk, stale mirror vs newer disk -> disk) runs 2-3-process
gloo gangs and is @slow; tier-1 pins every decision in-process through
the same code paths (the mirror encoding IS the sharded block layout, so
single-process restores exercise the identical reassembly).
"""

import json
import os
import shutil

import jax
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.checkpoint import ShardedCheckpointer
from distributed_tpu.checkpoint import sharded as sharded_lib
from distributed_tpu.resilience import (
    BuddyRedundancy,
    BuddyStore,
    FaultInjector,
    mirror_holder,
    mirror_source,
    recovery_rows,
    select_restore_tier,
)
from distributed_tpu.resilience import faults as faults_lib
from distributed_tpu.utils.profiler import redundancy_report


# ------------------------------------------------------------------ ring ----
class TestRingAssignment:
    def test_holder_source_inverse(self):
        for world in (1, 2, 3, 4, 8):
            for r in range(world):
                assert mirror_source(mirror_holder(r, world), world) == r
                assert mirror_holder(mirror_source(r, world), world) == r

    def test_ring_shape(self):
        assert mirror_holder(0, 4) == 1
        assert mirror_holder(3, 4) == 0
        assert mirror_source(0, 4) == 3
        assert mirror_holder(0, 1) == 0  # degenerate self-mirror


# ----------------------------------------------------------------- store ----
def _blocks(val, path="params/w"):
    data = np.full((4, 4), float(val), np.float32)
    key = sharded_lib._block_key(path, (0, 0), (4, 4))
    return {key: data}


def _manifest(source, world, extra=None):
    m = {"source": source, "world": world, "seed": 0, "input_shape": [4],
         "leaves": {"params/w": {"shape": [4, 4], "dtype": "float32"}}}
    m.update(extra or {})
    return m


class TestBuddyStore:
    def test_commit_protocol_and_torn_writes_invisible(self, tmp_path):
        st = BuddyStore(tmp_path)
        # A mirror dir without manifest.json (torn write) is not committed.
        torn = st._role_dir(0, "self") / "mirror-7"
        torn.mkdir(parents=True)
        np.save(torn / "block-0.npy", np.zeros(3))
        assert st.committed_steps(0, "self") == []
        # A stale tmp dir from a killed writer is invisible too.
        (st._role_dir(0, "self") / "mirror-9.tmp-123").mkdir()
        assert st.committed_steps(0, "self") == []
        st.write_mirror(0, "self", 8, _blocks(1), _manifest(0, 1))
        assert st.committed_steps(0, "self") == [8]
        # the commit swept the torn/tmp leftovers
        names = {p.name for p in st._role_dir(0, "self").iterdir()}
        assert names == {"mirror-8"}

    def test_keep_is_the_skew_tolerance(self, tmp_path):
        st = BuddyStore(tmp_path, keep=2)
        for s in (1, 2, 3):
            st.write_mirror(0, "self", s, _blocks(s), _manifest(0, 1))
        assert st.committed_steps(0, "self") == [2, 3]

    def test_invalidate_ranks_drops_whole_segments(self, tmp_path):
        st = BuddyStore(tmp_path)
        st.write_mirror(0, "self", 4, _blocks(0), _manifest(0, 2))
        st.write_mirror(1, "peer", 4, _blocks(0), _manifest(0, 2))
        assert st.invalidate_ranks([1, 5]) == [1]
        assert not st.segment(1).exists()
        assert st.committed_steps(0, "self") == [4]

    def test_available_step_requires_complete_same_step_coverage(
            self, tmp_path):
        st = BuddyStore(tmp_path)
        world = 2
        # Complete at 4: source 0 via rank-0 self, source 1 via rank-0 peer
        # (pushed by rank 1 to its holder (1+1)%2 == 0).
        st.write_mirror(0, "self", 4, _blocks(0), _manifest(0, world))
        st.write_mirror(0, "peer", 4, _blocks(1), _manifest(1, world))
        assert st.available_step() == 4
        # Newer but INCOMPLETE step never wins: source 0 refreshed at 5,
        # source 1 did not.
        st.write_mirror(0, "self", 5, _blocks(0), _manifest(0, world))
        assert st.available_step() == 4
        # Completing 5 moves the answer up.
        st.write_mirror(0, "peer", 5, _blocks(1), _manifest(1, world))
        assert st.available_step() == 5

    def test_buddy_pair_loss_leaves_no_complete_set(self, tmp_path):
        st = BuddyStore(tmp_path)
        world = 3
        # Full ring at step 6: every rank holds self + its source's peer.
        for r in range(world):
            st.write_mirror(r, "self", 6, _blocks(r), _manifest(r, world))
            src = mirror_source(r, world)
            st.write_mirror(r, "peer", 6, _blocks(src), _manifest(src, world))
        assert st.available_step() == 6
        # Lose rank 1 AND its mirror holder rank 2: shard 1's live copy
        # (rank-1 self) and its only mirror (rank-2 peer) die together.
        st.invalidate_ranks([1, mirror_holder(1, world)])
        assert st.available_step() is None

    def test_single_loss_keeps_coverage_via_the_buddy(self, tmp_path):
        st = BuddyStore(tmp_path)
        world = 3
        for r in range(world):
            st.write_mirror(r, "self", 6, _blocks(r), _manifest(r, world))
            src = mirror_source(r, world)
            st.write_mirror(r, "peer", 6, _blocks(src), _manifest(src, world))
        st.invalidate_ranks([1])  # shard 1 survives in rank-2's peer mirror
        assert st.available_step() == 6

    def test_mixed_world_steps_do_not_combine(self, tmp_path):
        """Mirrors from before a resize (world 4) must not complete a set
        with post-resize mirrors (world 2) at the same step."""
        st = BuddyStore(tmp_path)
        st.write_mirror(0, "self", 4, _blocks(0), _manifest(0, 2))
        st.write_mirror(1, "self", 4, _blocks(1), _manifest(1, 4))
        assert st.available_step() is None

    def test_bytes_held_prices_all_retained_mirrors(self, tmp_path):
        st = BuddyStore(tmp_path, keep=2)
        st.write_mirror(0, "self", 1, _blocks(1), _manifest(0, 1))
        st.write_mirror(0, "self", 2, _blocks(2), _manifest(0, 1))
        raw = 2 * 4 * 4 * 4  # two f32 (4,4) mirrors
        # file sizes: raw block bytes + the .npy headers actually resident
        assert raw <= st.bytes_held(0) <= raw + 2 * 1024
        assert st.bytes_held(3) == 0


# -------------------------------------------------------- tier selection ----
class _FakeDisk:
    def __init__(self, step):
        self._step = step

    def latest_step(self):
        return self._step


class TestTierSelection:
    def _buddy_at(self, tmp_path, step):
        st = BuddyStore(tmp_path)
        if step is not None:
            st.write_mirror(0, "self", step, _blocks(0), _manifest(0, 1))
        return BuddyRedundancy(st, rank=0, world=1)

    def test_fresh_buddy_beats_disk(self, tmp_path):
        b = self._buddy_at(tmp_path, 6)
        assert select_restore_tier(b, _FakeDisk(4)) == ("buddy", 6)
        assert select_restore_tier(b, _FakeDisk(6)) == ("buddy", 6)  # tie

    def test_stale_mirror_rejected_for_disk(self, tmp_path):
        b = self._buddy_at(tmp_path, 4)
        assert select_restore_tier(b, _FakeDisk(6)) == ("disk", 6)

    def test_missing_tiers(self, tmp_path):
        b = self._buddy_at(tmp_path, None)
        assert select_restore_tier(b, _FakeDisk(3)) == ("disk", 3)
        assert select_restore_tier(b, _FakeDisk(None)) == ("restart", None)
        assert select_restore_tier(None, _FakeDisk(None)) == ("restart", None)
        assert select_restore_tier(
            self._buddy_at(tmp_path / "b2", 2), _FakeDisk(None)
        ) == ("buddy", 2)


# ------------------------------------------------------------- in-process ----
def _data(n=64):
    x, y = dtpu.data.synthetic_images(n, (8, 8), 10, seed=3)
    return x, y


def _model():
    with dtpu.FullyShardedDataParallel().scope():
        m = dtpu.Model(dtpu.nn.Sequential([
            dtpu.nn.Flatten(),
            dtpu.nn.Dense(64, activation="relu"),
            dtpu.nn.Dense(10),
        ]))
        m.compile(optimizer=dtpu.optim.SGD(0.05, momentum=0.9),
                  loss="sparse_categorical_crossentropy")
    return m


def _loss_tracker(into):
    return dtpu.callbacks.LambdaCallback(
        on_batch_end=lambda model, step, logs: into.append(
            (int(step), float(logs["loss"]))
        )
    )


class TestInProcessRecovery:
    def test_buddy_restore_zero_disk_reads_and_parity(
            self, devices, tmp_path):
        """The tentpole contract, in-process: refresh mirrors during fit
        (async, cadence hook), kill nothing, restore a FRESH model from
        the buddy tier — zero sharded-checkpoint block reads — and
        continue training to a loss trajectory identical to the
        uninterrupted run (bit-exact here: the mirror is a byte-exact
        copy and the batch stream is (seed, step)-deterministic)."""
        x, y = _data(128)
        ref_losses = []
        m_ref = _model()
        m_ref.fit(x, y, batch_size=32, epochs=2, verbose=0, seed=0,
                  callbacks=[_loss_tracker(ref_losses)])

        store = tmp_path / "store"
        m1 = _model()
        cb = dtpu.callbacks.ModelCheckpoint(
            tmp_path / "ckpt", sharded=True, save_freq=2, async_save=True,
            buddy=store, buddy_refresh_every=1)
        m1.fit(x, y, batch_size=32, epochs=1, verbose=0, seed=0,
               callbacks=[cb])
        # telemetry pricing rode the fit
        red = m1.last_fit_telemetry["redundancy"]
        assert red["mirror_host_bytes"] > 0
        assert red["overhead_ratio"] > 1.0

        reads0 = sharded_lib.read_stats["block_reads"]
        losses2 = []
        m2 = _model()
        cb2 = dtpu.callbacks.ModelCheckpoint(
            tmp_path / "ckpt", sharded=True, save_freq=2, restore=True,
            buddy=store)
        m2.fit(x, y, batch_size=32, epochs=2, verbose=0, seed=0,
               callbacks=[cb2, _loss_tracker(losses2)])
        assert sharded_lib.read_stats["block_reads"] == reads0  # RAM only
        for a, b in zip(jax.tree_util.tree_leaves(m_ref.params),
                        jax.tree_util.tree_leaves(m2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ref = dict(ref_losses)
        for step, loss in losses2:  # epoch-2 steps, post-restore
            assert loss == ref[step], (step, loss, ref[step])

    def test_buddy_loss_falls_back_to_disk(self, devices, tmp_path):
        """Invalidating the only segment (the buddy died too) must route
        the SAME restore call through the disk tier — and the result is
        identical state, one save interval older at most."""
        x, y = _data()
        store = tmp_path / "store"
        m1 = _model()
        cb = dtpu.callbacks.ModelCheckpoint(
            tmp_path / "ckpt", sharded=True, save_freq=2,
            buddy=store, buddy_refresh_every=1)
        m1.fit(x, y, batch_size=32, epochs=1, verbose=0, seed=0,
               callbacks=[cb])
        BuddyStore(store).invalidate_ranks([0])

        reads0 = sharded_lib.read_stats["block_reads"]
        m2 = _model()
        cb2 = dtpu.callbacks.ModelCheckpoint(
            tmp_path / "ckpt", sharded=True, restore=True, buddy=store)
        m2.fit(x, y, batch_size=32, epochs=1, verbose=0, seed=0,
               callbacks=[cb2])
        assert sharded_lib.read_stats["block_reads"] > reads0  # disk tier
        assert m2.step == m1.step  # same final state after the replay
        for a, b in zip(jax.tree_util.tree_leaves(m1.params),
                        jax.tree_util.tree_leaves(m2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stale_mirror_rejected_in_restore_path(self, devices, tmp_path):
        """Mirrors frozen at an old step (refresh stopped; disk kept
        saving) must lose to the newer disk checkpoint in the REAL
        restore path, not just the selection unit."""
        x, y = _data()
        store = tmp_path / "store"
        m1 = _model()
        buddy = BuddyRedundancy(store)
        ck = ShardedCheckpointer(tmp_path / "ckpt")
        m1.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
               seed=0)
        buddy.refresh(m1)
        buddy.wait()
        m1.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
               seed=0, initial_epoch=0)
        ck.save(m1)  # disk at step 4, mirrors at step 2
        assert select_restore_tier(buddy, ck) == ("disk", 4)
        m2 = _model()
        cb2 = dtpu.callbacks.ModelCheckpoint(
            tmp_path / "ckpt", sharded=True, restore=True, buddy=store)
        m2.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=4, verbose=0,
               seed=0, callbacks=[cb2])
        assert m2.step == 4

    def test_restore_into_reshards_across_strategy(self, devices, tmp_path):
        """The mirror encoding is the block layout: an FSDP-sharded
        mirror restores into a ZeRO-1 model (replicated params) through
        the same read-time reshard a disk checkpoint gets."""
        x, y = _data()
        m1 = _model()
        m1.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
               seed=0)
        buddy = BuddyRedundancy(tmp_path / "store")
        buddy.refresh(m1)
        buddy.wait()

        with dtpu.ZeroDataParallel().scope():
            m2 = dtpu.Model(dtpu.nn.Sequential([
                dtpu.nn.Flatten(),
                dtpu.nn.Dense(64, activation="relu"),
                dtpu.nn.Dense(10),
            ]))
            m2.compile(optimizer=dtpu.optim.SGD(0.05, momentum=0.9),
                       loss="sparse_categorical_crossentropy")
        m2.build((8, 8))
        step = BuddyRedundancy(tmp_path / "store").restore_into(m2)
        assert step == m1.step
        from jax.sharding import PartitionSpec

        leaf = m2.params["dense"]["kernel"]
        assert leaf.sharding.spec == PartitionSpec()  # live strategy wins
        for a, b in zip(jax.tree_util.tree_leaves(m1.params),
                        jax.tree_util.tree_leaves(m2.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_refresh_failure_degrades_not_raises(self, devices, tmp_path,
                                                 monkeypatch):
        x, y = _data()
        m = _model()
        buddy = BuddyRedundancy(tmp_path / "store", async_refresh=False)
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=1, verbose=0,
              seed=0)
        monkeypatch.setattr(
            buddy.store, "write_mirror",
            lambda *a, **k: (_ for _ in ()).throw(OSError("store full")))
        buddy.refresh(m)  # must not raise
        assert isinstance(buddy.last_refresh_error, OSError)
        assert buddy.available_step() is None  # tier degraded, run alive


# ------------------------------------------------------------ fault modes ----
class TestNewFaultModes:
    def test_from_env_parses_new_modes(self, monkeypatch):
        monkeypatch.setenv("DTPU_FAULT", "buddy_kill:at_step=7,rank=1")
        f = FaultInjector.from_env()
        assert f.mode == "buddy_kill" and f.at_step == 7 and f.rank == 1
        monkeypatch.setenv("DTPU_FAULT", "kill_during_refresh:at_step=3")
        f = FaultInjector.from_env()
        assert f.mode == "kill_during_refresh" and f.at_step == 3

    def test_pair_modes_require_concrete_rank(self):
        with pytest.raises(ValueError, match="rank"):
            FaultInjector("buddy_kill", rank=None)
        with pytest.raises(ValueError, match="rank"):
            FaultInjector("kill_during_refresh", rank=None)

    def test_buddy_kill_arms_the_pair(self, monkeypatch):
        f = FaultInjector("buddy_kill", at_step=5, rank=1)
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        for me, armed in ((0, False), (1, True), (2, True), (3, False)):
            monkeypatch.setattr(jax, "process_index", lambda me=me: me)
            assert f._armed() is armed

    def test_buddy_kill_markers_are_per_rank(self, monkeypatch, tmp_path):
        """Both pair members must fire: the first one's once-marker must
        not disarm the second."""
        marker = tmp_path / "once"
        monkeypatch.setattr(jax, "process_count", lambda: 4)
        monkeypatch.setattr(jax, "process_index", lambda: 1)
        f = FaultInjector("buddy_kill", at_step=5, rank=1,
                          once_marker=marker)
        assert f._marker_path().name == "once.rank1"
        # rank 2 (the mirror holder) checks ITS marker, not rank 1's
        f._marker_path().touch()
        assert not f._armed()
        monkeypatch.setattr(jax, "process_index", lambda: 2)
        assert f._armed()

    def test_kill_during_refresh_fires_mid_refresh_only(self, monkeypatch,
                                                        tmp_path):
        exits = []
        monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        f = FaultInjector("kill_during_refresh", at_step=5, rank=0,
                          once_marker=tmp_path / "m")
        f.on_batch_end(None, 9, {})  # step-driven path must ignore it
        assert exits == [] and not f.fired
        f.on_train_begin(None)  # registers with the refresh hook
        faults_lib.fire_refresh_kill(3)  # below at_step: inert
        assert exits == []
        faults_lib.fire_refresh_kill(5)
        assert exits == [17] and f.fired
        assert (tmp_path / "m").exists()
        faults_lib.fire_refresh_kill(6)  # fired once, stays inert
        assert exits == [17]
        f.on_train_end(None, None)  # deregisters
        assert f not in faults_lib._REFRESH_FAULTS

    def test_corrupt_latest_checkpoint_handles_sharded_dirs(
            self, devices, tmp_path):
        from distributed_tpu.resilience import corrupt_latest_checkpoint

        m = _model()
        m.build((8, 8))
        ck = ShardedCheckpointer(tmp_path)
        ck.save(m, step=3)
        ck.save(m, step=5)
        hit = corrupt_latest_checkpoint(tmp_path)
        assert hit == tmp_path / "ckpt-5" / "proc-0.npz"
        m2 = _model()
        m2.build((8, 8))
        assert ck.restore_into(m2) == 3  # fell back past the garbage


# ------------------------------------------------------- MTTR breakdown ----
class TestRecoveryRows:
    def _events(self):
        t = 100.0
        return [
            {"event": "fault_injected", "ts": t + 1.0, "mode": "kill"},
            {"event": "attempt_end", "ts": t + 3.0, "attempt": 1,
             "ok": False},
            {"event": "attempt_start", "ts": t + 3.1, "attempt": 2},
            {"event": "restore_begin", "ts": t + 5.0, "rank": 1},
            {"event": "restore_begin", "ts": t + 5.5, "rank": 0},
            {"event": "restore_end", "ts": t + 6.0, "rank": 0,
             "tier": "buddy", "step": 4, "disk_block_reads": 0},
            {"event": "post_restore_step", "ts": t + 7.5, "rank": 0},
            {"event": "attempt_end", "ts": t + 9.0, "attempt": 2, "ok": True},
        ]

    def test_breakdown(self):
        rows = recovery_rows(self._events())
        assert len(rows) == 1
        row = rows[0]
        assert row["failed_attempt"] == 1 and row["recovered_attempt"] == 2
        assert row["detect_s"] == 2.0
        assert row["gang_reform_s"] == 2.5   # rank-0 restore_begin
        assert row["restore_s"] == 0.5
        assert row["recompile_s"] == 1.5
        assert row["restore_tier"] == "buddy" and row["restore_step"] == 4
        assert row["disk_block_reads"] == 0
        assert row["total_to_first_step_s"] == 4.5

    def test_tolerates_missing_worker_events(self):
        events = [e for e in self._events()
                  if e["event"] in ("attempt_end", "attempt_start")]
        (row,) = recovery_rows(events)
        assert row["restore_s"] is None and row["restore_tier"] is None

    def test_no_relaunch_no_row(self):
        events = [{"event": "attempt_end", "ts": 1.0, "attempt": 1,
                   "ok": False}]
        assert recovery_rows(events) == []


def test_redundancy_report_math():
    rep = redundancy_report(100, 50, world=4)
    assert rep["overhead_ratio"] == 1.5 and rep["world"] == 4
    assert redundancy_report(0, 10)["overhead_ratio"] is None


# ------------------------------------------------------ gang fault matrix ----
def _losses_by_step(events):
    """step -> loss from rank-0 step_mark events; later attempts win."""
    out = {}
    for e in sorted((e for e in events if e["event"] == "step_mark"),
                    key=lambda e: e["attempt"]):
        if e.get("loss") is not None:
            out[e["step"]] = e["loss"]
    return out


def _matrix_gang(tmp, **kw):
    from gang_harness import recovery_gang

    kw.setdefault("width", 192)
    kw.setdefault("steps", 8)
    kw.setdefault("record_loss", True)
    kw.setdefault("timeout", 900.0)
    res, events, store = recovery_gang(tmp, **kw)
    shutil.rmtree(store, ignore_errors=True)
    return res, events


def _assert_parity(tmp, events, steps=8, **ref_kw):
    """Post-recovery loss-trajectory parity at the PR 7 tolerance: the
    recovered run's per-step losses equal the uninterrupted run's."""
    ref_res, ref_events = _matrix_gang(tmp, fault=None, steps=steps,
                                       **ref_kw)
    assert ref_res.ok and ref_res.attempts == 1
    got, ref = _losses_by_step(events), _losses_by_step(ref_events)
    assert set(got) == set(ref) == set(range(1, steps + 1))
    traj = np.array([got[s] for s in range(1, steps + 1)])
    ref_traj = np.array([ref[s] for s in range(1, steps + 1)])
    np.testing.assert_allclose(traj, ref_traj, rtol=2e-5, atol=0)


def _recovery(events):
    return next(e for e in events if e["event"] == "recovery")


@pytest.mark.slow
def test_gang_single_loss_buddy_restore(tmp_path):
    """ACCEPTANCE: kill one of two FSDP workers mid-run; the relaunched
    gang restores the WHOLE state from the surviving segment's mirrors —
    tier buddy, zero disk-block reads — and the completed run's loss
    trajectory matches the uninterrupted one."""
    res, events = _matrix_gang(tmp_path / "run",
                               fault="kill:at_step=5,rank=1")
    assert res.ok, [(r.index, r.error) for r in res.results]
    row = _recovery(events)
    assert row["restore_tier"] == "buddy"
    assert row["disk_block_reads"] == 0
    inv = next(e for e in events
               if e["event"] == "buddy_segments_invalidated")
    assert inv["ranks"] == [1]
    _assert_parity(tmp_path / "ref", events)


@pytest.mark.slow
def test_gang_buddy_pair_loss_disk_fallback(tmp_path):
    """Kill a worker AND its mirror holder (buddy_kill): the shard's live
    copy and its only mirror die together, so the recovery must come from
    the disk checkpoint — and still complete with trajectory parity."""
    res, events = _matrix_gang(
        tmp_path / "run", world=3, global_batch=48,
        fault="buddy_kill:at_step=5,rank=1")
    assert res.ok, [(r.index, r.error) for r in res.results]
    row = _recovery(events)
    assert row["restore_tier"] == "disk"
    assert row["disk_block_reads"] > 0
    inv = next(e for e in events
               if e["event"] == "buddy_segments_invalidated")
    assert inv["ranks"] == [1, 2]  # rank 1 and holder (1+1)%3
    _assert_parity(tmp_path / "ref", events, world=3, global_batch=48)


@pytest.mark.slow
def test_gang_kill_during_refresh_stale_rejection(tmp_path):
    """Die MID-refresh (self committed, peer push not): the store keeps
    only an older complete set while the disk checkpoint is newer — the
    stale mirrors must be rejected for the disk tier."""
    res, events = _matrix_gang(
        tmp_path / "run", fault="kill_during_refresh:at_step=8,rank=1",
        refresh_every=4, save_freq=1, steps=10)
    assert res.ok, [(r.index, r.error) for r in res.results]
    assert any(e["event"] == "buddy_refresh" for e in events)  # tier was live
    row = _recovery(events)
    assert row["restore_tier"] == "disk"
    assert row["restore_step"] > 4  # newer than the stale complete set
    _assert_parity(tmp_path / "ref", events, refresh_every=4, save_freq=1,
                   steps=10)


@pytest.mark.slow
def test_gang_stale_mirror_disk_wins(tmp_path):
    """Lose a worker while the mirrors are legitimately STALE (coarse
    refresh cadence vs per-step synchronous saves): selection must prefer
    the newer disk step over the older complete mirror set."""
    res, events = _matrix_gang(
        tmp_path / "run", fault="kill:at_step=7,rank=1",
        refresh_every=2, save_freq=1, sync_save=True)
    assert res.ok, [(r.index, r.error) for r in res.results]
    row = _recovery(events)
    assert row["restore_tier"] == "disk"
    assert row["restore_step"] == 7  # sync save at the kill step
    _assert_parity(tmp_path / "ref", events, refresh_every=2, save_freq=1,
                   sync_save=True)
