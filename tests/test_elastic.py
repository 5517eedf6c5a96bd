"""Elastic gangs: permanent-loss detection, gang re-formation at a new
world size, grow-back under a capacity probe, and the cross-layer seams
that make a resized gang correct (cluster init override, pipeline
reshard, per-host world guard).

The acceptance bar (ISSUE 7): a supervised run with a repeatedly-injected
permanent rank failure at N=4 completes at N=2 with a loss trajectory
matching the equivalent-batch-math uninterrupted run under the documented
equivalence contract (docs/RESILIENCE.md "Elastic gangs"), and a
capacity-regain run grows 2->4. The real-gang end-to-ends are @slow; the
policy/ledger/supervisor/cluster/pipeline units stay in tier-1.
"""


import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.cluster import config as cluster_config
from distributed_tpu.cluster import init as cluster_init
from distributed_tpu.data.pipeline import Pipeline, native_available
from distributed_tpu.launch import WorkerResult
from distributed_tpu.resilience import (
    PREEMPTED_EXIT_CODE,
    ElasticPolicy,
    FailureLedger,
    RestartPolicy,
    Supervisor,
)
from distributed_tpu.resilience.supervisor import (
    _classify_preemption,
    _gang_collateral,
    _initiated,
)
from distributed_tpu.utils.events import EventLog, read_events


# ---------------------------------------------------------------- policy ----
class TestElasticPolicy:
    def test_validation(self):
        with pytest.raises(ValueError):
            ElasticPolicy(min_workers=0)
        with pytest.raises(ValueError):
            ElasticPolicy(min_workers=4, max_workers=2)
        with pytest.raises(ValueError):
            ElasticPolicy(failure_threshold=0)
        with pytest.raises(ValueError):
            ElasticPolicy(divisor_of=0)
        with pytest.raises(ValueError):
            ElasticPolicy(max_resizes=-1)

    def test_snap_clamps_into_bounds(self):
        p = ElasticPolicy(min_workers=2, max_workers=8)
        assert p.snap(16, 4) == 8   # explicit max wins over default
        assert p.snap(1, 4) == 2    # below the floor clamps UP to it
        assert p.snap(5, 4) == 5
        # max_workers=None: the supervisor's launch size is the ceiling
        assert ElasticPolicy(min_workers=1).snap(16, 4) == 4

    def test_snap_divisor_rounds_down_to_exact_batch_math(self):
        p = ElasticPolicy(min_workers=2, max_workers=8, divisor_of=64)
        assert p.snap(3, 8) == 2    # 64 % 3 != 0 -> largest divisor <= 3
        assert p.snap(7, 8) == 4
        assert p.snap(8, 8) == 8
        # No divisor in [min_workers, n]: infeasible, caller keeps fixed-N
        assert ElasticPolicy(min_workers=3, divisor_of=4).snap(3, 8) is None


# ---------------------------------------------------------------- ledger ----
class TestFailureLedger:
    def test_consecutive_initiator_counting(self):
        led = FailureLedger()
        led.record({1})
        led.record({1, 2})
        assert led.counts == {1: 2, 2: 1}
        assert led.permanent(2) == {1}
        # rank 1 NOT an initiator this attempt: its streak resets
        led.record({2})
        assert led.counts == {1: 0, 2: 2}
        assert led.permanent(2) == {2}

    def test_unattributable_failure_moves_nothing(self):
        led = FailureLedger()
        led.record({3})
        led.record(())  # launch error / whole-gang timeout: no blame
        assert led.counts == {3: 1}
        assert led.attempts_recorded == 1

    def test_reset(self):
        led = FailureLedger()
        led.record({0})
        led.reset()
        assert led.counts == {} and led.permanent(1) == set()


# -------------------------------------------------- failure classification --
def _row(i=0, *, ok=False, code=1, error="exit code 1", disposition=None):
    return WorkerResult(index=i, ok=ok, error=error, exit_code=code,
                        disposition=disposition)


class TestClassification:
    def test_gang_collateral_by_disposition(self):
        assert _gang_collateral(_row(disposition="gang_killed", code=None))
        assert not _gang_collateral(_row(disposition="liveness_killed",
                                         code=None))
        assert not _gang_collateral(_row(disposition="exited"))

    def test_legacy_rows_fall_back_to_exit_disposition(self):
        # No disposition, no exit code, no error: a launcher-killed peer.
        assert _gang_collateral(_row(code=None, error=None))
        assert _gang_collateral(
            _row(code=None, error="killed after peer failure (gang semantics)"))
        assert not _gang_collateral(
            _row(code=None, error="liveness timeout (no heartbeat for 3s)"))
        assert not _gang_collateral(_row(code=None, error="timeout"))
        assert not _gang_collateral(_row(code=17))

    def test_preemption_with_error_none_peer_row(self):
        """REGRESSION (ISSUE 7 satellite): a peer row with error=None used
        to fail the '"peer failure" in error' string match and burn restart
        budget on a clean preemption."""
        failed = [
            _row(0, code=PREEMPTED_EXIT_CODE, error=None),
            _row(1, code=None, error=None),
        ]
        assert _classify_preemption(failed)

    def test_preemption_not_masked_by_independent_fault(self):
        failed = [
            _row(0, code=PREEMPTED_EXIT_CODE),
            _row(1, code=17, disposition="exited"),  # its own crash
        ]
        assert not _classify_preemption(failed)

    def test_initiated_excludes_collateral_preemption_and_timeout(self):
        assert _initiated(_row(code=17, disposition="exited"))
        assert _initiated(_row(code=None, disposition="liveness_killed"))
        assert not _initiated(_row(code=None, disposition="gang_killed"))
        assert not _initiated(_row(code=PREEMPTED_EXIT_CODE))
        assert not _initiated(_row(code=None, disposition="timeout"))
        assert not _initiated(_row(ok=True, code=0, error=None))


# ------------------------------------------------------- supervisor elastic --
def _ok(i=0):
    return WorkerResult(index=i, ok=True, value="fine", exit_code=0,
                        disposition="exited")


def _fail(i=0, code=17):
    return WorkerResult(index=i, ok=False, error=f"exit code {code}",
                        exit_code=code, disposition="exited")


def _collateral(i=0):
    return WorkerResult(index=i, ok=False,
                        error="killed after peer failure (gang semantics)",
                        exit_code=None, disposition="gang_killed")


def _gang_fail(world, initiator):
    """One attempt's rows: `initiator` crashed, everyone else gang-killed."""
    return [
        _fail(i) if i == initiator else _collateral(i) for i in range(world)
    ]


def _gang_ok(world):
    return [_ok(i) for i in range(world)]


class FakeLauncher:
    """Scripted sized launcher: each entry is a CALLABLE of the requested
    num_workers (or a plain result list / 'raise'). Records the world size
    and env of every launch."""

    def __init__(self, script):
        self.script = list(script)
        self.env_extra = {}
        self.seen_worlds = []
        self.seen_env = []

    def run(self, argv, num_workers, **kw):
        self.seen_worlds.append(num_workers)
        self.seen_env.append(dict(self.env_extra))
        out = self.script.pop(0)
        if out == "raise":
            raise RuntimeError("preflight failed for relaunch")
        return out(num_workers) if callable(out) else out


class TestSupervisorElastic:
    def test_attribution_shrink_after_threshold_is_budget_free(self, tmp_path):
        """Rank 1 kills the 4-gang twice -> permanently lost -> the gang
        re-forms at 2 (divisor_of=64 snaps 3 down) WITHOUT burning a second
        restart, and the run completes there."""
        launcher = FakeLauncher([
            lambda w: _gang_fail(w, 1),
            lambda w: _gang_fail(w, 1),
            lambda w: _gang_ok(w),
        ])
        log = EventLog(tmp_path / "ev.jsonl")
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            policy=RestartPolicy(max_restarts=1, backoff=0.0),
            elastic=ElasticPolicy(min_workers=2, failure_threshold=2,
                                  divisor_of=64),
            event_log=log, sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.attempts == 3
        assert out.restarts_used == 1  # only the pre-detection failure
        assert out.resizes == 1 and out.world_size == 2
        assert launcher.seen_worlds == [4, 4, 2]
        # The relaunched workers learn their world from the env override.
        assert [e["DTPU_ELASTIC_WORLD"] for e in launcher.seen_env] == [
            "4", "4", "2"]
        events = log.read()
        resize = next(e for e in events if e["event"] == "gang_resize")
        assert resize["from_world"] == 4 and resize["to_world"] == 2
        assert resize["reason"] == "shrink"
        assert resize["trigger"] == "attribution"
        assert resize["lost_ranks"] == [1]
        starts = [e for e in events if e["event"] == "attempt_start"]
        assert [e["world_size"] for e in starts] == [4, 4, 2]
        restart = next(e for e in events if e["event"] == "restart"
                       and e["reason"] == "resize")
        assert restart["world_size"] == 2 and restart["resizes"] == 1
        done = next(e for e in events if e["event"] == "run_complete")
        assert done["resizes"] == 1 and done["world_size"] == 2

    def test_shrink_prevents_budget_exhaustion(self):
        """The ISSUE's motivating failure: with max_restarts=1 a fixed-size
        supervisor would die on the second rank-1 kill; elastic re-forms
        instead and finishes."""
        launcher = FakeLauncher([
            lambda w: _gang_fail(w, 1),
            lambda w: _gang_fail(w, 1),
            lambda w: _gang_ok(w),
        ])
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            policy=RestartPolicy(max_restarts=1, backoff=0.0),
            elastic=ElasticPolicy(min_workers=1, failure_threshold=2),
            sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.world_size == 3  # no divisor constraint
        # Fixed-size control: same script, no elastic -> budget exhausted.
        fixed = Supervisor(
            ["prog"], 4,
            launcher=FakeLauncher([lambda w: _gang_fail(w, 1)] * 3),
            policy=RestartPolicy(max_restarts=1, backoff=0.0),
            sleep=lambda s: None,
        )
        assert not fixed.run(timeout=5).ok

    def test_probe_shrinks_immediately_and_grows_back(self, tmp_path):
        """A capacity probe needs no attribution: capacity 2 resizes the
        next relaunch; capacity 4 grows it back at a later boundary. (The
        first probe is the pre-launch capacity check: full.)"""
        capacity = iter([4, 2, 4])
        launcher = FakeLauncher([
            lambda w: _gang_fail(w, 1),   # probe -> 2: shrink
            lambda w: _gang_fail(w, 0),   # transient at 2; probe -> 4: grow
            lambda w: _gang_ok(w),
        ])
        log = EventLog(tmp_path / "ev.jsonl")
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            policy=RestartPolicy(max_restarts=2, backoff=0.0),
            elastic=ElasticPolicy(min_workers=2, max_workers=4,
                                  probe=lambda: next(capacity)),
            event_log=log, sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.resizes == 2 and out.world_size == 4
        assert out.restarts_used == 0  # both boundaries resized: budget-free
        assert launcher.seen_worlds == [4, 2, 4]
        reasons = [e["reason"] for e in log.read()
                   if e["event"] == "gang_resize"]
        assert reasons == ["shrink", "grow"]

    def test_initial_probe_launches_at_available_capacity(self):
        launcher = FakeLauncher([lambda w: _gang_ok(w)])
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            elastic=ElasticPolicy(min_workers=1, probe=lambda: 2),
            sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.world_size == 2 and out.resizes == 1
        assert launcher.seen_worlds == [2]

    def test_max_resizes_caps_reformation(self, tmp_path):
        """An oscillating probe cannot resize forever: past max_resizes the
        supervisor falls back to fixed-size budget accounting."""
        capacity = iter([4, 2, 4, 2, 4])
        launcher = FakeLauncher([lambda w: _gang_fail(w, 0)] * 5)
        log = EventLog(tmp_path / "ev.jsonl")
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            policy=RestartPolicy(max_restarts=1, backoff=0.0),
            elastic=ElasticPolicy(min_workers=2, max_workers=4,
                                  probe=lambda: next(capacity),
                                  max_resizes=2),
            event_log=log, sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert not out.ok and out.resizes == 2
        kinds = [e["event"] for e in log.read()]
        assert "resize_cap_exhausted" in kinds
        assert kinds[-1] == "budget_exhausted"

    def test_non_elastic_behavior_unchanged(self, tmp_path):
        """No ElasticPolicy: no resize events, no DTPU_ELASTIC_WORLD in the
        worker env, fixed world in every event."""
        launcher = FakeLauncher([lambda w: _gang_fail(w, 1),
                                 lambda w: _gang_ok(w)])
        log = EventLog(tmp_path / "ev.jsonl")
        sup = Supervisor(["prog"], 4, launcher=launcher,
                         policy=RestartPolicy(max_restarts=2, backoff=0.0),
                         event_log=log, sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.resizes == 0 and out.world_size == 4
        assert launcher.seen_worlds == [4, 4]
        assert all("DTPU_ELASTIC_WORLD" not in e for e in launcher.seen_env)
        assert not [e for e in log.read() if e["event"] == "gang_resize"]

    def test_launch_error_rows_are_unattributable(self):
        """A relaunch whose preflight raises yields launch_error rows for
        every rank; the ledger must not blame anyone (a dead coordinator
        is not rank 0's fault), so no spurious shrink."""
        launcher = FakeLauncher(["raise", "raise", lambda w: _gang_ok(w)])
        sup = Supervisor(
            ["prog"], 4, launcher=launcher,
            policy=RestartPolicy(max_restarts=2, backoff=0.0),
            elastic=ElasticPolicy(min_workers=1, failure_threshold=2),
            sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.resizes == 0 and out.world_size == 4


class FakeSSHLauncher:
    """Host-list launcher shape (no env_extra attribute, no num_workers
    arg): the supervisor must resize it by rewriting the host list."""

    def __init__(self, hosts, script):
        self.hosts = list(hosts)
        self.script = list(script)
        self.seen_hosts = []

    def run(self, argv, *, env_extra=None, **kw):
        self.seen_hosts.append(list(self.hosts))
        out = self.script.pop(0)
        return out(len(self.hosts)) if callable(out) else out


class TestSupervisorElasticHosts:
    def test_shrink_excludes_the_lost_hosts(self):
        """4-host gang, host b (rank 1) permanently failing: the re-formed
        2-gang must run on surviving hosts — routed AROUND b, not a naive
        prefix truncation that would keep it."""
        launcher = FakeSSHLauncher(
            ["a", "b", "c", "d"],
            [lambda w: _gang_fail(w, 1),
             lambda w: _gang_fail(w, 1),
             lambda w: _gang_ok(w)],
        )
        sup = Supervisor(
            ["prog"], launcher=launcher,
            policy=RestartPolicy(max_restarts=2, backoff=0.0),
            elastic=ElasticPolicy(min_workers=2, failure_threshold=2,
                                  divisor_of=64),
            sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.world_size == 2
        assert launcher.seen_hosts == [
            ["a", "b", "c", "d"], ["a", "b", "c", "d"], ["a", "c"]]
        # the launcher's own host list is restored after every attempt
        assert launcher.hosts == ["a", "b", "c", "d"]

    def test_probe_grow_ceiling_is_the_launch_size(self):
        """REGRESSION: with max_workers unset on a host-list launcher the
        grow ceiling must be the LAUNCH world (len(hosts)), not the sized
        launcher's num_workers default (1). Shrunk hosts are re-admitted
        in original order on grow."""
        capacity = iter([2, 4])
        launcher = FakeSSHLauncher(
            ["a", "b", "c", "d"],
            [lambda w: _gang_fail(w, 0), lambda w: _gang_ok(w)],
        )
        sup = Supervisor(
            ["prog"], launcher=launcher,
            policy=RestartPolicy(max_restarts=2, backoff=0.0),
            elastic=ElasticPolicy(min_workers=2,
                                  probe=lambda: next(capacity)),
            sleep=lambda s: None,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.world_size == 4 and out.resizes == 2
        assert launcher.seen_hosts == [["a", "b"], ["a", "b", "c", "d"]]


# ------------------------------------------------------ cluster init seams --
class TestElasticWorldOverride:
    def _spec4(self):
        return cluster_config.ClusterSpec(
            workers=[f"10.0.0.{i}:8476" for i in range(4)], index=1)

    def test_override_truncates_inherited_spec(self, monkeypatch):
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "2")
        out = cluster_init._apply_elastic_world(self._spec4())
        assert out.num_processes == 2 and out.index == 1
        assert out.workers == ["10.0.0.0:8476", "10.0.0.1:8476"]

    def test_rank_outside_world_refuses_to_join(self, monkeypatch):
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "1")
        with pytest.raises(ValueError, match="outside the elastic world"):
            cluster_init._apply_elastic_world(self._spec4())

    def test_grow_past_inherited_list_keeps_spec(self, monkeypatch):
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "8")
        out = cluster_init._apply_elastic_world(self._spec4())
        assert out.num_processes == 4  # warn + keep; no invented addresses

    def test_no_override_is_identity(self, monkeypatch):
        monkeypatch.delenv(cluster_init.ELASTIC_WORLD_ENV, raising=False)
        spec = self._spec4()
        assert cluster_init._apply_elastic_world(spec) is spec

    def test_bad_override_raises(self, monkeypatch):
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "zero")
        with pytest.raises(ValueError, match="integer"):
            cluster_init._apply_elastic_world(self._spec4())
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "0")
        with pytest.raises(ValueError, match=">= 1"):
            cluster_init._apply_elastic_world(self._spec4())

    def test_initialize_honors_override_over_env_config(self, monkeypatch):
        """End-to-end through initialize(): an inherited 4-worker
        DTPU_CONFIG with DTPU_ELASTIC_WORLD=2 resolves to a 2-process
        spec. (_initialized is patched True: the backend handshake is the
        launcher e2e's job, resolution is this test's.)"""
        spec = cluster_config.ClusterSpec(
            workers=[f"127.0.0.1:{9000 + i}" for i in range(4)], index=0)
        monkeypatch.setenv(cluster_config.ENV_VAR, spec.to_json())
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "2")
        monkeypatch.setattr(cluster_init, "_initialized", True)
        out = cluster_init.initialize()
        assert out.num_processes == 2 and out.index == 0

    def test_explicit_spec_is_never_rewritten(self, monkeypatch):
        monkeypatch.setenv(cluster_init.ELASTIC_WORLD_ENV, "1")
        monkeypatch.setattr(cluster_init, "_initialized", True)
        spec = cluster_config.ClusterSpec(workers=["localhost:1"], index=0)
        out = cluster_init.initialize(spec)
        assert out.num_processes == 1


class TestResetForRelaunch:
    def test_clears_cached_coordinator_spec(self, monkeypatch):
        """A re-formed in-process test gang must not silently reuse the
        stale cached spec (ISSUE 7 satellite). The n=1 coordinator path
        caches without touching jax.distributed, so it can prove the reset
        in-process."""
        monkeypatch.setattr(cluster_init, "_initialized", False)
        monkeypatch.setattr(cluster_init, "_gathered_cache", None)
        first = cluster_init.initialize(coordinator="127.0.0.1:12345",
                                        num_processes=1, process_id=0)
        assert first.workers == ["127.0.0.1:12345"]
        # Repeat call: answered from the cache, even with different args.
        again = cluster_init.initialize(coordinator="127.0.0.1:54321",
                                        num_processes=1, process_id=0)
        assert again is first
        cluster_init.reset_for_relaunch()
        assert not cluster_init.is_initialized()
        fresh = cluster_init.initialize(coordinator="127.0.0.1:54321",
                                        num_processes=1, process_id=0)
        assert fresh.workers == ["127.0.0.1:54321"]

    def test_shutdown_without_runtime_is_safe(self, monkeypatch):
        monkeypatch.setattr(cluster_init, "_initialized", False)
        monkeypatch.setattr(cluster_init, "_gathered_cache", object())
        dtpu.cluster.shutdown()
        assert cluster_init._gathered_cache is None


# --------------------------------------------------------- pipeline reshard --
def _data(n=64, row=6, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 256, size=(n, row), dtype=np.uint8)
    y = rng.integers(0, 10, size=n).astype(np.int32)
    return x, y


class TestPipelineReshard:
    @pytest.mark.parametrize("use_native", [False, True], ids=["py", "native"])
    def test_reshard_preserves_the_global_stream_bit_exactly(self, use_native):
        """Consume at (0,4), reshard to (0,2) mid-stream: from the resize
        on, the new slices of each global batch must still concatenate into
        exactly the unsharded stream — the data half of the elastic
        batch-math contract, pinned bit-exactly."""
        if use_native and not native_available():
            pytest.skip("native pipeline unavailable")
        x, y = _data()
        with Pipeline(x, y, 16, seed=3, use_native=use_native) as full, \
             Pipeline(x, y, 16, seed=3, use_native=use_native,
                      shard=(0, 4)) as a, \
             Pipeline(x, y, 16, seed=3, use_native=use_native,
                      shard=(1, 2)) as b:
            for _ in range(3):
                next(full), next(a)
            a.reshard((0, 2))
            assert a.shard == (0, 2) and a.batch_shape == (8, 6)
            b.seek(3)
            for _ in range(5):  # crosses the pass boundary (reshuffle)
                xf, yf = next(full)
                x0, y0 = next(a)
                x1, y1 = next(b)
                np.testing.assert_array_equal(np.concatenate([x0, x1]), xf)
                np.testing.assert_array_equal(np.concatenate([y0, y1]), yf)

    def test_reshard_to_unsharded_and_auto(self):
        x, y = _data()
        with Pipeline(x, y, 16, seed=1, use_native=False,
                      shard=(1, 2)) as p:
            next(p)
            p.reshard(None)
            assert p.shard is None and p.batch_shape == (16, 6)
            # single-process runtime: auto == unsharded
            p.reshard("auto")
            assert p.shard is None and p.shard_rows == 16
        with Pipeline(x, y, 16, seed=1, use_native=False,
                      shard="auto") as auto:
            assert auto.shard is None

    def test_reshard_validation(self):
        x, y = _data()
        with Pipeline(x, y, 16, use_native=False) as p:
            with pytest.raises(ValueError, match="not divisible"):
                p.reshard((0, 3))
            with pytest.raises(ValueError, match="shard index"):
                p.reshard((2, 2))
            with pytest.raises(ValueError, match="'auto'"):
                p.reshard("automatic")
        with pytest.raises(ValueError, match="closed"):
            p.reshard((0, 2))

    def test_fit_rejects_stale_shard_count(self):
        """A pipeline whose shard count disagrees with the live world size
        (the canonical stale-handle-across-a-resize bug) fails loudly with
        the reshard remedy, instead of feeding the wrong batch fraction."""
        x, y = _data(64, 6)
        m = dtpu.Model(dtpu.nn.Sequential(
            [dtpu.nn.Dense(16, activation="relu"), dtpu.nn.Dense(10)]))
        m.compile(optimizer=dtpu.optim.SGD(0.1),
                  loss="sparse_categorical_crossentropy")
        m.build((6,))
        with Pipeline(x, y, 16, shard=(0, 2), use_native=False) as p:
            with pytest.raises(ValueError, match="reshard"):
                m.fit(p, epochs=1, verbose=0)
            with pytest.raises(ValueError, match="reshard"):
                m.evaluate(p)


# ----------------------------------------------------------- end to end -----
def _losses_by_step(events):
    """step -> loss from rank-0 step_mark events; later attempts win (the
    step that finally advanced the run is the one the trajectory keeps)."""
    out = {}
    for e in sorted((e for e in events if e["event"] == "step_mark"),
                    key=lambda e: e["attempt"]):
        if e.get("loss") is not None:
            out[e["step"]] = (e["loss"], e["world"])
    return out


@pytest.mark.slow
def test_elastic_shrink_e2e_4_to_2_with_loss_equivalence(tmp_path):
    """ACCEPTANCE (ISSUE 7): a supervised run with a repeatedly-injected
    permanent rank-1 failure at N=4 re-forms at N=2 (attribution + divisor
    snap), restores the 4-process sharded checkpoint into the 2-process
    gang through the block index, and completes with a loss trajectory
    matching the equivalent-batch-math uninterrupted run under the
    documented equivalence contract: identical global batches (bit-exact,
    pinned by TestPipelineReshard), loss equal to f32
    reduction-regrouping tolerance (docs/RESILIENCE.md "Elastic gangs")."""
    from gang_harness import elastic_gang

    steps = 10
    res, events = elastic_gang(
        tmp_path / "run", world=4, min_workers=2, global_batch=64,
        steps=steps, fault="kill:at_step=4,rank=1", fault_above=2,
        failure_threshold=2, max_restarts=3, record_loss=True,
        timeout=900.0,
    )
    assert res.ok, [(r.index, r.error, r.log_tail[-500:]) for r in res.results]
    assert res.world_size == 2 and res.resizes == 1
    assert res.restarts_used == 1  # one pre-detection failure, then resize
    resize = next(e for e in events if e["event"] == "gang_resize")
    assert (resize["from_world"], resize["to_world"]) == (4, 2)
    assert resize["lost_ranks"] == [1]
    # every attempt's world size is in the log, and the relaunch env told
    # the workers (worker rows report the world they actually formed)
    assert [r.value["world"] for r in res.results] == [2, 2]
    assert all(r.value["final_step"] == steps for r in res.results)

    # The equivalent-batch-math uninterrupted run: ONE process, same seed,
    # same GLOBAL batch stream (shard=(0,1) slices are the whole batch).
    ref_res, ref_events = elastic_gang(
        tmp_path / "ref", world=1, min_workers=1, global_batch=64,
        steps=steps, record_loss=True, timeout=900.0,
    )
    assert ref_res.ok and ref_res.attempts == 1

    got = _losses_by_step(events)
    ref = _losses_by_step(ref_events)
    assert set(got) == set(ref) == set(range(1, steps + 1))
    # Steps 1..4 ran at world 4, the rest at world 2 after the resize.
    assert got[4][1] == 4 and got[5][1] == 2 and got[steps][1] == 2
    traj = np.array([got[s][0] for s in range(1, steps + 1)])
    ref_traj = np.array([ref[s][0] for s in range(1, steps + 1)])
    np.testing.assert_allclose(traj, ref_traj, rtol=2e-5, atol=0)


@pytest.mark.slow
def test_elastic_grow_e2e_2_to_4_on_capacity_regain(tmp_path):
    """ACCEPTANCE (ISSUE 7): capacity regained (probe flips 2 -> 4 at the
    restart boundary) grows the gang 2 -> 4; the 2-process sharded
    checkpoint restores into the 4-process gang and the run completes."""
    from gang_harness import elastic_gang

    cap = tmp_path / "capacity"
    cap.write_text("2")
    res, events = elastic_gang(
        tmp_path / "run", world=2, min_workers=2, max_workers=4,
        global_batch=64, steps=8, fault="kill:at_step=3,rank=0",
        fault_above=0, probe_file=cap, cap_flip_to=4, cap_flip_at=3,
        max_restarts=3, timeout=900.0,
    )
    assert res.ok, [(r.index, r.error, r.log_tail[-500:]) for r in res.results]
    assert res.world_size == 4 and res.resizes == 1
    resize = next(e for e in events if e["event"] == "gang_resize")
    assert (resize["from_world"], resize["to_world"]) == (2, 4)
    assert resize["reason"] == "grow" and resize["trigger"] == "probe"
    assert [r.value["world"] for r in res.results] == [4] * 4
    assert all(r.value["final_step"] == 8 for r in res.results)
