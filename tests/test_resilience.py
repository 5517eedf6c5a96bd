"""Resilience subsystem: supervisor restart/resume, fault injection,
backoff/budget policy, preemption handling, corrupt-checkpoint fallback.

The acceptance bar (ISSUE 2): a fault-injected worker kill mid-epoch is
followed by automatic supervisor restart + checkpoint resume, and the
finished run's params match an uninterrupted run's. The full fault matrix
(kill / hang / slow-heartbeat / corrupt-checkpoint) is @slow; one kill
end-to-end plus all policy/unit coverage stays in tier-1.
"""

import json
import os
import signal
import socket
import sys
import textwrap
import types
from pathlib import Path

import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.cluster import net
from distributed_tpu.launch import LocalLauncher, WorkerResult
from distributed_tpu.resilience import (
    PREEMPTED_EXIT_CODE,
    FaultInjector,
    PreemptionHandler,
    RestartPolicy,
    Supervisor,
    corrupt_latest_checkpoint,
    read_resume_marker,
)
from distributed_tpu.training.callbacks import LambdaCallback, ModelCheckpoint
from distributed_tpu.utils.events import EventLog, read_events

REPO = str(Path(__file__).resolve().parent.parent)


def _small_model():
    model = dtpu.Model(dtpu.models.mnist_cnn())
    model.compile(
        optimizer=dtpu.optim.SGD(0.05),
        loss="sparse_categorical_crossentropy",
        metrics=["accuracy"],
    )
    return model


def _data(n=128):
    x, y = dtpu.data.synthetic_images(n, (28, 28), 10, seed=3)
    return x[..., None].astype(np.float32) / 255.0, y


# ---------------------------------------------------------------- policy ----
class TestRestartPolicy:
    @pytest.mark.smoke
    def test_backoff_schedule_is_bounded_exponential(self):
        p = RestartPolicy(backoff=1.0, backoff_factor=2.0, backoff_max=5.0)
        assert [p.delay(i) for i in (1, 2, 3, 4, 5)] == [1, 2, 4, 5, 5]

    def test_budget(self):
        p = RestartPolicy(max_restarts=2)
        assert p.allows_restart(0) and p.allows_restart(1)
        assert not p.allows_restart(2)
        assert RestartPolicy(max_restarts=0).allows_restart(0) is False

    def test_preemption_cap(self):
        p = RestartPolicy(max_preemptions=1)
        assert p.allows_preemption_restart(0)
        assert not p.allows_preemption_restart(1)

    def test_validation(self):
        with pytest.raises(ValueError):
            RestartPolicy(max_restarts=-1)
        with pytest.raises(ValueError):
            RestartPolicy(backoff_factor=0.5)
        with pytest.raises(ValueError):
            RestartPolicy(backoff=2.0, backoff_max=1.0)
        with pytest.raises(ValueError):
            RestartPolicy().delay(0)


# ------------------------------------------------------------- event log ----
class TestEventLog:
    def test_roundtrip_and_torn_tail(self, tmp_path):
        log = EventLog(tmp_path / "ev.jsonl")
        log.emit("restart", attempt=2, delay=1.5)
        log.emit("run_complete", attempts=3)
        # A writer killed mid-append leaves a torn line; reads must skip it.
        with open(log.path, "a") as f:
            f.write('{"event": "torn')
        events = log.read()
        assert [e["event"] for e in events] == ["restart", "run_complete"]
        assert events[0]["attempt"] == 2 and "ts" in events[0]

    def test_ambient_emit_noop_without_env(self, monkeypatch):
        from distributed_tpu.utils import events as ev

        monkeypatch.delenv(ev.ENV_VAR, raising=False)
        assert ev.emit("whatever") is None

    def test_ambient_emit_with_env(self, monkeypatch, tmp_path):
        from distributed_tpu.utils import events as ev

        path = tmp_path / "amb.jsonl"
        monkeypatch.setenv(ev.ENV_VAR, str(path))
        assert ev.emit("ping", x=1)["x"] == 1
        assert read_events(path)[0]["event"] == "ping"


# ------------------------------------------------------- net preflight ------
class TestPreflightBackoff:
    def test_backoff_schedule(self):
        assert net.backoff_schedule(1) == []
        assert net.backoff_schedule(5, backoff=0.5, backoff_max=2.0) == [
            0.5, 1.0, 2.0, 2.0,
        ]
        with pytest.raises(ValueError):
            net.backoff_schedule(0)

    def test_retries_until_worker_boots(self, monkeypatch):
        """A still-booting worker (connect timeouts, then up) passes the
        preflight instead of failing the first probe."""
        calls, sleeps = [], []

        class _Conn:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        def fake_create(addr, timeout=None):
            calls.append(addr)
            if len(calls) < 3:
                raise socket.timeout("still booting")
            return _Conn()

        monkeypatch.setattr(net.socket, "create_connection", fake_create)
        ok = net.check_reachable("10.9.9.9:8476", timeout=0.1, attempts=4,
                                 backoff=0.1, _sleep=sleeps.append)
        assert ok and len(calls) == 3
        assert sleeps == [0.1, 0.2]  # exponential, only between failures

    def test_refused_is_up_without_retry(self, monkeypatch):
        sleeps = []

        def fake_create(addr, timeout=None):
            raise ConnectionRefusedError

        monkeypatch.setattr(net.socket, "create_connection", fake_create)
        assert net.check_reachable("h:1", attempts=5, _sleep=sleeps.append)
        assert sleeps == []  # refusal means up: answer immediately

    def test_still_down_after_budget(self, monkeypatch):
        sleeps = []

        def fake_create(addr, timeout=None):
            raise OSError("no route")

        monkeypatch.setattr(net.socket, "create_connection", fake_create)
        assert not net.check_reachable("h:1", attempts=3, backoff=0.1,
                                       _sleep=sleeps.append)
        assert len(sleeps) == 2  # attempts-1 sleeps, then give up


# ------------------------------------------- checkpoint latest + corrupt ----
class TestLatestPointerAndCorruptFallback:
    def _trained(self, tmp_path, steps=(2, 4)):
        model = _small_model()
        model.build((28, 28, 1), seed=0)
        ckpt = dtpu.Checkpointer(tmp_path, keep=10)
        for s in steps:
            ckpt.save(model, step=s)
        return model, ckpt

    def test_pointer_written_atomically_and_read(self, tmp_path):
        _, ckpt = self._trained(tmp_path)
        pointer = tmp_path / "latest"
        assert json.loads(pointer.read_text()) == {"step": 4}
        assert ckpt.latest_step() == 4
        assert not list(tmp_path.glob("*.tmp"))  # no tmp litter

    def test_corrupt_pointer_falls_back_to_scan(self, tmp_path):
        _, ckpt = self._trained(tmp_path)
        (tmp_path / "latest").write_text('{"st')  # torn write simulation
        assert ckpt.latest_step() == 4

    def test_stale_pointer_loses_to_newer_file(self, tmp_path):
        # Crash between npz rename and pointer write: ckpt-6 exists,
        # pointer still says 4 — the newer complete file wins.
        model, ckpt = self._trained(tmp_path)
        from distributed_tpu.checkpoint.core import save_npz

        save_npz(ckpt._path(6), {"params": model.params,
                                 "state": {}, "opt_state": model.opt_state},
                 {"step": 6, "seed": 0, "input_shape": [28, 28, 1]})
        assert json.loads((tmp_path / "latest").read_text())["step"] == 4
        assert ckpt.latest_step() == 6

    def test_corrupt_latest_restores_previous_step(self, tmp_path, monkeypatch):
        from distributed_tpu.utils import events as ev

        monkeypatch.setenv(ev.ENV_VAR, str(tmp_path / "ev.jsonl"))
        _, ckpt = self._trained(tmp_path)
        assert corrupt_latest_checkpoint(tmp_path).name == "ckpt-4.npz"
        assert ckpt.is_valid(2) and not ckpt.is_valid(4)
        assert ckpt.latest_valid_step() == 2

        fresh = _small_model()
        step = dtpu.Checkpointer(tmp_path).restore_into(fresh)
        assert step == 2
        kinds = [e["event"] for e in read_events(tmp_path / "ev.jsonl")]
        assert "corrupt_checkpoint_skipped" in kinds

    def test_explicit_corrupt_step_raises(self, tmp_path):
        self._trained(tmp_path)
        corrupt_latest_checkpoint(tmp_path)
        fresh = _small_model()
        with pytest.raises((ValueError, OSError, KeyError)):
            dtpu.Checkpointer(tmp_path).restore_into(fresh, step=4)

    def test_all_corrupt_raises_filenotfound(self, tmp_path):
        self._trained(tmp_path, steps=(3,))
        corrupt_latest_checkpoint(tmp_path)
        fresh = _small_model()
        with pytest.raises(FileNotFoundError, match="corrupt"):
            dtpu.Checkpointer(tmp_path).restore_into(fresh)


# --------------------------------------------------------- fault injector ---
class TestFaultInjector:
    def test_from_env(self, monkeypatch, tmp_path):
        monkeypatch.setenv(
            "DTPU_FAULT", "kill:at_step=7,rank=all,exit_code=9")
        monkeypatch.setenv("DTPU_FAULT_MARKER", str(tmp_path / "m"))
        f = FaultInjector.from_env()
        assert (f.mode, f.at_step, f.rank, f.exit_code) == ("kill", 7, None, 9)
        assert f.once_marker == tmp_path / "m"

    def test_from_env_absent(self, monkeypatch):
        monkeypatch.delenv("DTPU_FAULT", raising=False)
        assert FaultInjector.from_env() is None

    def test_bad_mode_and_keys(self, monkeypatch):
        with pytest.raises(ValueError):
            FaultInjector("explode")
        with pytest.raises(ValueError):
            FaultInjector("corrupt_checkpoint")  # needs directory=
        monkeypatch.setenv("DTPU_FAULT", "kill:frequency=2")
        with pytest.raises(ValueError):
            FaultInjector.from_env()

    def test_once_marker_disarms(self, tmp_path):
        marker = tmp_path / "fired"
        marker.touch()
        f = FaultInjector("kill", at_step=0, once_marker=marker)
        # Would os._exit if armed; reaching the next line proves disarm.
        f.on_batch_end(types.SimpleNamespace(step=5), 5, {})
        assert not f.fired


# -------------------------------------------------------- supervisor unit ---
def _ok(i=0):
    return WorkerResult(index=i, ok=True, value="fine", exit_code=0)


def _fail(i=0, code=1):
    return WorkerResult(index=i, ok=False, error=f"exit code {code}",
                        exit_code=code)


def _preempted(i=0):
    return WorkerResult(index=i, ok=False,
                        error=f"exit code {PREEMPTED_EXIT_CODE}",
                        exit_code=PREEMPTED_EXIT_CODE)


class FakeLauncher:
    """Scripted launcher: each entry is a result list or 'raise'."""

    def __init__(self, script):
        self.script = list(script)
        self.env_extra = {}
        self.seen_env = []

    def run(self, argv, num_workers, **kw):
        self.seen_env.append(dict(self.env_extra))
        out = self.script.pop(0)
        if out == "raise":
            raise RuntimeError("preflight failed for relaunch")
        return out


class TestSupervisorUnit:
    def test_restart_until_success_with_backoff(self, tmp_path):
        sleeps = []
        launcher = FakeLauncher([[_fail()], [_fail()], [_ok()]])
        sup = Supervisor(
            ["prog"], 1, launcher=launcher,
            policy=RestartPolicy(max_restarts=3, backoff=0.5,
                                 backoff_factor=2.0, backoff_max=10.0),
            event_log=EventLog(tmp_path / "ev.jsonl"),
            sleep=sleeps.append,
        )
        out = sup.run(timeout=5)
        assert out.ok and out.attempts == 3 and out.restarts_used == 2
        assert sleeps == [0.5, 1.0]  # exponential between relaunches
        kinds = [e["event"] for e in read_events(tmp_path / "ev.jsonl")]
        assert kinds.count("attempt_start") == 3
        assert kinds.count("restart") == 2
        assert kinds[-1] == "run_complete"
        # Per-attempt env: the attempt counter and event-log path reach
        # workers through the launcher's env injection.
        assert [e["DTPU_ATTEMPT"] for e in launcher.seen_env] == ["1", "2", "3"]
        assert all(e["DTPU_EVENT_LOG"] == str(tmp_path / "ev.jsonl")
                   for e in launcher.seen_env)

    def test_budget_exhaustion(self, tmp_path):
        launcher = FakeLauncher([[_fail()]] * 3)
        sup = Supervisor(["prog"], 1, launcher=launcher,
                         policy=RestartPolicy(max_restarts=1, backoff=0.0),
                         event_log=EventLog(tmp_path / "ev.jsonl"),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert not out.ok and out.attempts == 2 and out.restarts_used == 1
        kinds = [e["event"] for e in read_events(tmp_path / "ev.jsonl")]
        assert "budget_exhausted" in kinds

    def test_preemption_does_not_consume_budget(self):
        launcher = FakeLauncher([[_preempted()], [_preempted()], [_ok()]])
        sup = Supervisor(["prog"], 1, launcher=launcher,
                         policy=RestartPolicy(max_restarts=0),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.preemptions == 2 and out.restarts_used == 0

    def test_preemption_with_gang_killed_peers_counts_as_preemption(self):
        rows = [
            _preempted(0),
            WorkerResult(index=1, ok=False,
                         error="killed after peer failure (gang semantics)"),
        ]
        launcher = FakeLauncher([rows, [_ok(0), _ok(1)]])
        sup = Supervisor(["prog"], 2, launcher=launcher,
                         policy=RestartPolicy(max_restarts=0),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.preemptions == 1 and out.restarts_used == 0

    def test_preemption_with_error_none_peers_counts_as_preemption(self):
        """REGRESSION (ISSUE 7 satellite): gang-killed peer rows can
        surface with error=None (a launcher that reports disposition
        structurally, or an exit-code-only integration); the old
        '"peer failure" in error' string match classified the clean
        preemption as a budget-burning failure."""
        rows = [
            _preempted(0),
            WorkerResult(index=1, ok=False, error=None, exit_code=None),
        ]
        launcher = FakeLauncher([rows, [_ok(0), _ok(1)]])
        sup = Supervisor(["prog"], 2, launcher=launcher,
                         policy=RestartPolicy(max_restarts=0),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.preemptions == 1 and out.restarts_used == 0

    def test_independent_fault_next_to_preemption_still_burns_budget(self):
        """The flip side of the disposition fix: a peer that EXITED on its
        own (it has an exit code) during a preemption is an independent
        fault — the attempt must NOT classify as preemption."""
        rows = [
            _preempted(0),
            WorkerResult(index=1, ok=False, error="exit code 17",
                         exit_code=17, disposition="exited"),
        ]
        launcher = FakeLauncher([rows, [_ok(0), _ok(1)]])
        sup = Supervisor(["prog"], 2, launcher=launcher,
                         policy=RestartPolicy(max_restarts=1, backoff=0.0),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.preemptions == 0 and out.restarts_used == 1

    def test_events_carry_world_size_and_result_carries_resizes(
            self, tmp_path):
        """ISSUE 7 satellite: attempt_start/restart events name the
        attempt's world size and SupervisedResult surfaces resize
        accounting, so the JSONL log can attribute restarts to resizes."""
        launcher = FakeLauncher([[_fail()], [_ok()]])
        log = EventLog(tmp_path / "ev.jsonl")
        sup = Supervisor(["prog"], 1, launcher=launcher,
                         policy=RestartPolicy(max_restarts=1, backoff=0.0),
                         event_log=log, sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.resizes == 0 and out.world_size == 1
        events = log.read()
        assert all(e["world_size"] == 1 for e in events
                   if e["event"] in ("attempt_start", "attempt_end",
                                     "restart", "run_complete"))
        restart = next(e for e in events if e["event"] == "restart")
        assert restart["resizes"] == 0

    def test_preemption_cap_bounds_the_loop(self):
        launcher = FakeLauncher([[_preempted()]] * 3)
        sup = Supervisor(["prog"], 1, launcher=launcher,
                         policy=RestartPolicy(max_restarts=0,
                                              max_preemptions=2),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert not out.ok and out.preemptions == 2 and out.attempts == 3

    def test_launcher_exception_becomes_failed_rows(self):
        launcher = FakeLauncher(["raise", [_ok()]])
        sup = Supervisor(["prog"], 1, launcher=launcher,
                         policy=RestartPolicy(max_restarts=1, backoff=0.0),
                         sleep=lambda s: None)
        out = sup.run(timeout=5)
        assert out.ok and out.restarts_used == 1


# ----------------------------------------------------- graceful mid-epoch ---
class TestGracefulStop:
    def test_stop_training_breaks_mid_epoch(self):
        model = _small_model()
        x, y = _data()
        stop = LambdaCallback(
            on_batch_end=lambda m, s, logs: (
                setattr(m, "stop_training", True) if s == 2 else None
            )
        )
        hist = model.fit(x, y, batch_size=32, epochs=3, steps_per_epoch=4,
                         verbose=0, callbacks=[stop])
        assert model.step == 2  # stopped at the batch boundary, not epoch
        assert len(hist.history["loss"]) == 1
        assert np.isfinite(hist.history["loss"][0])  # mean over 2 real steps


# ------------------------------------------------------------- preemption ---
class TestPreemptionHandler:
    def test_sigterm_checkpoints_and_stops_in_process(self, tmp_path):
        x, y = _data()
        kw = dict(batch_size=32, epochs=2, steps_per_epoch=4, verbose=0,
                  seed=7)

        preempt_at = 5
        send = LambdaCallback(
            on_batch_end=lambda m, s, logs: (
                os.kill(os.getpid(), signal.SIGTERM) if s == preempt_at
                else None
            )
        )
        handler = PreemptionHandler(tmp_path, exit_code=None)
        m2 = _small_model()
        m2.fit(x, y, **kw, callbacks=[send, handler])
        assert handler.triggered
        assert m2.step == preempt_at  # stopped right at the boundary
        assert dtpu.Checkpointer(tmp_path).latest_step() == preempt_at
        marker = read_resume_marker(tmp_path)
        assert marker and marker["step"] == preempt_at
        # Handler restored the previous SIGTERM disposition on train end.
        assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL

        # Relaunch of the identical command resumes and matches an
        # uninterrupted run exactly (the resume contract).
        m1 = _small_model()
        m1.fit(x, y, **kw)
        m3 = _small_model()
        m3.fit(x, y, **kw,
               callbacks=[ModelCheckpoint(tmp_path, save_freq=100,
                                          restore=True)])
        assert m3.step == m1.step
        import jax

        for a, b in zip(jax.tree_util.tree_leaves(m1.params),
                        jax.tree_util.tree_leaves(m3.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------- callbacks satellites --
class TestCallbackSatellites:
    def test_csvlogger_rows_durable_before_close(self, tmp_path):
        from distributed_tpu.training.callbacks import CSVLogger

        path = tmp_path / "log.csv"
        cb = CSVLogger(path)
        stub = types.SimpleNamespace()
        cb.on_epoch_end(stub, 0, {"loss": 1.5, "accuracy": 0.5})
        # Crash-visible: the row is on disk NOW, no close/flush needed.
        assert path.read_text() == "epoch,accuracy,loss\n0,0.5,1.5\n"
        cb.on_epoch_end(stub, 1, {"loss": 1.0, "accuracy": 0.75})
        assert path.read_text().splitlines()[-1] == "1,0.75,1.0"

    def test_sync_check_emits_event_and_raises(self, monkeypatch, tmp_path):
        from distributed_tpu.training.callbacks import SyncCheck
        from distributed_tpu.utils import events as ev
        from distributed_tpu.utils import sync_check as sc

        monkeypatch.setenv(ev.ENV_VAR, str(tmp_path / "ev.jsonl"))

        def boom(tree, what="params", cross_host=True):
            raise AssertionError(f"Replica divergence in {what} at fake")

        monkeypatch.setattr(sc, "assert_replicas_identical", boom)
        model = types.SimpleNamespace(params={}, state={}, opt_state={},
                                      step=12)
        with pytest.raises(AssertionError, match="divergence"):
            SyncCheck(every=1).on_epoch_end(model, 0, {})
        events = read_events(tmp_path / "ev.jsonl")
        assert events and events[0]["event"] == "sync_check_failed"
        assert events[0]["step"] == 12


# ----------------------------------------------------------- end to end -----
WORKER_BODY = """
    import os, sys, signal
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import distributed_tpu as dtpu
    from distributed_tpu.launch import report_result
    from distributed_tpu.resilience import FaultInjector, PreemptionHandler
    from distributed_tpu.training.callbacks import (
        LambdaCallback, ModelCheckpoint)

    CKPT = os.environ["TEST_CKPT_DIR"]
    x, y = dtpu.data.synthetic_images(256, (28, 28), 10, 0)
    x = x[..., None].astype(np.float32) / 255.0
    m = dtpu.Model(dtpu.models.mnist_cnn())
    m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
    cbs = [ModelCheckpoint(CKPT, save_freq=3, restore=True)]

    pre_step = int(os.environ.get("TEST_PREEMPT_STEP", "0"))
    pre_marker = os.environ.get("TEST_PREEMPT_MARKER", "")
    if pre_step:
        def send_sigterm(model, step, logs):
            if step == pre_step and not os.path.exists(pre_marker):
                open(pre_marker, "w").close()
                os.kill(os.getpid(), signal.SIGTERM)
        cbs.append(LambdaCallback(on_batch_end=send_sigterm))
        cbs.append(PreemptionHandler(CKPT))

    fault = FaultInjector.from_env()
    if fault is not None:
        cbs.append(fault)

    hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=2,
                 steps_per_epoch=4, verbose=0, seed=0, callbacks=cbs)
    leaf = np.asarray(jax.tree_util.tree_leaves(m.params)[0]).ravel()[:4]
    report_result({{"loss": hist.metrics["loss"][-1],
                   "acc": hist.metrics["accuracy"][-1],
                   "leaf": [float(v) for v in leaf]}})
    """


@pytest.fixture(scope="module")
def worker_script(tmp_path_factory):
    path = tmp_path_factory.mktemp("resil") / "worker.py"
    path.write_text(textwrap.dedent(WORKER_BODY.format(repo=REPO)))
    return str(path)


@pytest.fixture(scope="module")
def reference_value(worker_script, tmp_path_factory):
    """The uninterrupted run's final loss/params-leaf — computed once and
    shared by every parity assertion in this module."""
    ckpt = tmp_path_factory.mktemp("ckpt_ref")
    results = LocalLauncher(
        env_extra={"TEST_CKPT_DIR": str(ckpt)}
    ).run([sys.executable, worker_script], 1, timeout=300)
    assert results[0].ok, (results[0].error, results[0].log_tail[-600:])
    return results[0].value


def _assert_parity(value, reference):
    assert value["loss"] == pytest.approx(reference["loss"], rel=1e-6)
    np.testing.assert_allclose(value["leaf"], reference["leaf"], rtol=1e-6)


# @slow (tier-1 budget, PR 17): ~8s real-process kill/restart; the
# TestSupervisorUnit restart-policy tests stay in-tier, and the
# serve_service kill test drives a real-process kill with token-exact
# recovery every run.
@pytest.mark.slow
def test_supervisor_kill_restart_resume_parity(worker_script, reference_value,
                                               tmp_path):
    """ACCEPTANCE: fault-injected worker kill mid-epoch -> automatic
    supervisor restart -> checkpoint resume -> final params match an
    uninterrupted run (fp32 tolerance)."""
    log = EventLog(tmp_path / "events.jsonl")
    sup = Supervisor(
        [sys.executable, worker_script], 1,
        policy=RestartPolicy(max_restarts=2, backoff=0.05, backoff_max=0.1),
        checkpoint_dir=tmp_path / "ckpt",
        event_log=log,
        env_extra={
            "TEST_CKPT_DIR": str(tmp_path / "ckpt"),
            "DTPU_FAULT": "kill:at_step=5",  # mid-epoch-2 (4 steps/epoch)
            "DTPU_FAULT_MARKER": str(tmp_path / "fault_once"),
        },
    )
    out = sup.run(timeout=300, grace=5)
    assert out.ok, [(r.index, r.error, r.log_tail[-600:]) for r in out.results]
    assert out.attempts == 2 and out.restarts_used == 1
    _assert_parity(out.results[0].value, reference_value)

    kinds = [e["event"] for e in log.read()]
    assert "fault_injected" in kinds  # worker-side event, shared log
    assert "restart" in kinds and kinds[-1] == "run_complete"
    restart = next(e for e in log.read() if e["event"] == "restart")
    assert restart["reason"] == "failure"
    assert restart["resume_step"] == 3  # latest complete ckpt before step 5


@pytest.mark.slow
def test_supervisor_preemption_restart_is_budget_free(worker_script,
                                                      reference_value,
                                                      tmp_path):
    """SIGTERM mid-epoch -> PreemptionHandler checkpoints step 5 + exits 75
    -> supervisor restarts WITHOUT spending the failure budget -> resumed
    run matches the uninterrupted one."""
    log = EventLog(tmp_path / "events.jsonl")
    sup = Supervisor(
        [sys.executable, worker_script], 1,
        policy=RestartPolicy(max_restarts=0, backoff=0.05),  # zero budget!
        checkpoint_dir=tmp_path / "ckpt",
        event_log=log,
        env_extra={
            "TEST_CKPT_DIR": str(tmp_path / "ckpt"),
            "TEST_PREEMPT_STEP": "5",
            "TEST_PREEMPT_MARKER": str(tmp_path / "preempted_once"),
        },
    )
    out = sup.run(timeout=300, grace=5)
    assert out.ok, [(r.index, r.error, r.log_tail[-600:]) for r in out.results]
    assert out.preemptions == 1 and out.restarts_used == 0
    # Params match the uninterrupted run exactly; the final-epoch LOSS
    # legitimately differs — the preemption checkpointed mid-epoch (step 5),
    # so the resumed final epoch averages its metrics over the 3 replayed
    # steps, not 4 (the "modulo the replayed partial epoch" caveat).
    np.testing.assert_allclose(out.results[0].value["leaf"],
                               reference_value["leaf"], rtol=1e-6)
    kinds = [e["event"] for e in log.read()]
    assert "preempted" in kinds  # worker-side PreemptionHandler event
    restart = next(e for e in log.read() if e["event"] == "restart")
    assert restart["reason"] == "preempted"
    assert restart["marker_step"] == 5  # resume marker from the handler
    # Run completed: the supervisor cleared the resume marker.
    assert read_resume_marker(tmp_path / "ckpt") is None


@pytest.mark.slow
@pytest.mark.parametrize("mode,fault,needs_liveness", [
    ("hang", "hang:at_step=5", True),
    ("slow_heartbeat", "slow_heartbeat:at_step=5,hang_seconds=10000", True),
    ("corrupt", "corrupt_checkpoint:at_step=6,directory={ckpt}", False),
])
def test_fault_matrix_restart_resume_parity(worker_script, reference_value,
                                            tmp_path, mode, fault,
                                            needs_liveness):
    """The rest of the fault matrix: hang (SIGSTOP — only the heartbeat
    probe can see it), slow-heartbeat (alive but stalled in Python), and
    corrupt-checkpoint (newest file clobbered after the step-6 save; the
    relaunch must fall back to step 3 and still reach parity)."""
    ckpt = tmp_path / "ckpt"
    log = EventLog(tmp_path / "events.jsonl")
    sup = Supervisor(
        [sys.executable, worker_script], 1,
        policy=RestartPolicy(max_restarts=2, backoff=0.05, backoff_max=0.1),
        checkpoint_dir=ckpt,
        event_log=log,
        liveness_timeout=3.0 if needs_liveness else None,
        env_extra={
            "TEST_CKPT_DIR": str(ckpt),
            "DTPU_FAULT": fault.format(ckpt=ckpt),
            "DTPU_FAULT_MARKER": str(tmp_path / "fault_once"),
        },
    )
    out = sup.run(timeout=300, grace=5)
    assert out.ok, [(r.index, r.error, r.log_tail[-600:]) for r in out.results]
    assert out.restarts_used == 1
    _assert_parity(out.results[0].value, reference_value)
    events = log.read()
    kinds = [e["event"] for e in events]
    if needs_liveness:
        # The first attempt must have died by liveness, not run timeout.
        end = next(e for e in events if e["event"] == "attempt_end")
        assert end["duration"] < 120
    else:
        assert "corrupt_checkpoint_skipped" in kinds
        restart = next(e for e in events if e["event"] == "restart")
        assert restart["resume_step"] == 3  # step-6 file is corrupt


def test_cli_supervise_end_to_end(tmp_path):
    """dtpu-launch --supervise: fail-once worker is restarted by the
    Supervisor and the run completes with rc 0 + event log."""
    import subprocess

    marker = tmp_path / "failed_once"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        f"""
        import json, os, sys
        marker = {str(marker)!r}
        if not os.path.exists(marker):
            open(marker, "w").close()
            sys.exit(3)
        # report through the launcher's result-file protocol directly
        # (no framework import: keeps the CLI smoke fast)
        with open(os.environ["DTPU_RESULT_FILE"], "w") as f:
            json.dump({{"value": {{"attempt": os.environ["DTPU_ATTEMPT"]}}}}, f)
        """
    ))
    out_json = tmp_path / "rows.json"
    ev = tmp_path / "events.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.launch", "--supervise",
         "--num-workers", "1", "--max-restarts", "2",
         "--event-log", str(ev), "--results-json", str(out_json),
         str(script)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert marker.exists()
    rows = json.loads(out_json.read_text())
    assert rows[0]["ok"] and rows[0]["value"] == {"attempt": "2"}
    kinds = [e["event"] for e in read_events(ev)]
    assert "restart" in kinds and kinds[-1] == "run_complete"
    assert "supervisor: attempts=2 restarts=1" in proc.stdout
