"""Unified observability runtime (distributed_tpu/obs, docs/OBSERVABILITY.md).

Covers the four tentpole pieces in-process (registry, spans, flight
recorder, cross-rank aggregation), the exporters and the dtpu-events CLI,
the derived-view parity contract (``last_fit_telemetry`` /
``last_run_telemetry`` == the registry's stored reports, key-for-key with
the PR 13 key sets), and the PR's satellites: the event log's cached
append fd (rotation reopen + concurrent-writer whole-line interleaving),
``StepTimer.stall_report``'s unattributed remainder + per-category
fractions, and rank-stamped structured logging. The aggregation math is
pinned on synthetic event streams, the supervisor's straggler emission
on a scripted launcher, and the kill -> dump -> recovery-row path on a
real gang under ``-m slow``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import distributed_tpu as dtpu  # noqa: E402
from distributed_tpu import obs  # noqa: E402
from distributed_tpu.obs import aggregate, cli, export  # noqa: E402
from distributed_tpu.obs.flight import FlightRecorder  # noqa: E402
from distributed_tpu.obs.registry import MetricsRegistry  # noqa: E402
from distributed_tpu.resilience import FaultInjector  # noqa: E402
from distributed_tpu.resilience.supervisor import (  # noqa: E402
    Supervisor,
    recovery_rows,
)
from distributed_tpu.launch.core import WorkerResult  # noqa: E402
from distributed_tpu.utils.events import EventLog, read_events  # noqa: E402
from distributed_tpu.utils.logging import rank_world  # noqa: E402
from distributed_tpu.utils.profiler import StepTimer  # noqa: E402


def small_model(width=16):
    m = dtpu.Model(dtpu.nn.Sequential([
        dtpu.nn.Flatten(),
        dtpu.nn.Dense(width, activation="relu"),
        dtpu.nn.Dense(10),
    ]))
    m.compile(optimizer=dtpu.optim.SGD(0.05),
              loss="sparse_categorical_crossentropy")
    return m


# ------------------------------------------------------------- registry ----
class TestRegistry:
    def test_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.counter("a", 2)
        reg.counter("a", 3)
        reg.gauge("g", 1.5)
        reg.gauge("g", 2.5)  # last-value-wins
        reg.observe("h", 0.003)
        reg.observe("h", 999.0)  # overflow bucket
        snap = reg.snapshot()
        assert snap["counters"]["a"] == 5.0
        assert snap["gauges"]["g"] == 2.5
        h = snap["histograms"]["h"]
        assert h["count"] == 2 and h["overflow"] == 1
        assert sum(h["counts"]) == 1
        assert h["sum"] == pytest.approx(999.003)

    def test_ring_is_bounded(self):
        reg = MetricsRegistry(ring_size=8)
        for i in range(50):
            reg.ring_append("r", {"i": i})
        ring = reg.ring("r")
        assert len(ring) == 8  # never grows past N
        assert [r["i"] for r in ring] == list(range(42, 50))  # newest kept

    def test_snapshot_deterministic(self):
        """Same operations -> same key sequence AND same JSON (modulo the
        timestamp): the determinism exporters and tests rely on."""
        def build():
            reg = MetricsRegistry()
            for name in ("z", "a", "m"):
                reg.counter(name)
                reg.gauge("g/" + name, 1)
                reg.observe("h/" + name, 0.01)
                reg.ring_append("r/" + name, {"v": 1})
            return reg.snapshot()

        s1, s2 = build(), build()
        s1.pop("ts"), s2.pop("ts")
        assert json.dumps(s1) == json.dumps(s2)
        assert list(s1["counters"]) == ["a", "m", "z"]  # sorted

    def test_disabled_registry_noops(self):
        reg = MetricsRegistry()
        prev = obs.set_enabled(False)
        try:
            reg.counter("c")
            reg.gauge("g", 1)
            reg.observe("h", 1.0)
            reg.ring_append("r", {"x": 1})
            snap = reg.snapshot()
            assert not snap["counters"] and not snap["gauges"]
            assert not snap["histograms"] and not snap["rings"]
            # Reports STILL store: legacy telemetry must survive obs-off.
            rep = reg.set_report("x", {"k": 1})
            assert reg.get_report("x") is rep
        finally:
            obs.set_enabled(prev)

    def test_set_report_returns_stored_object(self):
        reg = MetricsRegistry()
        d = {"a": 1}
        assert reg.set_report("view", d) is d
        assert reg.get_report("view") is d


# ---------------------------------------------------------------- spans ----
class TestSpans:
    def test_span_records_and_nests(self):
        reg = MetricsRegistry()
        with obs.span("outer", registry=reg):
            assert obs.current_span() == "outer"
            with obs.span("inner", registry=reg):
                assert obs.current_span() == "outer/inner"
                time.sleep(0.01)
        assert obs.current_span() is None
        snap = reg.snapshot()
        assert snap["counters"]["span_calls/outer"] == 1
        assert snap["counters"]["span_calls/outer/inner"] == 1
        assert snap["histograms"]["span_seconds/outer/inner"]["sum"] >= 0.01

    def test_span_attributes_into_timer(self):
        t = StepTimer(warmup=0)
        with obs.span("input_wait", timer=t):
            time.sleep(0.005)
        assert t.stalls["input_wait"] >= 0.005

    def test_span_handle_exposes_seconds(self):
        with obs.span("x") as sp:
            time.sleep(0.002)
        assert sp.seconds >= 0.002

    def test_disabled_span_still_times_for_timer(self):
        """obs-off: the legacy stall buckets must be unchanged (a bare
        loop still reports input_stall_fraction etc.)."""
        reg = MetricsRegistry()
        t = StepTimer(warmup=0)
        prev = obs.set_enabled(False)
        try:
            with obs.span("dispatch", timer=t, registry=reg):
                time.sleep(0.002)
        finally:
            obs.set_enabled(prev)
        assert t.stalls["dispatch"] >= 0.002
        assert not reg.snapshot()["histograms"]

    def test_stall_attribute_forwards_to_registry(self):
        reg = obs.default_registry()
        before = reg.counter_value("stall_seconds/custom_cat")
        t = StepTimer(warmup=0)
        t.attribute("custom_cat", 0.5)
        assert reg.counter_value("stall_seconds/custom_cat") == \
            pytest.approx(before + 0.5)


# ------------------------------------------------------------ stall report --
class TestStallReport:
    def test_unattributed_and_fractions(self):
        t = StepTimer(warmup=0)
        t.attribute("input_wait", 0.01)
        t.attribute("dispatch", 0.02)
        time.sleep(0.03)
        rep = t.stall_report()
        # Legacy keys intact:
        assert {"input_wait", "dispatch", "checkpoint_wait",
                "total_seconds", "input_stall_fraction"} <= set(rep)
        # New: the honest remainder + per-category fractions.
        assert rep["unattributed"] >= 0.0
        assert rep["unattributed"] == pytest.approx(
            rep["total_seconds"] - rep["input_wait"] - rep["dispatch"]
            - rep["checkpoint_wait"], abs=1e-4)
        for cat in ("input_wait", "dispatch", "checkpoint_wait",
                    "unattributed"):
            frac = rep[f"{cat}_fraction"]
            assert 0.0 <= frac <= 1.0
        assert rep["input_stall_fraction"] == rep["input_wait_fraction"]

    def test_custom_category_gets_fraction(self):
        t = StepTimer(warmup=0)
        t.attribute("prefill", 0.004)
        rep = t.stall_report()
        assert rep["prefill"] >= 0.004
        assert "prefill_fraction" in rep


# ------------------------------------------------------- flight recorder ----
class TestFlightRecorder:
    def test_ring_never_grows_past_capacity(self):
        rec = FlightRecorder(capacity=16)
        for i in range(100):
            rec.record("step", step=i)
        assert len(rec) == 16
        steps = [r["step"] for r in rec.snapshot()]
        assert steps == list(range(84, 100))

    def test_dump_writes_header_and_records(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DTPU_EVENT_LOG", str(tmp_path / "ev.jsonl"))
        rec = FlightRecorder(capacity=8)
        for i in range(5):
            rec.record("step", step=i)
        path = rec.dump(tmp_path / "dump.jsonl", reason="test")
        records = obs.flight.read_dump(path)
        header = records[0]
        assert header["kind"] == "flight_header"
        assert header["reason"] == "test" and header["records"] == 5
        assert [r["step"] for r in records[1:]] == list(range(5))
        # The dump emitted a flight_dump event into the ambient log.
        events = read_events(tmp_path / "ev.jsonl")
        fd = [e for e in events if e["event"] == "flight_dump"]
        assert len(fd) == 1 and fd[0]["path"] == str(path)
        assert fd[0]["records"] == 5

    def test_dump_torn_final_line_recovers(self, tmp_path):
        rec = FlightRecorder()
        for i in range(3):
            rec.record("step", step=i)
        path = rec.dump(tmp_path / "dump.jsonl", reason="torn")
        with open(path, "a") as f:
            f.write('{"kind": "step", "step": 99')  # writer died mid-append
        records = obs.flight.read_dump(path)
        assert [r.get("step") for r in records[1:]] == [0, 1, 2]

    def test_dump_without_location_is_noop(self, monkeypatch):
        monkeypatch.delenv("DTPU_FLIGHT_DIR", raising=False)
        monkeypatch.delenv("DTPU_EVENT_LOG", raising=False)
        assert FlightRecorder().dump(reason="nowhere") is None

    def test_record_noop_when_disabled(self):
        rec = FlightRecorder()
        prev = obs.set_enabled(False)
        try:
            rec.record("step", step=1)
        finally:
            obs.set_enabled(prev)
        assert len(rec) == 0

    def test_fit_records_steps_and_exception_dumps(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("DTPU_FLIGHT_DIR", str(tmp_path))
        x, y = dtpu.data.synthetic_images(64, (8, 8), 10, 0)
        m = small_model()
        before = len(obs.default_recorder())
        m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=3, verbose=0)
        assert len(obs.default_recorder()) >= min(
            before + 3, obs.default_recorder().capacity
        )
        recs = obs.default_recorder().snapshot()
        step_recs = [r for r in recs if r["kind"] == "step"]
        assert {"step", "seconds", "input_wait_s", "dispatch_s",
                "self_s"} <= set(step_recs[-1])

        class Boom(Exception):
            pass

        class Bomb(dtpu.callbacks.Callback):
            def on_batch_end(self, model, step, logs):
                raise Boom("kaboom")

        with pytest.raises(Boom):
            m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=3,
                  verbose=0, callbacks=[Bomb()])
        dumps = list(tmp_path.glob("flight-rank*.jsonl"))
        assert dumps, "unhandled fit exception must leave a flight dump"
        header = obs.flight.read_dump(dumps[0])[0]
        assert header["reason"] == "exception:Boom"


# -------------------------------------------------------------- event log ---
class TestEventLogFd:
    def test_cached_fd_appends_whole_records(self, tmp_path):
        log = EventLog(tmp_path / "ev.jsonl")
        for i in range(5):
            log.emit("tick", i=i)
        assert log._f is not None  # handle cached, not reopened per emit
        assert [e["i"] for e in log.read()] == list(range(5))
        log.close()

    def test_reopen_after_rotation(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        log = EventLog(path)
        log.emit("a")
        os.rename(path, tmp_path / "ev.jsonl.1")
        log.emit("b")  # ENOENT at the configured path -> reopen
        assert [e["event"] for e in read_events(path)] == ["b"]
        assert [e["event"] for e in read_events(tmp_path / "ev.jsonl.1")] \
            == ["a"]
        log.close()

    def test_reopen_after_unlink(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        log = EventLog(path)
        log.emit("a")
        os.unlink(path)
        log.emit("b")
        assert [e["event"] for e in read_events(path)] == ["b"]
        log.close()

    def test_concurrent_writers_interleave_whole_lines(self, tmp_path):
        """Two PROCESSES appending concurrently produce only whole,
        parseable lines (O_APPEND + one write per record)."""
        path = tmp_path / "ev.jsonl"
        script = (
            "import sys\n"
            "sys.path.insert(0, sys.argv[3])\n"
            "from distributed_tpu.utils.events import EventLog\n"
            "log = EventLog(sys.argv[1])\n"
            "w = sys.argv[2]\n"
            "for i in range(120):\n"
            "    log.emit('rec', writer=w, i=i, pad='x' * 200)\n"
        )
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        procs = [
            subprocess.Popen([sys.executable, "-c", script, str(path),
                              name, root])
            for name in ("a", "b")
        ]
        for p in procs:
            assert p.wait(timeout=120) == 0
        raw = path.read_text().splitlines()
        assert len(raw) == 240
        recs = [json.loads(line) for line in raw]  # every line parses whole
        by_writer = {}
        for r in recs:
            by_writer.setdefault(r["writer"], []).append(r["i"])
        # Each writer's records arrive intact and in its own order.
        assert by_writer["a"] == list(range(120))
        assert by_writer["b"] == list(range(120))


# ------------------------------------------------------------- exporters ----
class TestExporters:
    def test_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("fit/steps", 7)
        reg.gauge("engine/queue_depth", 3)
        reg.observe("span_seconds/decode", 0.002)
        text = export.prometheus_text(registry=reg)
        assert "# TYPE dtpu_fit_steps counter" in text
        assert "dtpu_fit_steps 7.0" in text
        assert "# TYPE dtpu_engine_queue_depth gauge" in text
        assert "# TYPE dtpu_span_seconds_decode histogram" in text
        assert 'dtpu_span_seconds_decode_bucket{le="+Inf"} 1' in text
        assert "dtpu_span_seconds_decode_count 1" in text

    def test_prometheus_histogram_cumulative(self):
        reg = MetricsRegistry()
        for v in (0.0005, 0.003, 0.2, 100.0):
            reg.observe("h", v)
        text = export.prometheus_text(registry=reg)
        # cumulative counts are nondecreasing and end at the total
        counts = [int(line.rsplit(" ", 1)[1])
                  for line in text.splitlines() if "_bucket{" in line]
        assert counts == sorted(counts) and counts[-1] == 4

    def test_write_prometheus_and_jsonl_snapshot(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("c", 1)
        p = export.write_prometheus(tmp_path / "metrics.prom", registry=reg)
        assert "dtpu_c 1.0" in p.read_text()
        j = export.append_snapshot(tmp_path / "snaps.jsonl", registry=reg,
                                   step=5)
        export.append_snapshot(j, registry=reg, step=6)
        recs = read_events(j)
        assert len(recs) == 2
        assert recs[0]["counters"]["c"] == 1.0 and recs[1]["step"] == 6


# ------------------------------------------------------------- aggregation --
def _snap(rank, seconds, world=2, step=5):
    return {"event": "metrics_snapshot", "ts": 0.0, "rank": rank,
            "world": world, "step": step, "self_seconds": list(seconds)}


class TestAggregate:
    def test_skew_report_and_straggler(self):
        events = [
            _snap(0, [0.01, 0.011, 0.009]),
            _snap(1, [0.05, 0.055, 0.06]),
            _snap(0, [0.01, 0.012]),
        ]
        rep = aggregate.skew_report(events)
        assert rep["world"] == 2 and rep["slowest_rank"] == 1
        assert rep["max_skew"] > 1.5
        row = aggregate.straggler(events, threshold=1.5)
        assert row["rank"] == 1 and row["skew"] == rep["max_skew"]

    def test_no_straggler_below_threshold(self):
        events = [_snap(0, [0.01] * 4), _snap(1, [0.011] * 4)]
        assert aggregate.straggler(events, threshold=1.5) is None
        assert aggregate.skew_report(events)["max_skew"] < 1.2

    def test_single_rank_never_straggles(self):
        events = [_snap(0, [0.01] * 4, world=1)]
        assert aggregate.straggler(events) is None

    def test_empty_stream(self):
        assert aggregate.skew_report([{"event": "attempt_start"}]) is None

    def test_falls_back_to_step_seconds(self):
        events = [
            {"event": "metrics_snapshot", "rank": 0,
             "step_seconds": [0.01]},
            {"event": "metrics_snapshot", "rank": 1,
             "step_seconds": [0.09]},
        ]
        assert aggregate.straggler(events, threshold=1.5)["rank"] == 1

    def test_supervisor_emits_straggler_event(self, tmp_path):
        """A scripted (no-subprocess) supervised run whose event log holds
        worker snapshot flushes: the terminal boundary must emit rank_skew
        + straggler events naming the slow rank."""
        log = EventLog(tmp_path / "ev.jsonl")
        for snap in (_snap(0, [0.01] * 5), _snap(1, [0.08] * 5)):
            log.emit(snap.pop("event"), **{k: v for k, v in snap.items()
                                           if k != "ts"})

        class OkLauncher:
            env_extra = {}

            def run(self, argv, num_workers, **kw):
                return [WorkerResult(index=i, ok=True)
                        for i in range(num_workers)]

        sup = Supervisor(["cmd"], 2, launcher=OkLauncher(), event_log=log,
                         straggler_threshold=1.5)
        result = sup.run(timeout=5.0)
        assert result.ok
        events = log.read()
        skews = [e for e in events if e["event"] == "rank_skew"]
        strag = [e for e in events if e["event"] == "straggler"]
        assert len(skews) == 1
        assert len(strag) == 1 and strag[0]["rank"] == 1

    def test_recovery_rows_reference_flight_dumps(self):
        events = [
            {"event": "attempt_start", "attempt": 1, "ts": 0.0},
            {"event": "fault_injected", "mode": "kill", "ts": 1.0},
            {"event": "flight_dump", "path": "/shm/flight-rank1.jsonl",
             "attempt": 1, "ts": 1.0},
            {"event": "attempt_end", "attempt": 1, "ok": False, "ts": 2.0},
            {"event": "attempt_start", "attempt": 2, "ts": 3.0},
            {"event": "attempt_end", "attempt": 2, "ok": True, "ts": 9.0},
        ]
        (row,) = recovery_rows(events)
        assert row["flight_dumps"] == ["/shm/flight-rank1.jsonl"]


# ---------------------------------------------------------------- faults ----
class TestSlowStepsFault:
    def test_slow_steps_persists_and_announces_once(self, monkeypatch):
        sleeps = []
        monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
        inj = FaultInjector("slow_steps", at_step=3, rank=0,
                            slow_seconds=0.2)
        for step in range(1, 7):
            inj.on_batch_end(None, step, {})
        assert sleeps == [0.2] * 4  # every step from at_step on
        assert inj.fired is False  # degradation, not a one-shot death
        assert inj._slow_announced is True

    def test_slow_steps_from_env(self, monkeypatch):
        monkeypatch.setenv(
            "DTPU_FAULT", "slow_steps:at_step=2,rank=1,slow_seconds=0.5"
        )
        inj = FaultInjector.from_env()
        assert inj.mode == "slow_steps" and inj.slow_seconds == 0.5
        assert inj.at_step == 2 and inj.rank == 1

    def test_kill_mode_dumps_flight(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DTPU_FLIGHT_DIR", str(tmp_path))
        exits = []
        monkeypatch.setattr(os, "_exit", lambda code: exits.append(code))
        obs.default_recorder().record("step", step=4)
        inj = FaultInjector("kill", at_step=1, rank=0, exit_code=17)
        inj.on_batch_end(None, 1, {})
        assert exits == [17]
        dumps = list(tmp_path.glob("flight-rank*.jsonl"))
        assert dumps
        header = obs.flight.read_dump(dumps[0])[0]
        assert header["reason"] == "fault:kill"


# --------------------------------------------------- supervised gang e2e ----
_GANG_WORKER = """
import os, sys
sys.path.insert(0, os.environ["T_REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import distributed_tpu as dtpu
from distributed_tpu.data.pipeline import Pipeline
from distributed_tpu.launch import report_result
from distributed_tpu.resilience import FaultInjector

spec = dtpu.cluster.initialize()
world = spec.num_processes
x, y = dtpu.data.synthetic_images(128, (8, 8), 10, 0)
strategy = dtpu.DataParallel() if world > 1 else dtpu.SingleDevice()
with strategy.scope():
    m = dtpu.Model(dtpu.nn.Sequential([
        dtpu.nn.Flatten(), dtpu.nn.Dense(32, activation="relu"),
        dtpu.nn.Dense(10),
    ]))
    m.compile(optimizer=dtpu.optim.SGD(0.05),
              loss="sparse_categorical_crossentropy")
m.build((8, 8))
cbs = list(filter(None, [FaultInjector.from_env()]))
with Pipeline(x, y, 32, seed=0, use_native=False,
              shard=(spec.index, world)) as p:
    m.fit(p, epochs=1, steps_per_epoch=6, verbose=0, callbacks=cbs)
report_result({"world": world, "final_step": int(m.step)})
"""


@pytest.mark.slow
def test_gang_kill_leaves_flight_dump_in_recovery_row(tmp_path):
    """Acceptance e2e: a FaultInjector kill on a REAL supervised 2-worker
    gang yields a readable flight-recorder dump, referenced from the
    recovery postmortem row (and renderable by dtpu-events)."""
    from distributed_tpu.resilience import RestartPolicy

    worker = tmp_path / "worker.py"
    worker.write_text(_GANG_WORKER)
    log = EventLog(tmp_path / "ev.jsonl")
    sup = Supervisor(
        [sys.executable, str(worker)], 2,
        policy=RestartPolicy(max_restarts=2, backoff=0.01, backoff_max=0.01),
        event_log=log,
        env_extra={
            "T_REPO": os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            "DTPU_FAULT": "kill:at_step=3,rank=1",
            "DTPU_FAULT_MARKER": str(tmp_path / "once"),
        },
    )
    result = sup.run(timeout=300.0, grace=5.0)
    assert result.ok
    events = log.read()
    recov = [e for e in events if e["event"] == "recovery"]
    assert recov, "a kill-and-restart run must emit a recovery row"
    dumps = recov[0].get("flight_dumps")
    assert dumps, "the recovery row must reference the death's flight dump"
    records = obs.flight.read_dump(dumps[0])
    assert records and records[0]["kind"] == "flight_header"
    assert records[0]["reason"] == "fault:kill"
    steps = [r for r in records[1:] if r.get("kind") == "step"]
    assert steps, "the dump must hold the steps before death"
    # And the CLI renders it into the postmortem.
    out = cli.render(cli.summarize(events))
    assert "flight dump" in out
    assert "reason='fault:kill'" in out


# ------------------------------------------------------------ parity views --
# The PR 13 key sets (byte-compatible contract): these exact keys must
# still be present, and the legacy attributes must BE the registry's
# stored reports.
FIT_TELEMETRY_PR13_KEYS = {
    "input_wait", "dispatch", "checkpoint_wait", "total_seconds",
    "input_stall_fraction", "device_memory",
    "model_state_bytes_per_device", "precision", "comm_bytes_estimate",
}
RUN_TELEMETRY_PR13_KEYS = {
    "queue_wait", "prefill", "decode", "total_seconds",
    "input_stall_fraction", "kv_utilization", "generated_tokens",
    "tokens_per_sec", "time_to_first_token", "requests",
    "weights_version", "weight_swaps", "queue_depth", "free_blocks_min",
    "decode_steps", "prefill_dispatches", "preemptions",
}


class TestDerivedViewParity:
    def test_last_fit_telemetry_is_registry_view(self):
        x, y = dtpu.data.synthetic_images(64, (8, 8), 10, 0)
        m = small_model()
        m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=3, verbose=0)
        t = m.last_fit_telemetry
        assert FIT_TELEMETRY_PR13_KEYS <= set(t)
        assert t is obs.default_registry().get_report("model.fit")
        assert obs.default_registry().counter_value("fit/steps") > 0

    def test_last_run_telemetry_is_registry_view(self):
        m = dtpu.Model(dtpu.models.transformer_lm(
            32, num_layers=1, d_model=16, num_heads=2, max_len=32))
        m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
        m.build((8,))
        eng = dtpu.serving.Engine(m, max_slots=2, block_size=4, max_len=32)
        reqs = [(np.arange(1, 5, dtype=np.int32), 4),
                (np.arange(2, 8, dtype=np.int32), 4)]
        eng.run(reqs)
        t = eng.last_run_telemetry
        assert RUN_TELEMETRY_PR13_KEYS <= set(t)
        assert t is obs.default_registry().get_report("engine.run")
        reg = obs.default_registry()
        assert reg.gauge_value("engine/kv_utilization") is not None
        assert reg.counter_value("engine/requests") >= 2
        # span path: prefill/decode flowed through the tracer
        snap = reg.snapshot()
        assert "span_seconds/decode" in snap["histograms"]
        assert "span_seconds/prefill" in snap["histograms"]

    def test_fit_snapshot_flush_over_event_log(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DTPU_EVENT_LOG", str(tmp_path / "ev.jsonl"))
        monkeypatch.setenv("DTPU_OBS_FLUSH_EVERY", "2")
        x, y = dtpu.data.synthetic_images(64, (8, 8), 10, 0)
        m = small_model()
        m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=4, verbose=0)
        snaps = aggregate.snapshots(read_events(tmp_path / "ev.jsonl"))
        assert snaps, "fit must flush metrics_snapshot over DTPU_EVENT_LOG"
        total = sum(len(s["step_seconds"]) for s in snaps)
        assert total == 4
        assert all(len(s["self_seconds"]) == len(s["step_seconds"])
                   for s in snaps)
        assert snaps[0]["rank"] == 0 and snaps[0]["world"] == 1


# -------------------------------------------------------------- logging ----
class TestLoggingRanks:
    def test_rank_world_defaults(self):
        r, w = rank_world()
        assert r == 0 and w >= 1

    def test_rank_world_from_env_spec(self, monkeypatch):
        """A jax-free controller resolves ranks from the cluster spec env
        (monkeypatching jax out of sys.modules to simulate)."""
        monkeypatch.setitem(sys.modules, "jax", None)
        monkeypatch.setenv("DTPU_CONFIG", json.dumps({
            "cluster": {"worker": ["a:1", "b:2", "c:3"]},
            "task": {"type": "worker", "index": 2},
        }))
        assert rank_world() == (2, 3)

    def test_jsonl_event_carries_rank_fields(self, tmp_path):
        from distributed_tpu.utils import logging as dlog
        dlog.set_jsonl(str(tmp_path / "log.jsonl"))
        try:
            dlog.event("step_rate", steps_per_sec=1.0)
        finally:
            dlog.set_jsonl(None)
        (rec,) = read_events(tmp_path / "log.jsonl")
        assert rec["process_index"] == 0 and rec["world_size"] >= 1

    def test_stderr_record_has_rankstamp(self):
        import logging as pylog
        logger = pylog.getLogger("distributed_tpu")
        record = logger.makeRecord("distributed_tpu", pylog.INFO, "f", 1,
                                   "msg", (), None)
        for f in logger.handlers[0].filters:
            f.filter(record)
        assert hasattr(record, "rankstamp")
        assert record.process_index == 0


# ------------------------------------------------------------------- CLI ----
class TestCli:
    def _write_log(self, tmp_path):
        log = EventLog(tmp_path / "ev.jsonl")
        log.emit("attempt_start", attempt=1, world_size=2)
        log.emit("fault_injected", mode="slow_steps", step=3)
        for snap in (_snap(0, [0.01] * 4), _snap(1, [0.08] * 4)):
            log.emit(snap.pop("event"),
                     **{k: v for k, v in snap.items() if k != "ts"})
        log.emit("attempt_end", attempt=1, ok=True, world_size=2)
        log.emit("run_complete", attempts=1)
        log.close()
        return tmp_path / "ev.jsonl"

    def test_summarize_and_render(self, tmp_path, capsys):
        path = self._write_log(tmp_path)
        rec = FlightRecorder()
        rec.record("step", step=7, seconds=0.01)
        dump = rec.dump(tmp_path / "flight.jsonl", reason="test")
        rc = cli.main([str(path), "--flight", str(dump)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "postmortem" in out
        assert "attempt 1" in out
        assert "fault injected: slow_steps" in out
        assert "rank skew" in out
        assert "STRAGGLER: rank 1" in out
        assert "flight.jsonl" in out and "step=7" in out

    def test_json_mode(self, tmp_path, capsys):
        path = self._write_log(tmp_path)
        assert cli.main([str(path), "--json"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["straggler"]["rank"] == 1
        assert summary["attempts"][0]["attempt"] == 1

    def test_missing_log(self, tmp_path, capsys):
        assert cli.main([str(tmp_path / "nope.jsonl")]) == 2

    def test_follow_tails_appends_and_rotation(self, tmp_path):
        """--follow yields events as they are appended, waits for a log
        that does not exist yet, and survives the writer's rotation
        (new inode) — the EventLog stat/reopen idiom from the reader
        side. Pull-based generator, so no threads needed to test it."""
        path = tmp_path / "ev.jsonl"
        gen = cli.follow(path, poll_s=0.01, stop=lambda: True)
        assert list(gen) == []  # no file yet + stop(): clean exit
        log = EventLog(path)
        log.emit("attempt_start", attempt=1, world_size=2)
        log.emit("stream_open", request_id=0, tenant="a")
        deadline = time.time() + 10  # hang guard, not the exit path
        seen = []
        gen = cli.follow(path, poll_s=0.01,
                         stop=lambda: time.time() > deadline)
        for e in gen:
            seen.append(e)
            if len(seen) == 2:
                break
        assert [e["event"] for e in seen] == ["attempt_start",
                                              "stream_open"]
        # Append while the generator is live: the next pull gets it.
        log.emit("quota_reject", tenant="flood")
        assert next(gen)["event"] == "quota_reject"
        # Rotate: unlink + fresh file. The tail reopens and keeps going.
        log.close()
        path.unlink()
        log2 = EventLog(path)
        log2.emit("run_complete", attempts=1)
        assert next(gen)["event"] == "run_complete"
        log2.close()
        gen.close()

    def test_follow_holds_back_torn_tail_line(self, tmp_path):
        path = tmp_path / "ev.jsonl"
        with open(path, "w") as f:
            f.write('{"event": "restart", "ts": 1.0, "attempt": 1}\n')
            f.write('{"event": "torn')  # no newline: write in progress
        gen = cli.follow(path, poll_s=0.01, stop=lambda: True)
        events = list(gen)
        assert [e["event"] for e in events] == ["restart"]
        # The tail completes -> the event is whole on the next tail.
        with open(path, "a") as f:
            f.write('_no_more", "ts": 2.0}\n')
        events = list(cli.follow(path, poll_s=0.01, stop=lambda: True))
        assert [e["event"] for e in events] == ["restart", "torn_no_more"]

    def test_event_line_rendering(self):
        line = cli.event_line({"ts": 0.0, "event": "replica_spawn",
                               "pid": 1, "replica": "decode-0",
                               "role": "decode"})
        assert line.endswith("replica_spawn replica=decode-0 role=decode")
