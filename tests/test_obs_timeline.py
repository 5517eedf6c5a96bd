"""The span timeline and the compile ledger (distributed_tpu/obs,
docs/OBSERVABILITY.md "Span tracer", "Compile ledger").

What a span's record holds (start, end, thread, parent, on one clock from
the process's start), phases, the 8-dispatch rule and the journals' bound;
what the ledger says of a tiny ``Model``'s set-up (the train step once a
stage, inside the first ``dispatch`` span; an inner ``jit`` nested in the
outer's trace; the persistent cache's miss and hit); ``DTPU_OBS=0``; and
the dump, through ``dtpu-events --timeline``.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
import distributed_tpu as dtpu  # noqa: E402
from distributed_tpu import obs  # noqa: E402
from distributed_tpu.obs import cli, compile_ledger, flight, spans  # noqa: E402
from distributed_tpu.obs.registry import (  # noqa: E402
    JOURNAL_CAPACITY,
    MetricsRegistry,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The package's own import is the process's first record, whatever a
# worker ran before this file (a journal keeps its first records).
IMPORTED = [r for r in obs.default_registry().journal(spans.TIMELINE)
            if r["path"] == "import"]


@pytest.fixture(autouse=True)
def journals_with_room():
    """An xdist worker runs a thousand tests in one process, and a journal
    keeps its FIRST 4,096 records: make room for this test's."""
    reg = obs.default_registry()
    reg.journal_clear(spans.TIMELINE)
    reg.journal_clear(compile_ledger.LEDGER)


def small_model(width=16):
    m = dtpu.Model(dtpu.nn.Sequential([
        dtpu.nn.Flatten(),
        dtpu.nn.Dense(width, activation="relu"),
        dtpu.nn.Dense(10),
    ]))
    m.compile(optimizer=dtpu.optim.SGD(0.05),
              loss="sparse_categorical_crossentropy")
    return m


def data(n=64):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, 4, 4)).astype("float32"),
            rng.integers(0, 10, size=n))


class Marks:
    """What the process-global journals gained since this was made."""

    def __init__(self):
        self.reg = obs.default_registry()
        self.n_spans = len(self.reg.journal(spans.TIMELINE))
        self.n_compiles = len(self.reg.journal(compile_ledger.LEDGER))

    def spans(self):
        return self.reg.journal(spans.TIMELINE)[self.n_spans:]

    def compiles(self):
        return self.reg.journal(compile_ledger.LEDGER)[self.n_compiles:]


def union_s(intervals):
    return cli._union_s(intervals)


# ------------------------------------------------------------- timeline --
class TestTimeline:
    def test_a_record_holds_start_end_thread_and_parent(self):
        reg = MetricsRegistry()
        before = time.time_ns()
        with obs.span("outer", registry=reg):
            with obs.span("inner", registry=reg):
                time.sleep(0.01)
        after = time.time_ns()
        inner, outer = reg.journal(spans.TIMELINE)  # in closing order
        assert (inner["path"], inner["parent"]) == ("outer/inner", "outer")
        assert (outer["path"], outer["parent"]) == ("outer", None)
        assert inner["thread"] == outer["thread"] == threading.get_ident()
        for r in (inner, outer):
            assert r["start"] <= r["end"]
            # Unix nanoseconds, on the wall clock to the anchor's precision
            assert before - 5e7 <= r["start"] and r["end"] <= after + 5e7
        assert outer["start"] <= inner["start"]
        assert inner["end"] <= outer["end"]
        assert inner["end"] - inner["start"] >= 0.01 * 1e9
        # the histograms and counters are what they were
        snap = reg.snapshot()
        assert snap["counters"]["span_calls/outer/inner"] == 1
        assert snap["histograms"]["span_seconds/outer"]["count"] == 1

    def test_spans_of_another_thread_carry_its_ident_and_no_parent(self):
        reg = MetricsRegistry()
        seen = {}

        def work():
            seen["ident"] = threading.get_ident()
            with obs.span("stage", registry=reg):
                seen["inside"] = obs.current_span()

        with obs.span("main", registry=reg):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        stage, main = reg.journal(spans.TIMELINE)
        assert seen["inside"] == "stage"  # not main/stage: stacks are per thread
        assert (stage["path"], stage["parent"]) == ("stage", None)
        assert stage["thread"] == seen["ident"] != main["thread"]
        assert main["start"] <= stage["start"] <= stage["end"] <= main["end"]

    def test_the_clock_is_one_anchor_and_the_zero_is_the_os_start(self):
        t = time.perf_counter()
        assert abs(spans.unix_ns(t) - time.time_ns()) < 5e7
        assert spans.unix_ns(t + 1.0) - spans.unix_ns(t) == 10 ** 9
        zero = spans.process_start_ns()
        imported = IMPORTED
        # The package's own import is in the timeline, after the process's
        # start and (pytest, jax and conftest first) well within the hour.
        assert len(imported) == 1 and imported[0]["parent"] is None
        assert zero < imported[0]["start"] < imported[0]["end"]
        assert imported[0]["start"] - zero < 3600 * 1e9
        assert abs(spans.process_start_ns() - zero) < 5e7  # good to a tick

    def test_a_phase_is_a_span_that_is_not_on_the_stack(self):
        marks = Marks()
        phase = spans.begin("tl_phase")
        assert obs.current_span() == "tl_phase"
        with obs.span("tl_inside") as sp:
            assert sp.path == "tl_inside"  # its own path, not tl_phase/...
        phase.end()
        phase.end()  # once
        assert obs.current_span() is None
        inside, closed = marks.spans()
        assert (inside["path"], inside["parent"]) == ("tl_inside", "tl_phase")
        assert (closed["path"], closed["parent"]) == ("tl_phase", None)
        assert closed["start"] <= inside["start"]
        assert inside["end"] <= closed["end"]
        assert obs.default_registry().counter_value(
            "span_calls/tl_phase") == 1

    def test_a_phase_whose_owner_raised_is_forgotten(self):
        marks = Marks()

        def owner():
            phase = spans.begin("tl_doomed")
            assert obs.current_span() == "tl_doomed"
            raise ValueError(phase.name)

        with pytest.raises(ValueError):
            owner()
        assert obs.current_span() is None
        with obs.span("tl_after") as sp:
            pass
        assert sp.path == "tl_after"
        assert [r["path"] for r in marks.spans()] == ["tl_after"]

    def test_a_loop_keeps_its_first_eight_spans_of_each_name(self):
        reg = MetricsRegistry()
        keep = spans.loop_gate()
        for _ in range(20):
            with obs.span("a", registry=reg, timeline=keep("a")):
                pass
            with obs.span("b", registry=reg, timeline=keep("b")):
                pass
        paths = [r["path"] for r in reg.journal(spans.TIMELINE)]
        assert paths == ["a", "b"] * spans.LOOP_SPANS_KEPT
        assert reg.counter_value("span_calls/a") == 20  # all of them accrue

    def test_a_journal_keeps_its_first_records_and_counts_the_rest(self):
        reg = MetricsRegistry()
        for i in range(JOURNAL_CAPACITY + 10):
            reg.journal_append("j", {"i": i})
        kept = reg.journal("j")
        assert len(kept) == JOURNAL_CAPACITY == 4096
        assert [r["i"] for r in kept[:3]] == [0, 1, 2]  # the set-up stays
        assert reg.counter_value("journal_dropped/j") == 10
        assert "j" not in json.dumps(reg.snapshot()["rings"])
        reg.journal_clear("j")
        assert reg.journal("j") == []
        reg.journal_append("j", {"i": -1})  # and it has room again
        assert reg.journal("j") == [{"i": -1}]

    def test_build_stays_in_the_timeline_after_300_steps(self):
        marks = Marks()
        m = small_model()
        x, y = data(64)
        m.fit(x, y, batch_size=8, epochs=1, steps_per_epoch=300, verbose=0,
              shuffle=False)
        new = marks.spans()
        by_path = {}
        for r in new:
            by_path.setdefault(r["path"], []).append(r)
        assert len(by_path["build"]) == 1
        assert {"build/init", "build/place", "build/opt_state"} <= set(
            by_path)
        assert by_path["build"][0]["parent"] == "fit_setup"  # fit built it
        assert by_path["build/init"][0]["parent"] == "build"
        # the step loop's first 8 of each, and the epoch's closing sync
        assert len(by_path["input_wait"]) == spans.LOOP_SPANS_KEPT
        assert len(by_path["dispatch"]) == spans.LOOP_SPANS_KEPT + 1
        setup, teardown = by_path["fit_setup"][0], by_path["fit_teardown"][0]
        first_wait = min(r["start"] for r in by_path["input_wait"])
        assert setup["parent"] is None and teardown["parent"] is None
        assert setup["end"] <= first_wait
        assert max(r["end"] for r in by_path["dispatch"]) <= teardown["start"]
        # every one of the 300 still accrued
        tele = m.last_fit_telemetry
        assert tele["input_wait"] >= 0 and tele["dispatch"] > 0
        assert marks.reg.counter_value("fit/steps") >= 300

    def test_obs_off_records_nothing_and_the_timer_still_fills(self):
        m = small_model()
        x, y = data(64)
        m.fit(x, y, batch_size=8, epochs=1, verbose=0)  # compiled, built
        marks = Marks()
        calls = marks.reg.counter_value("span_calls/dispatch")
        prev = obs.set_enabled(False)
        try:
            m.fit(x, y, batch_size=8, epochs=1, verbose=0)
            f = jax.jit(lambda a: a * 5 - 2)
            f(jnp.ones(3))  # a compile with the listeners installed
        finally:
            obs.set_enabled(prev)
        assert marks.spans() == [] and marks.compiles() == []
        assert marks.reg.counter_value("span_calls/dispatch") == calls
        tele = m.last_fit_telemetry
        assert tele["dispatch"] > 0 and tele["input_wait"] >= 0


# --------------------------------------------------------------- ledger --
class TestCompileLedger:
    def test_the_train_step_is_named_once_a_stage_inside_its_dispatch(self):
        marks = Marks()
        m = small_model(width=23)  # shapes no other test has compiled
        x, y = data(64)
        m.fit(x, y, batch_size=8, epochs=1, verbose=0)
        first = min((r for r in marks.spans() if r["path"] == "dispatch"),
                    key=lambda r: r["start"])
        step = [r for r in marks.compiles() if r["fun_name"] == "step"]
        assert sorted(r["stage"] for r in step) == [
            "backend", "lower", "trace"]
        assert [r["stage"] for r in sorted(step, key=lambda r: r["start"])
                ] == ["trace", "lower", "backend"]
        for r in step:
            assert r["span"] == "dispatch"
            assert r["thread"] == first["thread"]
            # JAX's time.time() against the timeline's anchor: 1 ms of room
            assert first["start"] - 1e6 <= r["start"] <= r["end"]
            assert r["end"] <= first["end"] + 1e6
        assert [r for r in step if r["stage"] == "backend"][0]["cache"] in (
            "miss", "hit", "uncached")
        # whatever else was compiled, each stage's union fits the span
        inside = [r for r in marks.compiles() if r["span"] == "dispatch"
                  and r["end"] <= first["end"] + 1e6]
        for stage in ("trace", "lower", "backend"):
            total = union_s((r["start"], r["end"]) for r in inside
                            if r["stage"] == stage)
            assert total <= (first["end"] - first["start"]) / 1e9 + 1e-3
        # init's programs are put down to build/init, not to the caller
        assert any(r["span"] == "build/init" for r in marks.compiles())

    def test_an_inner_jit_is_nested_in_the_outers_trace_and_counted_once(
            self):
        marks = Marks()

        @jax.jit
        def tl_inner(a):
            time.sleep(0.005)  # the trace of a kernel call, in miniature
            return a * 2 + 1

        @jax.jit
        def tl_outer(a):
            return tl_inner(a) + tl_inner(a + 1)  # traced once, cached

        with obs.span("tl_compile") as sp:
            jax.block_until_ready(tl_outer(jnp.ones(4)))
        mine = [r for r in marks.compiles() if r["span"] == "tl_compile"]
        outer = [r for r in mine if r["fun_name"] == "tl_outer"
                 and r["stage"] == "trace"]
        inner = [r for r in mine if r["fun_name"] == "tl_inner"]
        assert len(outer) == 1
        assert inner and all(r["stage"] == "trace" for r in inner)
        # never lowered on its own, and nested in the outer's interval
        assert outer[0]["start"] <= inner[0]["start"]
        assert inner[0]["end"] <= outer[0]["end"]
        traces = [(r["start"], r["end"]) for r in mine
                  if r["stage"] == "trace"]
        summed = sum(e - s for s, e in traces) / 1e9
        assert union_s(traces) < summed  # the inner one is not counted twice
        assert union_s(traces) <= sp.seconds + 1e-3
        # the sub-millisecond traces (a*2, +1, ...) were counted, not kept
        assert marks.reg.counter_value("compile/short_traces") > 0
        assert marks.reg.counter_value("compile/short_trace_seconds") > 0
        assert all(r["end"] - r["start"] >= 1e6 for r in mine
                   if r["stage"] == "trace")

    def test_a_first_compile_reads_miss_and_a_second_hit(self, tmp_path):
        from jax.experimental.compilation_cache import compilation_cache

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        before = {n: getattr(jax.config, n) for n in names}
        marks = Marks()
        reg = marks.reg
        counted = {k: reg.counter_value(f"compile/{k}")
                   for k in ("cache_lookups", "cache_hits")}
        cache = chip_smoke.CacheCounter()
        snap = cache.snapshot()
        try:
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            compilation_cache.reset_cache()
            x = jnp.arange(12.0)
            # Two functions, one program: the second finds the first's entry.
            with obs.span("tl_cold"):
                jax.block_until_ready(
                    jax.jit(lambda a: jnp.tanh(a) * 7 + 3)(x))
            with obs.span("tl_warm"):
                jax.block_until_ready(
                    jax.jit(lambda a: jnp.tanh(a) * 7 + 3)(x))
        finally:
            for n, v in before.items():
                jax.config.update(n, v)
            compilation_cache.reset_cache()
        backend = {r["span"]: r for r in marks.compiles()
                   if r["stage"] == "backend" and r["fun_name"] == "<lambda>"}
        assert backend["tl_cold"]["cache"] == "miss"
        assert "retrieval_s" not in backend["tl_cold"]
        assert backend["tl_warm"]["cache"] == "hit"
        assert backend["tl_warm"]["retrieval_s"] >= 0
        gained = {k: reg.counter_value(f"compile/{k}") - v
                  for k, v in counted.items()}
        assert gained["cache_hits"] == 1
        # every lookup but that one became a record that reads "miss"
        verdicts = [r["cache"] for r in marks.compiles()
                    if r["stage"] == "backend"]
        assert verdicts.count("hit") == 1
        assert verdicts.count("miss") == gained["cache_lookups"] - 1 >= 1
        # chip_smoke's counter reads the same two counters
        assert cache.since(snap) == {
            "cache_requests": int(gained["cache_lookups"]), "cache_hits": 1}

    def test_install_registers_once(self):
        from jax._src import monitoring

        n = len(monitoring.get_event_time_span_listeners())
        compile_ledger.install()
        compile_ledger.install()
        assert len(monitoring.get_event_time_span_listeners()) == n


# ----------------------------------------------------------------- dump --
S = 10 ** 9


def built_dump(tmp_path):
    """A hand-built dump: import, a build with a child that compiled one
    program, a caller's gap with the caller's own program, a fit."""
    z = 1_000 * S
    span = lambda path, a, b, parent=None, thread=1: {
        "kind": "span", "path": path, "start": z + int(a * S),
        "end": z + int(b * S), "thread": thread, "parent": parent}
    prog = lambda name, stage, a, b, where, **kw: {
        "kind": "compile", "fun_name": name, "stage": stage,
        "start": z + int(a * S), "end": z + int(b * S), "thread": 1,
        "span": where, **kw}
    records = [
        {"kind": "timeline_header", "process_start": z, "pid": 7, "rank": 0,
         "main_thread": 1, "dropped": {"timeline": 0, "compile_ledger": 0},
         "short_traces": 41, "short_trace_seconds": 0.0123},
        span("import", 2.0, 5.0),
        span("build/init", 6.0, 8.0, "build"),
        span("build", 6.0, 9.0),
        span("fit_setup", 11.0, 11.5),
        span("dispatch", 11.5, 16.5),
        span("stage", 11.0, 12.0, thread=2),
        prog("_normal", "trace", 6.5, 6.6, "build/init"),
        prog("_normal", "backend", 6.6, 7.6, "build/init", cache="hit",
             retrieval_s=0.9),
        prog("reference", "backend", 9.5, 10.5, None, cache="miss"),
        prog("step", "trace", 11.6, 13.6, "dispatch"),
        prog("kernel", "trace", 12.0, 13.0, "dispatch"),
        prog("step", "lower", 13.6, 14.1, "dispatch"),
        prog("step", "backend", 14.1, 16.1, "dispatch", cache="miss"),
    ]
    path = tmp_path / "timeline-rank0-pid7.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    return path


class TestDump:
    def test_summary_of_a_hand_built_dump(self, tmp_path):
        summary = cli.summarize_timeline(
            flight.read_dump(built_dump(tmp_path)))
        rows = {(r["path"], round(r["start"], 3)): r for r in summary["rows"]}
        assert [r["path"] for r in summary["rows"]] == [
            "(before import)", "import", "caller", "build", "caller",
            "fit_setup", "dispatch"]
        assert rows[("(before import)", 0.0)]["seconds"] == pytest.approx(2.0)
        assert rows[("import", 2.0)]["self_seconds"] == pytest.approx(3.0)
        build = rows[("build", 6.0)]
        assert build["seconds"] == pytest.approx(3.0)
        assert build["self_seconds"] == pytest.approx(1.0)  # less its child
        init, = build["children"]
        assert init["path"] == "build/init"
        assert init["self_seconds"] == pytest.approx(2.0 - 1.1)
        assert init["programs"]["hit"] == 1
        assert [p["name"] for p in init["programs"]["named"]] == ["_normal"]
        gap = rows[("caller", 9.0)]  # the caller's own program is under it
        assert gap["seconds"] == pytest.approx(2.0)
        assert gap["programs"]["named"][0]["name"] == "reference"
        assert gap["programs"]["miss"] == 1
        dispatch = rows[("dispatch", 11.5)]
        # trace 11.6-13.6 (the inner one inside it), lower, backend to 16.1
        assert dispatch["self_seconds"] == pytest.approx(5.0 - 4.5)
        assert dispatch["programs"]["stage_s"]["trace"] == pytest.approx(2.0)
        assert dispatch["programs"]["miss"] == 1
        w = summary["window"]
        assert w["seconds"] == pytest.approx(11.5)
        assert w["spans_s"] == pytest.approx(3.0 + 3.0 + 0.5)
        # the program's own: the step's is after the window, and the
        # caller's reference (no span asked for it) is in no sum
        assert w["backend_s"] == pytest.approx(1.0)
        assert w["cache_misses"] == 0
        assert summary["short_traces"] == 41
        assert summary["other_threads"] == [2]

    def test_dtpu_events_renders_a_dump(self, tmp_path, capsys):
        path = built_dump(tmp_path)
        assert cli.main(["--timeline", str(path)]) == 0
        out = capsys.readouterr().out
        assert "(before import)" in out and "caller" in out
        assert "build/init" in out and "fit_setup" in out
        assert "step: trace 2.000 lower 0.500 backend 2.000 [miss]" in out
        assert "set-up window" in out
        assert "41 traces under 1 ms (0.012 s) counted and not kept" in out
        assert cli.main(["--timeline", str(path), "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["spans"] == 6
        assert cli.main(["--timeline", str(tmp_path / "none.jsonl")]) == 2
        with pytest.raises(SystemExit):
            cli.main([])  # neither an event log nor a timeline
        capsys.readouterr()

    def test_a_fit_round_trips_through_the_dump(self, tmp_path, capsys,
                                                monkeypatch):
        m = small_model()
        x, y = data(64)
        m.fit(x, y, batch_size=8, epochs=1, verbose=0)
        monkeypatch.delenv(flight.ENV_DIR, raising=False)
        monkeypatch.delenv("DTPU_EVENT_LOG", raising=False)
        assert flight.dump_timeline() is None  # no location: nowhere
        monkeypatch.setenv(flight.ENV_DIR, str(tmp_path))
        path = flight.dump_timeline()
        assert path.parent == tmp_path
        assert path.name.startswith("timeline-rank0-pid")
        records = flight.read_dump(path)
        header = records[0]
        assert header["kind"] == "timeline_header"
        assert header["main_thread"] == threading.main_thread().ident
        assert header["process_start"] == pytest.approx(
            spans.process_start_ns(), abs=5e7)
        reg = obs.default_registry()
        assert header["short_traces"] == reg.counter_value(
            "compile/short_traces") > 0
        assert header["short_trace_seconds"] == pytest.approx(
            reg.counter_value("compile/short_trace_seconds"))
        assert len(records) - 1 == (
            len(reg.journal(spans.TIMELINE))
            + len(reg.journal(compile_ledger.LEDGER)))
        assert cli.main(["--timeline", str(path)]) == 0
        out = capsys.readouterr().out
        assert "import" in out and "fit_teardown" in out and "step:" in out

    def test_the_package_dumps_at_exit_where_a_location_is_set(
            self, tmp_path):
        code = ("import distributed_tpu as d, jax, jax.numpy as jnp\n"
                "with d.obs.span('work'):\n"
                "    jax.jit(lambda a: a + 1)(jnp.ones(3))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   DTPU_FLIGHT_DIR=str(tmp_path))
        env.pop("DTPU_EVENT_LOG", None)
        proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        dump, = list(tmp_path.glob("timeline-rank0-pid*.jsonl"))
        records = flight.read_dump(dump)
        paths = [r["path"] for r in records if r["kind"] == "span"]
        assert paths == ["import", "work"]
        assert any(r["kind"] == "compile" and r["span"] == "work"
                   and r["stage"] == "backend" for r in records)
        # the interpreter and jax came up before the package's first line
        start = [r for r in records if r["kind"] == "span"][0]["start"]
        assert start > records[0]["process_start"]
