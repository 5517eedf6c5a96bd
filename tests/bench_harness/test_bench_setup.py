"""The set-up's per-layer metrics: a rehearsal of the train driver that lists
the seven ``setup_*`` names (its manifest is ``rehearsal-setup.json``;
``run.py --rehearsal`` reads the accepted ``rehearsal.json``, which this PR
may not edit, so the child process points it at the new file, as
``test_bench_lfm2.py`` does), each reader on a hand-built timeline and
ledger against the value computed by hand, a program without a timeline,
and the entries of ``BENCHMARK.json``."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, setup_timeline  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal-setup.json")
SETUP_METRICS = ("setup_import_s", "setup_build_s", "setup_trace_s",
                 "setup_lower_s", "setup_backend_s", "setup_cache_misses",
                 "setup_unseen_s")
TRAIN_CELLS = ("gpt2-medium.train.1chip", "gpt2-large.train.fsdp4",
               "kanana2-30b.train.ep8share", "keye-vl2-30b.train.dsa8k",
               "lfm2-8b-a1b.train.ep4share")
CHILD = ("import sys; sys.path.insert(0, {root!r}); "
         "from benchmarks import run; run.REHEARSAL_MANIFEST = {manifest!r}; "
         "sys.exit(run.main(sys.argv[1:]))")


def test_rehearsal_lists_the_seven_names():
    env = dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=1 "
                        "--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = CHILD.format(root=ROOT, manifest=MANIFEST)
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", "gpt2-tiny.train",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
        preexec_fn=lambda: os.nice(15))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert set(SETUP_METRICS) < set(line["metrics"])
    assert set(line["metrics"]) == {
        m["name"] for m in harness.load_json(MANIFEST)["per_layer"]}
    # no number of a CPU run is written under the name of a metric
    assert all(m["value"] is None and m["unit"]
               for m in line["metrics"].values())


# ------------------------------------------------------- a set-up by hand --
S = 10 ** 9
ZERO = 1_000 * S
MAIN, OTHER = 1, 2


def span(path, a, b, parent=None, thread=MAIN):
    return {"path": path, "start": ZERO + int(a * S), "end": ZERO + int(b * S),
            "thread": thread, "parent": parent}


def prog(name, stage, a, b, where, thread=MAIN, **more):
    return {"fun_name": name, "stage": stage, "start": ZERO + int(a * S),
            "end": ZERO + int(b * S), "thread": thread, "span": where, **more}


# A benchmark run in miniature, seconds from the process's start: the
# interpreter and jax to 2.0; import; compile; build with three children;
# the caller's reference; the first fit (its step compiled inside the first
# dispatch, a kernel's trace nested in the step's); the caller's
# global_norm; the timed fit, whose fit_setup ends the window at 18.4 and
# whose first step recompiles something after it.
TIMELINE = [
    span("import", 2.0, 5.0),
    span("compile", 5.5, 5.6),
    span("build/init", 6.0, 8.0, "build"),
    span("build/place", 8.0, 8.5, "build"),
    span("build/opt_state", 8.5, 8.9, "build"),
    span("build", 6.0, 9.0),
    span("fit_setup", 11.0, 11.5),
    span("stage", 11.0, 12.0, thread=OTHER),
    span("input_wait", 11.5, 11.6),
    span("dispatch", 11.6, 16.6),
    span("dispatch", 16.6, 16.8),
    span("fit_teardown", 16.8, 17.0),
    span("fit_setup", 18.0, 18.4),
    span("input_wait", 18.4, 18.5),
    span("dispatch", 18.5, 18.7),
]
LEDGER = [
    prog("_normal", "trace", 6.1, 6.3, "build/init"),
    prog("_normal", "lower", 6.3, 6.5, "build/init"),
    prog("_normal", "backend", 6.5, 7.5, "build/init", cache="hit",
         retrieval_s=0.9),
    prog("reference", "trace", 9.2, 9.6, None),
    prog("reference", "backend", 9.6, 10.6, None, cache="hit",
         retrieval_s=0.9),
    prog("put", "trace", 11.2, 11.4, "stage", thread=OTHER),
    prog("step", "trace", 11.7, 13.7, "dispatch"),
    prog("kernel", "trace", 12.0, 13.0, "dispatch"),
    prog("step", "lower", 13.7, 14.7, "dispatch"),
    prog("step", "backend", 14.7, 16.2, "dispatch", cache="miss"),
    prog("norm", "trace", 17.2, 17.3, None),
    prog("norm", "backend", 17.3, 17.6, None, cache="miss"),
    prog("late", "trace", 18.5, 18.6, "dispatch"),
    prog("late", "backend", 18.6, 18.7, "dispatch", cache="miss"),
]
BY_HAND = {
    "setup_import_s": 5.0,
    "setup_build_s": 3.0,
    # 6.1-6.3, the other thread's 11.2-11.4, and 11.7-13.7 with the
    # kernel's 12.0-13.0 inside it counted once. The caller's programs
    # (the reference and the norm: no span of the program asked for them)
    # are in no sum, and the norm's miss is no miss of the program's
    "setup_trace_s": 0.2 + 0.2 + 2.0,
    "setup_lower_s": 0.2 + 1.0,
    "setup_backend_s": 1.0 + 1.5,
    "setup_cache_misses": 1,  # and the late one is past the window
    # import 3.0 and compile 0.1 (no child), build 3.0 less 6.0-8.9,
    # fit_setup 0.5 (the other thread's program covers none of it),
    # input_wait 0.1, dispatch 5.0 less 11.7-16.2, the closing dispatch 0.2,
    # fit_teardown 0.2, the second fit_setup 0.4; no gap between them
    "setup_unseen_s": 3.0 + 0.1 + 0.1 + 0.5 + 0.1 + 0.5 + 0.2 + 0.2 + 0.4,
}


def ctx_of(telemetry):
    return harness.LayerContext(
        trace=None, telemetry=telemetry, config={}, traffic={},
        cell={"name": "no-such-cell"}, peaks=None, values={})


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_each_reader_on_a_hand_built_set_up(name):
    setup = setup_timeline.setup_of(TIMELINE, LEDGER, ZERO, MAIN)
    assert reader(name).read(ctx_of({"_setup": setup})) == pytest.approx(
        BY_HAND[name])


def test_the_window_ends_at_the_last_fit_setup_and_the_account_closes():
    setup = setup_timeline.setup_of(TIMELINE, LEDGER, ZERO, MAIN)
    assert (setup.end - ZERO) / S == pytest.approx(18.4)
    assert all(r["end"] <= setup.end for r in setup.spans + setup.compiles)
    top = setup_timeline.top_level(setup)
    assert [r["path"] for r in top] == [
        "import", "compile", "build", "fit_setup", "input_wait", "dispatch",
        "dispatch", "fit_teardown", "fit_setup"]
    in_spans = sum(r["end"] - r["start"] for r in top) / S
    assert in_spans == pytest.approx(12.5)
    # the rest of the window is the caller's: 0-2, 5-5.5, 5.6-6, 9-11, 17-18
    assert 18.4 - in_spans == pytest.approx(2.0 + 0.5 + 0.4 + 2.0 + 1.0)
    # one fit alone: its fit_setup ends the window
    first = setup_timeline.setup_of(TIMELINE[:12], LEDGER, ZERO, MAIN)
    assert (first.end - ZERO) / S == pytest.approx(11.5)
    assert setup_timeline.cache_misses(first) == 0


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_a_program_without_a_timeline_reads_as_nothing(name, monkeypatch):
    """The parent commit's registry keeps no journal, and a run with
    ``DTPU_OBS=0`` or without a fit closes no ``fit_setup``: every reader
    returns None and raises nothing."""
    from distributed_tpu import obs

    assert setup_timeline.setup_of([], [], ZERO, MAIN) is None
    assert setup_timeline.setup_of(TIMELINE[:6], LEDGER, ZERO, MAIN) is None
    monkeypatch.setattr(obs, "default_registry",
                        lambda: types.SimpleNamespace())
    ctx = ctx_of({})
    assert reader(name).read(ctx) is None
    assert ctx.telemetry["_setup"] is None


def test_the_readers_read_this_process(monkeypatch):
    """``read_setup`` joins the default registry's journals, the process's
    start as the program has it and the main thread, once a context."""
    import threading

    from distributed_tpu import obs

    main = threading.main_thread().ident
    retag = lambda rows: [dict(r, thread=main if r["thread"] == MAIN
                               else r["thread"]) for r in rows]
    journals = {"timeline": retag(TIMELINE), "compile_ledger": retag(LEDGER)}
    calls = []

    def journal(name):
        calls.append(name)
        return journals[name]

    monkeypatch.setattr(obs, "default_registry",
                        lambda: types.SimpleNamespace(journal=journal))
    monkeypatch.setattr(obs.spans, "process_start_ns", lambda: ZERO)
    ctx = ctx_of({})
    for name in SETUP_METRICS:
        assert reader(name).read(ctx) == pytest.approx(BY_HAND[name])
    assert sorted(calls) == ["compile_ledger", "timeline"]  # joined once


def test_benchmark_json_gains_seven_entries_that_move_setup_s():
    manifest = harness.load_manifest()
    # in the issue's order among themselves; what else a list holds, and
    # what a later PR appends, is not asserted here (ROADMAP D17)
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in SETUP_METRICS] == list(SETUP_METRICS)
    layers = {"setup_import_s": "process", "setup_build_s": "Model.build",
              "setup_trace_s": "step program (host)",
              "setup_lower_s": "step program (host)",
              "setup_backend_s": "compile cache",
              "setup_cache_misses": "compile cache",
              "setup_unseen_s": "process"}
    for name in SETUP_METRICS:
        entry = harness.entry(manifest, "per_layer", name)
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert entry["layer"] == layers[name]
        assert set(TRAIN_CELLS) <= set(entry["workloads"])
        counter = name == "setup_cache_misses"
        assert entry["unit"] == ("programs" if counter else "s")
        assert entry["source"] == "program_span"  # the ledger's records
        for cell in TRAIN_CELLS:
            assert entry in harness.metrics_of(manifest, "per_layer", cell)
    # the outside check stays
    assert harness.entry(manifest, "per_layer", "setup_compile_s")[
        "layer"] == "compile cache"
