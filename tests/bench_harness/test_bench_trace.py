"""The trace reduction on a small hand-written XSpace whose busy union, gaps,
kernel totals and exposed collective time are known exactly. Its event names
are those of the programs compiled for a described v5e; no trace recorded on
the chip is kept here yet (record_fixture.py records one)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond


# Event names as the step compiled for a described v5e names its operations
# (benchmarks/rehearsal/compile_v5e.py, PR 22): a Mosaic kernel is named
# after the scope it is called in; its neighbours are not.
KERNEL_LINE = (
    "%jvp_dtpu_flash_fwd_packed_.24 = (bf16[8,1024,1024]{2,1,0:T(8,128)(2,1)"
    "S(1)}, f32[8,8,1024,128]{3,2,1,0:T(8,128)}, f32[8,8,1024,128]{3,2,1,0:"
    "T(8,128)}) custom-call(bf16[8,1024,1024]{2,1,0:T(8,128)(2,1)} "
    "%convolution_add_fusion.168, bf16[8,1024,1024]{2,1,0:T(8,128)(2,1)} "
    "%all-gather.1)")


def _event(meta, start_us, dur_us, stat=None):
    stats = (f' stats {{ metadata_id: 1 str_value: "{stat}" }}'
             if stat else "")
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US}{stats} }}")


# Device 0, microseconds: program run A [0, 100), run B [200, 300).
#   A: flash kernel [0, 40), fusion [40, 60), all-gather [60, 80) alone,
#      fusion [90, 100)      -> busy 90, gap [80, 90)
#   B: flash kernel [200, 240), all-reduce [240, 260) alone, fusion
#      [260, 300)            -> busy 100
# Host: span "dispatch" [78, 92), span "input_wait" [100, 200).
XSPACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_event(10, 0, 100)} {_event(10, 200, 100)} {_event(11, 150, 1)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 0, 40, "jit(step)/jvp(dtpu_flash_fwd_packed)/pallas_call")}
    {_event(2, 40, 20, "jit(step)/jvp(dtpu_flash_fwd_packed)/convert")}
    {_event(3, 60, 20)} {_event(2, 90, 10)}
    {_event(1, 200, 40, "jit(step)/jvp(dtpu_flash_fwd_packed)/pallas_call")}
    {_event(4, 240, 20)} {_event(5, 260, 40)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{KERNEL_LINE}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.7 = f32[4]{0} fusion(f32[4]{0} %all-reduce.5)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%all-gather.2 = f32[4]{0} all-gather(f32[1]{0} %p)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %q)" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%fusion.9.remat = f32[4]{0} fusion(f32[4]{0} %r)" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name: "jit_step(123)" }} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "jit_tiny(5)" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
    {_event(1, 78, 14)} {_event(2, 100, 100)} {_event(3, 0, 300)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "dispatch" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "input_wait" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "unrelated" }} }}
}}
"""


@pytest.fixture(scope="module")
def parsed():
    from jax.profiler import ProfileData

    return trace.parse(ProfileData.from_text_proto(XSPACE))


def us(seconds):
    return round(seconds * 1e6, 6)


def test_interval_arithmetic():
    assert trace.union([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    assert trace.gaps([(1, 2)], (0, 3)) == [(0, 1), (2, 3)]
    assert trace.total(trace.clip([(0, 5), (7, 9)], (4, 8))) == 2


def test_planes_and_window(parsed):
    assert [d.ordinal for d in parsed.devices] == [0]
    assert us(parsed.window_s) == 300
    assert set(parsed.host_spans) == {"dispatch", "input_wait"}


def test_busy_union_and_idle_share(parsed):
    assert us(trace.busy_seconds(parsed)) == 190
    assert trace.idle_share(parsed) == pytest.approx(110 / 300)


def test_kernel_total_counts_the_call_and_not_its_neighbours(parsed):
    dev = trace.device(parsed)
    calls = trace.matching(dev, "dtpu_flash_fwd")
    assert len(calls) == 2 and us(sum(e.seconds for e in calls)) == 80
    assert trace.matching(dev, "dtpu_xent") == []
    assert trace.kernel_names(parsed) == ["dtpu_flash_fwd_packed"]


# Operation names of the five Mosaic kernels, and of what stands around
# them, in the gpt2-medium step (and one of the gpt2-large FSDP step)
# compiled for a described v5e (PR 22).
COMPILED_NAMES = {
    "%jvp_dtpu_flash_fwd_packed_.24": "dtpu_flash_fwd_packed",
    "%transpose_jvp_dtpu_flash_dq_packed__.3": "dtpu_flash_dq_packed",
    "%transpose_jvp_dtpu_flash_dkv_packed__.24": "dtpu_flash_dkv_packed",
    "%jvp_dtpu_xent_fwd_.1": "dtpu_xent_fwd",
    "%transpose_jvp_dtpu_xent_bwd__.1": "dtpu_xent_bwd",
    "%dtpu_flash_dkv_packed.12": "dtpu_flash_dkv_packed",  # under FSDP
    "%pallas_call.195": None,
    "%convolution_add_fusion.168": None,
}


@pytest.mark.parametrize("op", sorted(COMPILED_NAMES))
def test_kernel_name_of_a_compiled_operation(op):
    line = f"{op} = bf16[8,1024,1024]{{2,1,0}} custom-call(bf16[8] %p)"
    ev = trace.Event(line, 0.0, 1.0, line + " jit(step)/jvp("
                     "dtpu_flash_fwd_packed)/pallas_call")
    assert trace.kernel_name(ev) == COMPILED_NAMES[op]


def test_the_train_mixes_expect_kernels_the_compiled_step_holds():
    from benchmarks import harness

    ran = {k for k in COMPILED_NAMES.values() if k}
    for mix in ("train-b8-t1024", "train-b16-t1024"):
        want = harness.load_json(os.path.join(
            ROOT, "benchmarks", "traffic", mix + ".json"))["expect_kernels"]
        assert len(want) == 5
        assert all(any(r.startswith(k) for r in ran) for k in want)


# Collectives as the gpt2-large FSDP step compiled for a described v5e:2x2
# names them (PR 22), and operations that only take one as an operand.
@pytest.mark.parametrize("line,want", [
    ("%all-gather.2726 = bf16[1280,1280]{1,0:T(8,128)(2,1)} "
     "all-gather(bf16[320,1280]{1,0} %param_0.24334)", True),
    ("%all-reduce.1127 = bf16[20544,8,128]{2,1,0:T(8,128)(2,1)} "
     "all-reduce(bf16[20544,8,128]{2,1,0} %pad.2)", True),
    ("%collective-permute-start = (bf16[48,8,128]{2,1,0}, bf16[48,8,128]"
     "{2,1,0}) collective-permute-start(bf16[48,8,128]{2,1,0} %slice.4)",
     True),
    ("%collective-permute-done = bf16[48,8,128]{2,1,0} "
     "collective-permute-done(%collective-permute-start)", True),
    ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %all-reduce.5)", False),
    ("%convolution_add_fusion.168 = bf16[8,1024,1024]{2,1,0} "
     "fusion(bf16[1280,1280]{1,0} %all-gather.2726)", False),
])
def test_collectives_by_their_compiled_names(line, want):
    assert trace.is_collective(trace.Event(line, 0.0, 1.0)) is want


def test_busy_time_is_the_mean_over_the_chips():
    from jax.profiler import ProfileData

    def plane(n, busy_us):
        return f"""planes {{ id: {n + 1} name: "/device:TPU:{n}"
          lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
            {_event(1, 0, busy_us)} {_event(1, 90, 10)} }}
          event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[4]{{0}} fusion()" }} }}
        }}"""

    two = trace.parse(ProfileData.from_text_proto(plane(1, 30) + plane(0, 50)))
    assert [d.ordinal for d in two.devices] == [0, 1]
    assert us(two.window_s) == 100
    assert us(trace.busy_seconds(two)) == (60 + 40) / 2
    assert trace.idle_share(two) == pytest.approx(0.5)


def test_program_runs_and_their_busy_time(parsed):
    dev = trace.device(parsed)
    runs = trace.module_runs(dev)  # the program with most time
    assert [r.name for r in runs] == ["jit_step(123)"] * 2
    assert [us(s) for s in trace.run_busy_seconds(dev, runs)] == [90, 100]


def test_exposed_collective_time(parsed):
    dev = trace.device(parsed)
    assert us(trace.exposed_collective_seconds(dev)) == 40
    first = trace.module_runs(dev)[0]
    assert us(trace.exposed_collective_seconds(
        dev, (first.start, first.end))) == 20


def test_breakdown_names_kernels_and_gaps(parsed):
    ops = dict(trace.top_ops(parsed))
    assert us(ops["dtpu_flash_fwd_packed"]) == 80
    assert us(ops["fusion"]) == 70
    gaps = trace.idle_gaps(parsed)
    assert gaps[0][0] == "input_wait" and us(gaps[0][1]) == 100
    assert gaps[1][0] == "dispatch" and us(gaps[1][1]) == 10


def test_layer_metric_readers_on_the_synthetic_trace(parsed):
    from benchmarks import harness

    manifest = harness.load_manifest()
    peaks = harness.peaks_for("TPU v5 lite")
    ctx = harness.LayerContext(
        trace=parsed, telemetry={
            "train_flops_per_token": 1e9, "tokens_per_step": 197,
            "chips": 1, "rows_per_chip": 1, "seq_len": 1024,
            "vocab_rows": 50304}, config={"n_embd": 1024, "n_head": 16},
        traffic={}, cell={}, peaks=peaks, values={})

    def read(name):
        return harness.load_module(manifest, "layer_metrics", name).read(ctx)

    assert read("step_device_ms") == pytest.approx(0.095)
    ctx.values["step_device_ms"] = 0.095
    # 197 GFLOP in 95 us on a 197 TFLOP/s chip
    assert read("step_mfu_pct") == pytest.approx(100 * 1e-3 / 95e-6)
    assert read("exposed_collective_pct") == pytest.approx(20.0)
    assert read("train_device_idle_pct") == pytest.approx(100 * 110 / 300)
    # two forward calls over one 1024-token sequence of 16 heads of 64
    ops = 2 * (2 * 16 * 1024 * 1024 * 64) / 2
    assert read("flash_roofline") == pytest.approx(
        100 * 2 * (ops / 197e12) / 80e-6)
    assert read("xent_roofline") is None  # nothing to read: left out
