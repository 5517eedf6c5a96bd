"""``benchmarks/scopes.py`` and its six readers on a small hand-written XSpace
laid out as the v5e's profiler lays one out (``tf_op`` in the event metadata,
looked at by hand in PR 24), with the path forms the train step compiled for
a described v5e (and for XLA:CPU, ``tests/test_device_scopes.py``) writes:
the group and phase of each form, the groups summing to the operations'
total, an enclosing ``while`` counted once, and what a program without scopes
(the commit before them) gives: nothing."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, scopes, trace  # noqa: E402

US = 1_000_000  # picoseconds in a microsecond

# (tf_op, group, phase): the path forms.
FORMS = [
    ("jit(step)/jvp(residual_7)/main/dense_1/dot_general", "mlp", "forward"),
    ("jit(step)/transpose(jvp(residual_7))/main/dense_1/transpose", "mlp",
     "backward"),
    ("jit(step)/jvp(residual_5)/main/moe/Gecd,edh->Gech/dot_general", "mlp",
     "forward"),
    # a rematerialised block: recomputed in, and counted with, the backward
    ("jit(step)/transpose(jvp(residual_3))/jvp(residual_3)/checkpoint/"
     "rematted_computation/main/dense/dot_general", "mlp", "backward"),
    # a scanned stack (S2's twin)
    ("jit(step)/jvp(scanned_blocks)/while/body/closed_call/blocks/residual_1"
     "/main/dense/dot_general", "mlp", "forward"),
    ("jit(step)/transpose(jvp(scanned_blocks))/while/body/closed_call/blocks"
     "/residual/main/multi_head_attention/bqhd,bkhd->bhqk/dot_general",
     "attention", "backward"),
    # a kernel under an attention scope, as the v5e compile names it
    ("jit(step)/jvp(residual_6)/main/multi_head_attention/"
     "dtpu_flash_fwd_packed/pallas_call", "attention", "forward"),
    ("jit(step)/transpose(jvp(residual_6))/main/multi_head_attention/"
     "dtpu_flash_dkv_packed/pallas_call", "attention", "backward"),
    ("jit(step)/jvp(residual_6)/main/multi_head_attention/jit(_where)/"
     "select_n", "attention", "forward"),
    # under FSDP the kernels run in a shard_map (seen in the fsdp4 trace)
    ("jit(step)/transpose(jvp(residual_6))/main/multi_head_attention/"
     "shard_map/reshape", "attention", "backward"),
    # the head and its loss; the chunked head runs in a checkpointed scan
    ("jit(step)/jvp(dense)/dot_general", "head_loss", "forward"),
    ("jit(step)/transpose(jvp(loss))/dtpu_xent_bwd/pallas_call", "head_loss",
     "backward"),
    ("jit(step)/jvp(loss)/jit(log_softmax)/reduce_max", "head_loss",
     "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/dense/dot_general", "head_loss", "backward"),
    ("jit(step)/optimizer/sub", "optimizer", "neither"),
    ("jit(step)/optimizer/jit(_where)/select_n", "optimizer", "neither"),
    ("jit(step)/jvp(cast)/convert_element_type", "cast", "forward"),
    ("jit(step)/transpose(jvp(cast))/convert_element_type", "cast",
     "backward"),
    ("jit(step)/jvp(residual_6)/main/layer_norm/mul", "other", "forward"),
    ("jit(step)/jvp(residual_6)/add", "other", "forward"),
    ("jit(step)/transpose(jvp(embedding))/jit(_take)/scatter-add", "other",
     "backward"),
    ("jit(step)/metrics/eq", "other", "neither"),
    # merged operations: the first name stands
    ("jit(step)/transpose(jvp(loss))/broadcast_in_dim;jit(step)/optimizer/"
     "mul", "head_loss", "backward"),
    # no scope of the program: a collective the partitioner put in, a
    # scale outside every scope, an event with no tf_op at all
    ("jit(step)/jvp()/all_gather", "unattributed", "forward"),
    ("jit(step)/mul", "unattributed", "neither"),
    ("", "unattributed", "neither"),
]


@pytest.mark.parametrize("tf_op,group,phase", FORMS)
def test_group_and_phase_of_a_path(tf_op, group, phase):
    found, got_phase = scopes.scope_of(tf_op + ":" if tf_op else "")
    assert (scopes.group_of(found), got_phase) == (group, phase)


def test_a_layers_scopes_are_its_parameter_path():
    assert scopes.scope_of(
        "jit(step)/transpose(jvp(residual_7))/main/dense_1/transpose:")[0] == [
            "residual_7", "main", "dense_1"]


S = "jit(step)/"
BODY = S + "jvp(scanned_blocks)/while/body/closed_call/blocks/"
FUSION = "%fusion.{i} = f32[4]{0} fusion(f32[4]{0} %all-reduce.5)"  # i: its row
# Device 0, microseconds: (HLO line, start, duration, tf_op). Program run A
# [0, 100): fourteen operations end to end. Run B [200, 300): a while [200,
# 260) around two operations of its body, then the optimizer [260, 300).
RUN_A = [  # duration, tf_op                           group, phase
    ("%dtpu_flash_fwd_packed.24 = (bf16[8,1024,1024]{2,1,0}) custom-call("
     "bf16[8,1024,1024]{2,1,0} %convolution_add_fusion.168)", 10,
     S + "jvp(residual_2)/main/multi_head_attention/dtpu_flash_fwd_packed/"
     "pallas_call"),                                   # attention forward
    (FUSION, 10, S + "transpose(jvp(residual_2))/main/multi_head_attention/"
     "dot_general"),                                   # attention backward
    (FUSION, 12, S + "jvp(residual_3)/main/dense_1/dot_general"),
    (FUSION, 8, S + "transpose(jvp(residual_3))/jvp(residual_3)/checkpoint/"
     "rematted_computation/main/dense/dot_general"),   # mlp backward
    (FUSION, 7, S + "jvp(dense)/dot_general"),         # head_loss forward
    ("%dtpu_xent_bwd.1 = bf16[8192,50304]{1,0} custom-call(bf16[8192,50304]"
     "{1,0} %p)", 3, S + "transpose(jvp(loss))/dtpu_xent_bwd/pallas_call"),
    (FUSION, 15, S + "optimizer/sub"),                 # optimizer
    ("%convert_element_type.3 = bf16[4]{0} convert(f32[4]{0} %p)", 5,
     S + "jvp(cast)/convert_element_type"),            # cast forward
    ("%convert_element_type.4 = f32[4]{0} convert(bf16[4]{0} %p)", 4,
     S + "transpose(jvp(cast))/convert_element_type"),
    (FUSION, 6, S + "jvp(residual_2)/main/layer_norm/mul"),  # other forward
    (FUSION, 4, S + "metrics/eq"),                     # other neither
    # a collective the partitioner put in, under no scope of the program
    ("%all-gather.2 = f32[4]{0} all-gather(f32[1]{0} %p)", 6,
     S + "jvp()/all_gather"),                          # unattributed
    # a collective under a layer's scope counts in the layer's group
    ("%all-reduce.1127 = bf16[4]{0} all-reduce(bf16[4]{0} %pad.2)", 5,
     S + "transpose(jvp(residual_3))/main/dense/dot_general"),
    ("%copy-done.9 = f32[4]{0} copy-done(%copy-start.9)", 5, None),
]
RUN_B = [  # start, duration
    ("%while.3 = (f32[4]{0}) while(%tuple.1)", 200, 60,
     S + "jvp(scanned_blocks)/while"),
    (FUSION, 205, 20, BODY + "residual/main/multi_head_attention/dot_general"),
    (FUSION, 225, 30, BODY + "residual_1/main/moe/dot_general"),
    (FUSION, 260, 40, S + "optimizer/add"),
]
# The same device as the commit before the scopes traces it: kernels are
# named, and nothing of the program's layers or phases is.
BEFORE = [
    ("%jvp_dtpu_flash_fwd_packed_.24 = (bf16[8]{0}) custom-call(bf16[8]{0} "
     "%p)", 0, 40, S + "jvp(dtpu_flash_fwd_packed)/pallas_call"),
    (FUSION, 40, 30, S + "jvp()/bqhd,bkhd->bhqk/dot_general"),
    (FUSION, 70, 20, S + "mul"), (FUSION, 90, 10, None),
]


def xspace(ops, modules):
    """A device plane as the v5e's profiler writes it: one event metadata
    per HLO instruction, named by the whole HLO line and holding ``tf_op``
    (the path and a colon) beside other statistics; the events hold times
    only. The second ``tf_op`` is written as a reference to an interned
    string, the form the format also allows."""
    events, metas = [], []
    stat_metas = ['stat_metadata { key: 1 value { id: 1 name: "tf_op" } }',
                  'stat_metadata { key: 2 value { id: 2 name: '
                  '"hlo_category" } }']
    for i, (line, start, dur, tf_op) in enumerate(ops, start=1):
        events.append(f"events {{ metadata_id: {i} offset_ps: {start * US} "
                      f"duration_ps: {dur * US} }}")
        stats = 'stats { metadata_id: 2 str_value: "fusion" }'
        if tf_op and i == 2:
            stat_metas.append(f'stat_metadata {{ key: 50 value {{ id: 50 '
                              f'name: "{tf_op}:" }} }}')
            stats += " stats { metadata_id: 1 ref_value: 50 }"
        elif tf_op:
            stats += f' stats {{ metadata_id: 1 str_value: "{tf_op}:" }}'
        name = line.replace("{i}", str(i))
        metas.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{name}" {stats} }} }}')
    runs = " ".join(
        f"events {{ metadata_id: 900 offset_ps: {s * US} duration_ps: "
        f"{d * US} }}" for s, d in modules)
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {runs}
    events {{ metadata_id: 901 offset_ps: {150 * US} duration_ps: {US} }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {" ".join(events)} }}
  {" ".join(metas)}
  event_metadata {{ key: 900 value {{ id: 900 name: "jit_step(123)" }} }}
  event_metadata {{ key: 901 value {{ id: 901 name: "jit_tiny(5)" }} }}
  {" ".join(stat_metas)}
}}
planes {{ id: 2 name: "/host:CPU" }}
"""


def _scoped_ops():
    ops, t = [], 0
    for line, dur, tf_op in RUN_A:
        ops.append((line, t, dur, tf_op))
        t += dur
    assert t == 100
    return ops + RUN_B


CELL = "gpt2-medium.train.1chip"


def record(root, ops, modules):
    """Write the XSpace where a traced run of ``CELL`` leaves its file
    under ``root``; return (path, parsed trace)."""
    from jax.profiler import ProfileData

    path = root / CELL / "plugins" / "profile" / "x" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        xspace(ops, modules)))
    return str(path), trace.load(str(path))


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    root = tmp_path_factory.mktemp("scoped")
    path, parsed = record(root, _scoped_ops(), [(0, 100), (200, 100)])
    return types.SimpleNamespace(root=str(root), path=path, trace=parsed)


def us(table):
    return {k: round(v * 1e6, 6) for k, v in table.items()}


def test_op_names_reads_the_metadata_statistic(scoped):
    names = scopes.op_names(scoped.path)
    assert len(names) == len(RUN_A) - 1 + len(RUN_B)  # one has no tf_op
    assert names["%convert_element_type.3 = bf16[4]{0} convert(f32[4]{0} "
                 "%p)"] == S + "jvp(cast)/convert_element_type:"
    # the interned form
    assert names[FUSION.replace("{i}", "2")].endswith(
        "multi_head_attention/dot_general:")
    assert scopes.op_names(scoped.path, ordinal=3) == {}
    # ProfileData itself hands out none of it: why this reader exists
    assert all(e.text == e.name for e in trace.device(scoped.trace).ops)


def test_each_step_by_group_and_phase(scoped):
    tables = [t for t, _ in scopes.steps(
        scoped.trace, scopes.op_names(scoped.path))]
    a, b = (us(t) for t in tables)
    assert a == {
        ("attention", "forward"): 10, ("attention", "backward"): 10,
        ("mlp", "forward"): 12, ("mlp", "backward"): 8 + 5,  # + all-reduce
        ("head_loss", "forward"): 7, ("head_loss", "backward"): 3,
        ("optimizer", "neither"): 15,
        ("cast", "forward"): 5, ("cast", "backward"): 4,
        ("other", "forward"): 6, ("other", "neither"): 4,
        ("unattributed", "forward"): 6, ("unattributed", "neither"): 5}
    # the while counts what its body's operations do not cover
    assert b == {
        ("attention", "forward"): 20, ("mlp", "forward"): 30,
        ("other", "forward"): 60 - 50, ("optimizer", "neither"): 40}


def test_the_groups_sum_to_the_operations_total(scoped):
    dev = trace.device(scoped.trace)
    busy = trace.run_busy_seconds(dev, trace.module_runs(dev))
    tables = [t for t, _ in scopes.steps(
        scoped.trace, scopes.op_names(scoped.path))]
    for table, step_busy in zip(tables, busy):
        assert sum(table.values()) == pytest.approx(step_busy)
        assert sum(scopes.group_seconds(table, g)
                   for g in scopes.GROUPS) == pytest.approx(step_busy)


def context(parsed):
    return harness.LayerContext(
        trace=parsed, telemetry={}, config={}, traffic={},
        cell={"name": CELL}, peaks=None, values={})


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


# Medians over the two steps (of two values, their mean), milliseconds.
READINGS = {
    "attn_device_ms": (20 + 20) / 2 * 1e-3,
    "mlp_device_ms": (25 + 30) / 2 * 1e-3,
    "head_loss_device_ms": (10 + 0) / 2 * 1e-3,
    "optimizer_device_ms": (15 + 40) / 2 * 1e-3,
    "cast_device_ms": (9 + 0) / 2 * 1e-3,
    "scope_unattributed_pct": (11.0 + 0.0) / 2,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_on_the_synthetic_trace(scoped, monkeypatch, name):
    monkeypatch.setattr(scopes, "TRACE_ROOT", scoped.root)
    ctx = context(trace.load(scoped.path))
    assert reader(name).read(ctx) == pytest.approx(READINGS[name])


@pytest.mark.parametrize("name", sorted(READINGS))
def test_reader_reports_nothing_without_a_trace_or_the_scopes(
        tmp_path, monkeypatch, name):
    monkeypatch.setattr(scopes, "TRACE_ROOT", str(tmp_path))
    assert reader(name).read(context(None)) is None
    _, before = record(tmp_path, BEFORE, [(0, 100)])
    assert trace.kernel_names(before) == ["dtpu_flash_fwd_packed"]
    assert reader(name).read(context(before)) is None


def test_the_table_as_printed(scoped):
    text = scopes.describe(scoped.path)
    assert "2 steps" in text
    row = [r for r in text.splitlines() if r.startswith("mlp")][0].split()
    assert [float(v) for v in row[1:5]] == pytest.approx(
        [0.021, 0.0065, 0.0, 0.0275], abs=6e-4)  # forward .. neither, all
    assert "copy-done" in text and "all-gather" in text  # unattributed
    assert "cast forward" in text  # the bare converts, by group


# Operation names of the five Mosaic kernels in the steps compiled for a
# described v5e with the scopes in (compile_v5e.py, PR 24): the compiler now
# names the call after the innermost scope, the kernel's own, with and
# without FSDP. trace.kernel_name must keep finding them: expect_kernels and
# the two rooflines read it.
SCOPED_KERNEL_NAMES = {
    "%dtpu_flash_fwd_packed.24": "dtpu_flash_fwd_packed",
    "%dtpu_flash_dq_packed.36": "dtpu_flash_dq_packed",
    "%dtpu_flash_dkv_packed.12": "dtpu_flash_dkv_packed",
    "%dtpu_xent_fwd.1": "dtpu_xent_fwd",
    "%dtpu_xent_bwd.1": "dtpu_xent_bwd",
}


@pytest.mark.parametrize("op", sorted(SCOPED_KERNEL_NAMES))
def test_kernel_name_of_a_scoped_compiled_operation(op):
    line = f"{op} = bf16[8,1024,1024]{{2,1,0}} custom-call(bf16[8] %p)"
    ev = trace.Event(line, 0.0, 1.0, line + " jit(step)/jvp(residual_2)/main"
                     "/multi_head_attention/dtpu_flash_fwd_packed/pallas_call")
    assert trace.kernel_name(ev) == SCOPED_KERNEL_NAMES[op]
    want = {k for mix in ("train-b8-t1024", "train-b16-t1024")
            for k in harness.load_json(os.path.join(
                ROOT, "benchmarks", "traffic", mix + ".json"))[
                    "expect_kernels"]}
    assert any(SCOPED_KERNEL_NAMES[op].startswith(k) for k in want)


# The rehearsal's manifest (tests/bench_harness/rehearsal.json) is a file the
# benchmark already had and is not edited by the PR that adds these metrics,
# so the control flow of run.py over the real manifest is proved here: every
# per-layer metric of both train cells, the six new ones among them, has a
# reader that run.py finds by name and that reports nothing without a trace.
@pytest.mark.parametrize("cell", ["gpt2-medium.train.1chip",
                                  "gpt2-large.train.fsdp4"])
def test_run_py_lists_the_scope_metrics_for_the_train_cells(cell, tmp_path):
    from benchmarks import run

    manifest = harness.load_manifest()
    env = types.SimpleNamespace(
        trace_dir=str(tmp_path), rehearsal=True, config={}, traffic={},
        cell=harness.entry(manifest, "workloads", cell))
    metrics, parsed = run.layer_metrics(env, manifest, cell, {}, "cpu")
    assert parsed is None
    assert set(READINGS) <= set(metrics)
    assert all(metrics[n] == {"value": None, "unit": "%" if n.endswith("pct")
                              else "ms"} for n in READINGS)
    for n in READINGS:
        entry = harness.entry(manifest, "per_layer", n)
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["better"] == "lower"
        assert entry["workloads"] == ["gpt2-medium.train.1chip",
                                      "gpt2-large.train.fsdp4"]
