"""The Laguna family's benchmark files: a rehearsal of
``drivers/train_family.py`` on the tiny configuration (its manifest is
``rehearsal-laguna.json``; ``run.py --rehearsal`` reads the accepted
``rehearsal.json``, which this PR may not edit, so the child process points
it at the new file, as ``test_bench_kanana.py`` does), the readers of the six
new per-layer metrics on made-up telemetry and a made-up trace, and the
entries of ``BENCHMARK.json``. What is asserted of a metric's ``workloads``
is that this family's cell is among them, never what the whole list is: the
next family edits no fixture for these tests (ROADMAP D17)."""

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

from benchmarks import flops, flops_laguna, harness, scopes  # noqa: E402
from benchmarks import scopes_swa, trace as trace_lib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal-laguna.json")
CELL = "laguna-xs2.train.swa8k"
NEW_METRICS = ("swa_flash_roofline", "gqa128_flash_roofline",
               "swa_attn_device_ms", "attn_gate_device_ms",
               "swa_window_pairs_pct", "swa_walked_pairs_pct")
MOE_METRICS = ("moe_route_device_ms", "moe_experts_device_ms",
               "moe_experts_roofline", "moe_held_rows_pct",
               "moe_load_max_over_mean", "moe_buffer_used_pct")
# Every per-layer metric ISSUE 38's point 6 lists for the cell, and those
# that list no cell at all.
LISTED = NEW_METRICS + MOE_METRICS + (
    "input_wait_pct", "step_device_ms", "step_mfu_pct", "xent_roofline",
    "train_device_idle_pct", "setup_compile_s", "attn_device_ms",
    "mlp_device_ms", "head_loss_device_ms", "optimizer_device_ms",
    "cast_device_ms", "scope_unattributed_pct")
CHILD = ("import sys; sys.path.insert(0, {root!r}); "
         "from benchmarks import run; run.REHEARSAL_MANIFEST = {manifest!r}; "
         "sys.exit(run.main(sys.argv[1:]))")


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=1 "
                        "--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = CHILD.format(root=ROOT, manifest=MANIFEST)
    return subprocess.run([sys.executable, "-c", code, *args], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900, preexec_fn=lambda: os.nice(15))


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_family_driver_prints_a_well_formed_line(trace):
    proc = rehearse("--workload", "laguna-tiny.train", "--seed", "2147483999",
                    "--seconds", "1", "--trace", str(trace), "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    manifest = harness.load_json(MANIFEST)
    section = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {m["name"] for m in manifest[section]}
    if trace:  # the rehearsal lists every metric of the issue's point 6
        assert set(line["metrics"]) == set(LISTED)
    assert all(m["value"] is None and m["unit"]
               for m in line["metrics"].values())
    checks = [json.loads(l) for l in proc.stderr.splitlines()
              if l.startswith('{"event": "checks"')][0]
    assert checks["loss_agrees"] and checks["grad_norm_agrees"]
    assert checks["routing_agrees"] and checks["grad_differences_agree"]
    assert checks["first_loss_near_ln_vocab"]
    assert len(checks["flipped_pairs_share"]) == 2
    assert set(checks["grad_differences"]) == {
        "full_attention", "sliding_attention", "dense_mlp", "router",
        "shared", "experts", "other"}
    assert checks["params"] > 0 and checks["steps"] > 10
    family = checks["family"]
    assert sorted(family["moe_counters"]) == [
        "residual_3/main/moe", "residual_5/main/moe"]
    assert sorted(family["window_counters"]) == [
        "residual_2/main/multi_head_attention_swa",
        "residual_4/main/multi_head_attention_swa"]
    for c in family["window_counters"].values():
        assert c["steps"] == checks["steps"]
        assert c["window_pairs"] / c["causal_pairs"] == pytest.approx(
            flops_laguna.window_pairs(64, 16) / flops_laguna.causal_pairs(64))


def test_a_program_without_the_builder_fails_at_once(monkeypatch):
    """The parent commit has no ``models.laguna_lm``: the family says so
    with the harness's own error (exit code 2, before any device work)."""
    import distributed_tpu as dtpu

    fam = harness.load_module(harness.load_manifest(), "families", "laguna")
    monkeypatch.delattr(dtpu.models, "laguna_lm")
    with pytest.raises(harness.BenchmarkError, match="laguna_lm"):
        fam.build_module({"name": "laguna-xs.2"})


def ctx_of(telemetry, config=None, trace=None, peaks=None):
    return harness.LayerContext(
        trace=trace, telemetry=telemetry, config=config or {}, traffic={},
        cell={"name": "no-such-cell"}, peaks=peaks, values={})


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_a_trace_reads_as_nothing(name):
    """The parent commit has neither the window nor its scopes and
    counters: every new reader returns None for it and raises nothing,
    whatever the configuration it is handed."""
    assert reader(name).read(ctx_of({})) is None
    assert reader(name).read(ctx_of({"moe_counters": {}},
                                    config={"n_head": 16})) is None
    assert reader(name).read(ctx_of({"window_counters": {}})) is None
    trace = types.SimpleNamespace(devices=[])
    assert reader(name).read(ctx_of({}, trace=trace)) is None
    # another family's trace and scopes: nothing of this one's to read
    other = fake_device([("dtpu_flash_fwd", 1.0), ("dtpu_gmm", 1.0)])
    other.swa_scope_sums = []
    lfm2 = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "lfm2-8b-a1b.json"))
    assert reader(name).read(ctx_of(
        {"rows_per_chip": 1, "seq_len": 8192}, config=lfm2, trace=other,
        peaks=PEAKS)) is None


def test_scope_split_reads_the_sliding_layers_and_every_gate():
    swa = ["residual_4", "main", "multi_head_attention_swa"]
    full = ["residual_8", "main", "multi_head_attention_gqa"]
    assert scopes_swa._kinds(swa) == ["swa"]
    assert scopes_swa._kinds(swa + ["gate"]) == ["swa", "gate"]
    assert scopes_swa._kinds(full) == []
    assert scopes_swa._kinds(full + ["gate"]) == ["gate"]
    assert scopes_swa._kinds(swa + ["q_norm"]) == ["swa"]
    # a gated MLP's ``gate``-less scopes, an expert layer, the head
    assert scopes_swa._kinds(["residual_3", "main", "moe", "route"]) == []
    assert scopes_swa._kinds(["residual_1", "main", "gated_mlp",
                              "dense"]) == []
    assert scopes_swa._kinds(["dense"]) == []
    # as the profiler writes them: forward, and the backward of a block
    path, phase = scopes.scope_of(
        "jit(step)/jvp(residual_4)/main/multi_head_attention_swa/gate/"
        "logistic")
    assert phase == "forward" and scopes_swa._kinds(path) == ["swa", "gate"]
    path, phase = scopes.scope_of(
        "jit(step)/transpose(jvp(residual_8))/main/multi_head_attention_gqa/"
        "gate/mul")
    assert phase == "backward" and scopes_swa._kinds(path) == ["gate"]
    path, _ = scopes.scope_of(
        "jit(step)/jvp(residual_2)/main/multi_head_attention_swa/"
        "jit(flash_fwd)/dtpu_flash_fwd_swa/pallas_call")
    assert scopes_swa._kinds(path) == ["swa"]
    # the accepted grouping: both kinds are ``attention``
    assert scopes.group_of(swa + ["gate"]) == "attention"
    assert scopes.group_of(full) == "attention"


def test_scope_readers_take_the_median_step_of_a_made_up_trace():
    trace = types.SimpleNamespace(devices=[object()], swa_scope_sums=[
        {"swa": 0.100, "gate": 0.010}, {"swa": 0.120, "gate": 0.014},
        {"swa": 0.110, "gate": 0.012}])
    ctx = ctx_of({}, trace=trace)
    assert reader("swa_attn_device_ms").read(ctx) == pytest.approx(110.0)
    assert reader("attn_gate_device_ms").read(ctx) == pytest.approx(12.0)
    # gates and no sliding layer (a stack of full layers): one reads
    gated = types.SimpleNamespace(devices=[object()], swa_scope_sums=[
        {"gate": 0.010}, {"gate": 0.012}])
    assert reader("swa_attn_device_ms").read(ctx_of({}, trace=gated)) is None
    assert reader("attn_gate_device_ms").read(
        ctx_of({}, trace=gated)) == pytest.approx(11.0)


def test_counter_readers_sum_the_layers():
    layer = {"steps": 100.0, "queries": 819200.0,
             "causal_pairs": 100.0 * 33_558_528,
             "window_pairs": 100.0 * 4_063_488,
             "walked_pairs": 100.0 * 310 * 128 * 128}
    tel = {"window_counters": {"residual_2/main/multi_head_attention_swa":
                               layer,
                               "residual_4/main/multi_head_attention_swa":
                               layer}}
    assert reader("swa_window_pairs_pct").read(ctx_of(tel)) == pytest.approx(
        12.1087, abs=1e-3)
    assert reader("swa_walked_pairs_pct").read(ctx_of(tel)) == pytest.approx(
        15.1347, abs=1e-3)
    # the dense path walks no sub-tile: the window's share is still read
    dense = {"window_counters": {"a": dict(layer, walked_pairs=0.0)}}
    assert reader("swa_walked_pairs_pct").read(ctx_of(dense)) is None
    assert reader("swa_window_pairs_pct").read(ctx_of(dense)) is not None


def fake_device(events):
    ops = [trace_lib.Event(name=f"%{n}.{i} = custom-call()", start=float(i),
                           end=float(i) + s) for i, (n, s) in
           enumerate(events)]
    return types.SimpleNamespace(devices=[trace_lib.DeviceTrace(
        ordinal=0, ops=ops, modules=[])])


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_two_rooflines_read_their_own_kernels_and_pairs():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "laguna-xs.2.json"))
    tel = {"rows_per_chip": 1, "seq_len": 8192}
    inside = flops_laguna.window_pairs(8192, 512)
    causal = flops_laguna.causal_pairs(8192)
    swa = {k: flops_laguna.gqa_flash_cost(k, 1, inside, 8192, 64, 8, 128)
           for k in ("fwd", "dq", "dkv")}
    full = {k: flops_laguna.gqa_flash_cost(k, 1, causal, 8192, 48, 8, 128)
            for k in ("fwd", "dq", "dkv")}
    assert [swa[k][0] for k in ("fwd", "dq", "dkv")] == [
        n * 2.0 * inside * 64 * 128 for n in (2, 3, 4)]
    # the plain count on the causal half, within a row's diagonal pair
    assert full["fwd"][0] == pytest.approx(
        flops.flash_cost("fwd", 1, 8192, 48, 128)[0], rel=2e-4)
    least = lambda costs: {k: flops.least_seconds(*c, PEAKS)
                           for k, c in costs.items()}
    assert all(b == "compute" for _, b in least(full).values())
    trace = fake_device(
        [(f"dtpu_flash_{k}_swa", 2 * least(swa)[k][0])
         for k in ("fwd", "dq", "dkv")] * 3
        + [(f"dtpu_flash_{k}_packed", 4 * least(full)[k][0])
           for k in ("fwd", "dq", "dkv")] * 2 + [("dtpu_gmm", 1.0)])
    ctx = ctx_of(tel, config=cfg, trace=trace, peaks=PEAKS)
    assert reader("swa_flash_roofline").read(ctx) == pytest.approx(50.0)
    assert reader("gqa128_flash_roofline").read(ctx) == pytest.approx(25.0)
    # a walk over the whole triangle at the plain kernels' rate would read
    # window over causal pairs of it
    assert 100.0 * inside / causal == pytest.approx(12.109, abs=1e-3)
    # a trace with neither kind, and a stack with no sliding layer
    plain = fake_device([("dtpu_gmm", 1.0)])
    for name in ("swa_flash_roofline", "gqa128_flash_roofline"):
        assert reader(name).read(
            ctx_of(tel, config=cfg, trace=plain, peaks=PEAKS)) is None
    no_swa = dict(cfg, layer_types=["full_attention"] * 5)
    assert reader("swa_flash_roofline").read(
        ctx_of(tel, config=no_swa, trace=trace, peaks=PEAKS)) is None


def test_the_configuration_keeps_every_published_number():
    catalog = None
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(guide):
        with open(guide) as f:
            rows = [json.loads(line) for line in f]
        catalog = next(r for r in rows if r["name"] == "Laguna-XS.2")
    manifest = harness.load_manifest()
    entry = harness.entry(manifest, "configs", "laguna-xs.2")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert cfg["reduced"] == entry["reduced"] == reduced
    widths = {"hidden_size": 2048, "intermediate_size": 8192,
              "num_attention_heads": 48, "num_key_value_heads": 8,
              "head_dim": 128, "num_experts_per_tok": 8,
              "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512, "sliding_window": 512,
              "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
              "partial_rotary_factor": 0.5, "gating": True,
              "tie_word_embeddings": False, "attention_bias": False,
              "max_position_embeddings": 262144, "model_type": "laguna"}
    for key, value in widths.items():
        assert cfg[key] == value, key
    if catalog is not None:  # every key of the catalog's config, the cut
        assert entry["source"] == cfg["source"] == catalog["source_url"]
        for key, value in catalog["config"].items():
            if key in reduced:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["rope_theta"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["partial_rotary_factor"]) == (
        "yarn", 64, 500000, 4096, 64, 1, 0.5)
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert cfg["family"] == "laguna"
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (40, 256, 100352)
    # the cut: published layers 0-4, the dense layer once and one period
    assert cfg["layer_types"] == pub["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert cfg["mlp_layer_types"] == pub["mlp_layer_types"][:5] == [
        "dense", "sparse", "sparse", "sparse", "sparse"]
    assert cfg["num_attention_heads_per_layer"] == pub[
        "num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert cfg["num_hidden_layers"] == 5
    # the guide's floors: a whole period and four layers after the dense
    # one, 8 experts, an eighth of the rows; no width among the reduced keys
    assert pub["layer_types"][1:5] == pub["layer_types"][5:9]
    assert cfg["mlp_layer_types"].count("sparse") >= 4
    assert cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 8 == 100352 and cfg["vocab_size"] % 128 == 0
    assert cfg["assumed"]["vocab_rows_held"] == cfg["vocab_size"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in reduced)
    deployment = cfg["deployment"]
    assert deployment["chips_per_layer"] == 32
    assert deployment["router_experts"] == 256
    assert deployment["expert_offset"] == 0
    assumed = cfg["assumed"]
    assert assumed["router_bias_update_rate"] == 0.0
    assert assumed["lr_warmup_steps"] == 2000
    assert assumed["gate"] == "per-head"
    assert "WEAKEST" in assumed["qk_norm"]
    # every assumption that is a choice says where it comes from
    for key in ("gate_why", "router", "router_bias", "qk_norm", "rope",
                "lr_warmup", "reference_q_block_why", "init",
                "vocab_rows_why"):
        assert len(assumed[key]) > 40, key
    assert json.dumps(cfg)  # plain data
    assert all(len(v) <= 200 for v in (entry["why"], entry["source"]))


def test_the_cell_reports_what_issue_38_lists():
    manifest = harness.load_manifest()
    cell = harness.entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == "laguna-xs.2"
    assert cell["traffic"] == "train-b1-t8192-z05-swa"
    assert len(cell["why"]) <= 200
    traffic = harness.load_json(harness.find_file(
        manifest, "traffic", cell["traffic"]))
    assert (traffic["global_batch"], traffic["seq_len"],
            traffic["zipf_exponent"], traffic["distinct_batches"],
            traffic["learning_rate"]) == (1, 8192, 0.5, 64, 1e-4)
    assert traffic["driver"] == "train_family"
    assert traffic["strategy"] == "SingleDevice"
    assert traffic["loss"] == "pallas_sparse_categorical_crossentropy"
    # the windowed and the plain flash kernels, by their own names
    for kernel in ("fwd", "dq", "dkv"):
        assert f"dtpu_flash_{kernel}_swa" in traffic["expect_kernels"]
        assert f"dtpu_flash_{kernel}_packed" in traffic["expect_kernels"]
    assert {"dtpu_gmm", "dtpu_moe_rows_gather", "dtpu_moe_rows_sum",
            "dtpu_xent_fwd", "dtpu_xent_bwd"} <= set(
                traffic["expect_kernels"])
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in harness.metrics_of(manifest, s, CELL)}
    assert set(LISTED) | {"train_tokens_per_s", "setup_s"} <= listed
    # the other families' own readers find nothing here
    assert {"flash_roofline", "mla_flash_roofline", "dsa_flash_roofline",
            "dsa_index_device_ms", "exposed_collective_pct",
            "gqa_flash_roofline", "shortconv_device_ms"}.isdisjoint(listed)
    for name in NEW_METRICS:
        entry = harness.entry(manifest, "per_layer", name)
        assert CELL in entry["workloads"]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["source"] == ("program_counter" if name.endswith(
            "pairs_pct") else "device_trace")
        assert entry["unit"] == ("ms" if name.endswith("_ms") else "%")
    for name in MOE_METRICS + ("xent_roofline",):
        assert CELL in harness.entry(manifest, "per_layer", name)[
            "workloads"]
    assert CELL in harness.entry(
        manifest, "end_to_end", "train_tokens_per_s")["workloads"]
    # the four-chip cells stay within a quarter of the cells, rounded down,
    # or the one that always may
    fours = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert fours <= max(1, len(manifest["workloads"]) // 4)


def test_run_py_lists_the_metrics_for_the_laguna_cell(tmp_path):
    from benchmarks import run

    manifest = harness.load_manifest()
    env = types.SimpleNamespace(
        trace_dir=str(tmp_path), rehearsal=True, config={}, traffic={},
        cell=harness.entry(manifest, "workloads", CELL))
    metrics, parsed = run.layer_metrics(env, manifest, CELL, {}, "cpu")
    assert parsed is None
    assert set(LISTED) <= set(metrics)
    for name in NEW_METRICS:
        assert metrics[name] == {
            "value": None, "unit": "ms" if name.endswith("_ms") else "%"}
