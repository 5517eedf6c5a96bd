"""The LFM2-MoE family's benchmark files: a rehearsal of
``drivers/train_family_tied.py`` on the tiny configuration (its manifest is
``rehearsal-lfm2.json``; ``run.py --rehearsal`` reads the accepted
``rehearsal.json``, which this PR may not edit, so the child process points
it at the new file, as ``test_bench_kanana.py`` does), the readers of the
three new per-layer metrics on made-up telemetry and a made-up trace, and
the entries of ``BENCHMARK.json``. What is asserted of a metric's
``workloads`` is that this family's cell is among them, never what the whole
list is: the next family edits no fixture for these tests (ROADMAP D17)."""

import json
import math
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, harness, scopes, scopes_conv  # noqa: E402
from benchmarks import trace as trace_lib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal-lfm2.json")
CELL = "lfm2-8b-a1b.train.ep4share"
NEW_METRICS = ("shortconv_device_ms", "shortconv_mix_device_ms",
               "gqa_flash_roofline")
MOE_METRICS = ("moe_route_device_ms", "moe_experts_device_ms",
               "moe_experts_roofline", "moe_held_rows_pct",
               "moe_load_max_over_mean", "moe_buffer_used_pct")
# Every per-layer metric ISSUE 34's point 6 lists for the cell.
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
def test_rehearsal_of_the_tied_driver_prints_a_well_formed_line(trace):
    proc = rehearse("--workload", "lfm2-tiny.train", "--seed", "2147483999",
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
    assert len(checks["flipped_pairs_share"]) == 2
    assert set(checks["grad_differences"]) == {
        "short_conv", "attention", "dense_mlp", "router", "experts", "table",
        "norms"}
    # the first loss is held to ln(rows) plus half the logits' mean square
    assert checks["first_loss_expected"] == pytest.approx(
        math.log(512) + 0.5 * checks["reference_logit_mean_square"])
    assert checks["first_loss_near_ln_vocab"]
    assert checks["layers_built_as_configured"]
    assert checks["layers_built"] == {
        "conv": 2, "attention": 1, "dense": 1, "experts": 2}
    assert checks["params"] > 0 and checks["steps"] > 10
    counted = checks["family"]["moe_counters"]
    assert sorted(counted) == ["residual_3/main/moe", "residual_5/main/moe"]


@pytest.mark.parametrize("others_pass,mean_square,convs_built,want", [
    (True, 0.8, 2, True), (True, 0.0, 2, False), (False, 0.8, 2, False),
    (True, 0.8, 3, False)])
def test_tied_driver_ands_its_checks_to_train_familys_verdict(
        monkeypatch, others_pass, mean_square, convs_built, want):
    """``train_family.run`` runs with its limit on the whole first loss
    lifted, so its verdict is that of its other checks; the limit on the
    first loss less half the logits' mean square is and-ed to it, and so is
    the stack the program's gauges say it built against the one the
    configuration states."""
    from benchmarks.drivers import train_family, train_family_tied
    from distributed_tpu.obs.registry import default_registry

    limit = train_family.FIRST_LOSS_TOL
    seen = {}

    def run(env):
        seen["limit"] = train_family.FIRST_LOSS_TOL
        for kind, n in (("conv", convs_built), ("attention", 1),
                        ("dense", 1), ("experts", 2)):
            default_registry().gauge(f"model.layers_{kind}", n)
        return {"correct": others_pass, "checks": {
            "first_loss": math.log(512) + 0.4 + 0.05,
            "reference_logit_mean_square": mean_square}}

    monkeypatch.setattr(train_family, "run", run)
    env = types.SimpleNamespace(
        config={"layer_types": ["conv", "full_attention", "conv"],
                "num_dense_layers": 1},
        family=types.SimpleNamespace(vocab_rows=lambda cfg: 512))
    result = train_family_tied.run(env)
    assert seen["limit"] == math.inf
    assert train_family.FIRST_LOSS_TOL == limit
    assert result["correct"] is want
    assert result["checks"]["first_loss_near_ln_vocab"] is (
        mean_square == 0.8)
    assert result["checks"]["layers_built_as_configured"] is (
        convs_built == 2)


def ctx_of(telemetry, config=None, trace=None, peaks=None):
    return harness.LayerContext(
        trace=trace, telemetry=telemetry, config=config or {}, traffic={},
        cell={"name": "no-such-cell"}, peaks=peaks, values={})


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_a_trace_reads_as_nothing(name):
    """The parent commit has neither the layer nor its scopes: every new
    reader returns None for it and raises nothing, whatever the
    configuration it is handed."""
    assert reader(name).read(ctx_of({})) is None
    assert reader(name).read(ctx_of({"moe_counters": {}},
                                    config={"n_head": 16})) is None
    trace = types.SimpleNamespace(devices=[])
    assert reader(name).read(ctx_of({}, trace=trace)) is None


def test_scope_split_takes_the_mix_out_of_the_layer():
    conv = ["residual_4", "main", "short_conv"]
    assert scopes_conv._inner(conv) == "short_conv"
    assert scopes_conv._inner(conv + ["mix"]) == "mix"
    assert scopes_conv._inner(["residual_3", "main", "moe", "route"]) is None
    assert scopes_conv._inner(["residual_2", "main", "rms_norm"]) is None
    assert scopes_conv._inner(["dense"]) is None
    # the backward pass of the checkpoint re-enters the name stack, and
    # jnp.pad's own jit is no scope: the accepted rule drops both
    path, phase = scopes.scope_of(
        "jit(step)/transpose(jvp(residual_4))/main/short_conv/mix/"
        "jvp(residual_4)/main/short_conv/mix/checkpoint/"
        "rematted_computation/jit(_pad)/pad")
    assert phase == "backward" and scopes_conv._inner(path) == "mix"
    path, phase = scopes.scope_of(
        "jit(step)/jvp(residual_4)/main/short_conv/dot_general")
    assert phase == "forward" and scopes_conv._inner(path) == "short_conv"
    # the accepted grouping: the layer's time is ``other`` (no attention
    # prefix, no ``dense*`` or ``moe*`` under the block), the tied head's
    # product is the head's
    assert scopes.group_of(conv + ["mix"]) == "other"
    assert scopes.group_of(["dense"]) == "head_loss"
    assert scopes.group_of(
        ["residual_2", "main", "multi_head_attention_gqa"]) == "attention"


def test_scope_readers_sum_the_steps_of_a_made_up_trace(monkeypatch):
    trace = types.SimpleNamespace(devices=[object()], conv_scope_sums=[
        {"short_conv": 0.010, "mix": 0.004},
        {"short_conv": 0.012, "mix": 0.006},
        {"short_conv": 0.011, "mix": 0.005}])
    ctx = ctx_of({}, trace=trace)
    assert reader("shortconv_device_ms").read(ctx) == pytest.approx(16.0)
    assert reader("shortconv_mix_device_ms").read(ctx) == pytest.approx(5.0)


def fake_device(events):
    ops = [trace_lib.Event(name=f"%{n}.{i} = custom-call()", start=float(i),
                           end=float(i) + s) for i, (n, s) in
           enumerate(events)]
    return types.SimpleNamespace(devices=[trace_lib.DeviceTrace(
        ordinal=0, ops=ops, modules=[])])


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_gqa_flash_roofline_counts_the_query_heads_causal_half():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "lfm2-8b-a1b.json"))
    costs = {k: flops.flash_cost(k, 1, 8192, 32, 64)
             for k in ("fwd", "dq", "dkv")}
    half = 2.0 * 32 * 8192 * 8192 * 64 * 0.5
    assert [costs[k][0] for k in ("fwd", "dq", "dkv")] == [
        2 * half, 3 * half, 4 * half]
    least = {k: flops.least_seconds(*c, PEAKS) for k, c in costs.items()}
    assert all(bound == "compute" for _, bound in least.values())
    trace = fake_device([("dtpu_flash_fwd_packed", 4 * least["fwd"][0]),
                         ("dtpu_flash_dq_packed", 4 * least["dq"][0]),
                         ("dtpu_flash_dkv_packed", 4 * least["dkv"][0]),
                         ("dtpu_gmm", 1.0)])
    tel = {"rows_per_chip": 1, "seq_len": 8192}
    got = reader("gqa_flash_roofline").read(
        ctx_of(tel, config=cfg, trace=trace, peaks=PEAKS))
    assert got == pytest.approx(25.0)
    # a trace without the flash kernels, a stack with no attention layer, or
    # another family's configuration: nothing to read
    plain = fake_device([("dtpu_gmm", 1.0)])
    assert reader("gqa_flash_roofline").read(
        ctx_of(tel, config=cfg, trace=plain, peaks=PEAKS)) is None
    convs = dict(cfg, layer_types=["conv"] * 5)
    assert reader("gqa_flash_roofline").read(
        ctx_of(tel, config=convs, trace=trace, peaks=PEAKS)) is None
    assert reader("gqa_flash_roofline").read(
        ctx_of(tel, config={"n_head": 16}, trace=trace, peaks=PEAKS)) is None


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True}
    published = ["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv", "full_attention",
                 "conv", "conv", "conv", "full_attention", "conv", "conv",
                 "conv", "full_attention", "conv", "conv", "full_attention",
                 "conv", "conv"]
    manifest = harness.load_manifest()
    entry = harness.entry(manifest, "configs", "lfm2-8b-a1b")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    for key, value in catalog.items():
        assert cfg[key] == value, key
    assert cfg["source"] == entry["source"]
    assert cfg["family"] == "lfm2_moe"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 24, "num_dense_layers": 2,
        "layer_types": published, "num_experts": 32, "vocab_size": 65536}
    # the cut: published layer 0 and layers 2-5, one whole period
    assert cfg["layer_types"] == published[:1] + published[2:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_dense_layers"] == 1
    assert cfg["assumed"]["vocab_rows_held"] == cfg["vocab_size"] == 16384
    assert cfg["assumed"]["vocab_rows_held"] % 128 == 0
    assert cfg["assumed"]["head_dim"] * cfg["num_attention_heads"] == cfg[
        "hidden_size"]
    assert cfg["assumed"]["tie_word_embeddings"] is True
    assert cfg["assumed"]["router_bias_update_rate"] == 0.001
    assert cfg["assumed"]["lr_warmup_steps"] == 2000
    assert json.dumps(cfg)  # plain data
    assert cfg["deployment"]["chips_per_layer"] == 4
    assert cfg["deployment"]["router_experts"] == 32
    # the guide's floors: a whole period and four layers after the dense
    # ones, 8 experts, an eighth of the rows; no width among the reduced keys
    assert len(cfg["layer_types"]) - cfg["num_dense_layers"] >= 4
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= 65536
    assert all(len(v) <= 200 for v in (entry["why"], entry["source"]))
    # every entry of ``assumed`` that is a choice says where it comes from
    for key in ("head_dim", "vocab_rows", "tie_word_embeddings",
                "reference_q_block"):
        assert len(cfg["assumed"][key + "_why"]) > 40, key


def test_the_cell_reports_what_issue_34_lists():
    manifest = harness.load_manifest()
    cell = harness.entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == "lfm2-8b-a1b"
    assert cell["traffic"] == "train-b1-t8192-z05-conv"
    assert len(cell["why"]) <= 200
    traffic = harness.load_json(harness.find_file(
        manifest, "traffic", cell["traffic"]))
    assert (traffic["global_batch"], traffic["seq_len"],
            traffic["zipf_exponent"], traffic["distinct_batches"],
            traffic["learning_rate"]) == (1, 8192, 0.5, 64, 1e-4)
    assert traffic["driver"] == "train_family_tied"
    assert traffic["strategy"] == "SingleDevice"
    assert traffic["loss"] == "pallas_sparse_categorical_crossentropy"
    assert traffic["expect_kernels"] == [
        "dtpu_flash_fwd", "dtpu_flash_dq", "dtpu_flash_dkv", "dtpu_gmm",
        "dtpu_moe_rows_gather", "dtpu_moe_rows_sum", "dtpu_xent_fwd",
        "dtpu_xent_bwd"]
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in harness.metrics_of(manifest, s, CELL)}
    assert set(LISTED) | {"train_tokens_per_s", "setup_s"} == listed
    # GPT-2's, kanana's and Keye's own readers find nothing here
    assert {"flash_roofline", "mla_flash_roofline", "dsa_flash_roofline",
            "dsa_index_device_ms", "exposed_collective_pct"}.isdisjoint(
        listed)
    for name in NEW_METRICS:
        entry = harness.entry(manifest, "per_layer", name)
        assert CELL in entry["workloads"]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["source"] == "device_trace"
    for name in MOE_METRICS:  # the new cell is among them, wherever
        assert CELL in harness.entry(manifest, "per_layer", name)[
            "workloads"]
    assert CELL in harness.entry(
        manifest, "end_to_end", "train_tokens_per_s")["workloads"]
    # the four-chip cells stay within a quarter of the cells, rounded down,
    # or the one that always may
    fours = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert fours <= max(1, len(manifest["workloads"]) // 4)


def test_run_py_lists_the_metrics_for_the_lfm2_cell(tmp_path):
    from benchmarks import run

    manifest = harness.load_manifest()
    env = types.SimpleNamespace(
        trace_dir=str(tmp_path), rehearsal=True, config={}, traffic={},
        cell=harness.entry(manifest, "workloads", CELL))
    metrics, parsed = run.layer_metrics(env, manifest, CELL, {}, "cpu")
    assert parsed is None
    assert set(metrics) == set(LISTED)
    for name in ("attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "cast_device_ms",
                 "scope_unattributed_pct") + NEW_METRICS[:2]:
        assert metrics[name] == {
            "value": None, "unit": "%" if name.endswith("pct") else "ms"}
