"""The DeepSeek-V3 family's benchmark files: a rehearsal of
``drivers/train_family.py`` on the tiny configuration (its manifest is
``rehearsal-kanana.json``; ``run.py --rehearsal`` reads the accepted
``rehearsal.json``, which this PR may not edit, so the child process points
it at the new file), the readers of the six new per-layer metrics on made-up
telemetry and a made-up trace, the operation counts, and the entries of
``BENCHMARK.json``."""

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

from benchmarks import flops, flops_deepseek_v3, harness, scopes  # noqa: E402
from benchmarks import scopes_moe, trace as trace_lib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal-kanana.json")
CELL = "kanana2-30b.train.ep8share"
NEW_METRICS = ("moe_route_device_ms", "moe_experts_device_ms",
               "moe_experts_roofline", "mla_flash_roofline",
               "moe_held_rows_pct", "moe_load_max_over_mean")
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
    proc = rehearse("--workload", "kanana-tiny.train", "--seed", "2147483999",
                    "--seconds", "1", "--trace", str(trace), "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    manifest = harness.load_json(MANIFEST)
    section = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {m["name"] for m in manifest[section]}
    assert all(m["value"] is None and m["unit"]
               for m in line["metrics"].values())
    checks = [json.loads(l) for l in proc.stderr.splitlines()
              if l.startswith('{"event": "checks"')][0]
    assert checks["loss_agrees"] and checks["grad_norm_agrees"]
    assert checks["params"] > 0 and checks["steps"] > 10


def ctx_of(telemetry, config=None, trace=None, peaks=None):
    return harness.LayerContext(
        trace=trace, telemetry=telemetry, config=config or {}, traffic={},
        cell={"name": "no-such-cell"}, peaks=peaks, values={})


COUNTED = {"moe_counters": {
    "residual_3/main/moe": {"steps": 10.0, "pairs": 10 * 24576.0,
                            "held_rows": 10 * 3000.0,
                            "load_max_sum": 10 * 400.0},
    "residual_5/main/moe": {"steps": 10.0, "pairs": 10 * 24576.0,
                            "held_rows": 10 * 3144.0,
                            "load_max_sum": 10 * 368.0}},
    "experts_held": 16, "router_experts": 128}


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


def test_counter_metrics_read_the_programs_counters():
    ctx = ctx_of(COUNTED)
    assert reader("moe_held_rows_pct").read(ctx) == pytest.approx(12.5)
    # busiest expert 384 pairs a step, the mean expert 24576 / 128 = 192
    assert reader("moe_load_max_over_mean").read(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_or_a_trace_reads_as_nothing(name):
    """The parent commit has neither the scopes nor the counters: every new
    reader returns None for it and raises nothing."""
    assert reader(name).read(ctx_of({})) is None
    assert reader(name).read(ctx_of({"moe_counters": {}})) is None


def test_scope_split_follows_the_scope_after_the_layers():
    assert scopes_moe._inner(["residual_3", "main", "moe", "route"]) == "route"
    assert scopes_moe._inner(
        ["residual_5", "main", "moe_1", "shared", "dense"]) == "shared"
    assert scopes_moe._inner(["residual_1", "main", "gated_mlp", "dense"]
                             ) is None
    assert scopes_moe._inner(["residual_3", "main", "moe"]) is None
    # and the accepted grouping puts all of it in ``mlp``, the attention
    # layer by its prefix, the bias-free head with the loss
    assert scopes.group_of(["residual_3", "main", "moe", "route"]) == "mlp"
    assert scopes.group_of(["residual_1", "main", "gated_mlp", "dense_2"]
                           ) == "mlp"
    assert scopes.group_of(
        ["residual_2", "main", "multi_head_attention_latent", "kv_norm"]
    ) == "attention"
    assert scopes.group_of(["dense"]) == "head_loss"
    assert scopes.group_of(["residual_2", "main", "rms_norm"]) == "other"


def fake_device(events):
    ops = [trace_lib.Event(name=f"%{n}.{i} = custom-call()", start=float(i),
                           end=float(i) + s) for i, (n, s) in
           enumerate(events)]
    return types.SimpleNamespace(devices=[trace_lib.DeviceTrace(
        ordinal=0, ops=ops, modules=[])])


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_grouped_matmul_roofline_counts_the_held_rows_work():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "kanana-2-30b-a3b.json"))
    ops, nbytes = flops_deepseek_v3.grouped_matmul_cost(3072, 16, 2048, 768)
    assert ops == 9 * 2 * 3072 * 2048 * 768
    assert nbytes == 9 * 2 * (16 * 2048 * 768 + 3072 * (2048 + 768))
    least, bound = flops.least_seconds(ops / 9, nbytes / 9, PEAKS)
    assert bound == "memory"  # 192 rows an expert: the weights' bytes rule
    # nine calls, each taking twice its least time: 50%
    trace = fake_device([("dtpu_gmm", 2 * least)] * 3
                        + [("dtpu_gmm_nt", 2 * least)] * 3
                        + [("dtpu_gmm_tn", 2 * least)] * 3
                        + [("fusion", 1.0)])
    got = reader("moe_experts_roofline").read(
        ctx_of(COUNTED, config=cfg, trace=trace, peaks=PEAKS))
    assert got == pytest.approx(50.0)


def test_latent_flash_roofline_counts_both_widths():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "kanana-2-30b-a3b.json"))
    half_square = 2.0 * 32 * 4096 * 4096 * 0.5
    costs = {k: flops_deepseek_v3.mla_flash_cost(k, 1, 4096, 32, 192, 128)
             for k in ("fwd", "dq", "dkv")}
    assert costs["fwd"][0] == half_square * (192 + 128)
    assert costs["dq"][0] == half_square * (2 * 192 + 128)
    assert costs["dkv"][0] == half_square * (2 * 192 + 2 * 128)
    assert costs["fwd"][1] == 4096 * 32 * 2 * (2 * 192 + 2 * 128)
    # at these widths every kernel is compute-bound; equal width gives the
    # accepted flash_cost back
    assert flops_deepseek_v3.mla_flash_cost("dkv", 2, 512, 4, 64, 64) == (
        flops.flash_cost("dkv", 2, 512, 4, 64))
    least = {k: flops.least_seconds(*c, PEAKS)[0] for k, c in costs.items()}
    trace = fake_device([("dtpu_flash_fwd", 4 * least["fwd"]),
                         ("dtpu_flash_dq", 4 * least["dq"]),
                         ("dtpu_flash_dkv", 4 * least["dkv"])])
    t = {"rows_per_chip": 1, "seq_len": 4096}
    got = reader("mla_flash_roofline").read(
        ctx_of(t, config=cfg, trace=trace, peaks=PEAKS))
    assert got == pytest.approx(25.0)
    # a GPT-2 configuration has no such keys: nothing to read
    assert reader("mla_flash_roofline").read(
        ctx_of(t, config={"n_head": 16}, trace=trace, peaks=PEAKS)) is None


def test_the_steps_operations_by_the_shapes():
    """Of a step of 4,096 tokens, forward and twice that backward: dense and
    shared products 5.13 TFLOP, the head 0.81, the routed experts at their
    mean share 0.35, causal attention's two products 2.58 (the issue's 3.9
    counts the kernels' nine, recomputation included, which a model's
    operations do not)."""
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "kanana-2-30b-a3b.json"))
    per_token = flops_deepseek_v3.train_flops_per_token(cfg, 16128, 4096, 128)
    assert 4096 * per_token == pytest.approx(8.86e12, rel=0.01)
    assert flops_deepseek_v3.attention_params(cfg) == 26_345_472
    routed = 4 * flops_deepseek_v3.expert_params(cfg) * 6 * 16 / 128
    assert 6.0 * routed * 4096 == pytest.approx(0.348e12, rel=0.01)


def test_the_configuration_keeps_every_published_width():
    catalog = {
        "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_shared_experts": 2,
        "routed_scaling_factor": 2.448, "rope_theta": 1000000,
        "rms_norm_eps": 1e-06, "first_k_dense_replace": 1}
    manifest = harness.load_manifest()
    entry = harness.entry(manifest, "configs", "kanana-2-30b-a3b")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    for key, value in catalog.items():
        assert cfg[key] == value, key
    # What test_bench_traffic.py's test_config_files_state_their_departures
    # asks of every entry and, pinned to GPT-2's keys, cannot ask of this one
    # (tests/conftest.py hands that test the GPT-2 entries alone).
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["assumed"]["vocab_rows_held"] % 128 == 0
    assert cfg["assumed"]["vocab_rows_held"] >= cfg["vocab_size"]
    assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == cfg[
        "qk_head_dim"] == 192
    assert cfg["assumed"]["router_bias_update_rate"] == 0.001
    assert json.dumps(cfg)  # plain data
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128, "vocab_size": 128256}
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["router_experts"] == 128
    # the guide's floors: four expert layers, 8 experts, an eighth of the rows
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= 128256


def test_the_cell_reports_what_the_issue_lists():
    manifest = harness.load_manifest()
    cell = harness.entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-b1-t4096-z05"
    traffic = harness.load_json(harness.find_file(
        manifest, "traffic", cell["traffic"]))
    assert (traffic["global_batch"], traffic["seq_len"],
            traffic["zipf_exponent"]) == (1, 4096, 0.5)
    assert len(traffic["expect_kernels"]) == 6
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in harness.metrics_of(manifest, s, CELL)}
    assert set(NEW_METRICS) <= listed
    assert {"flash_roofline", "exposed_collective_pct"}.isdisjoint(listed)
    assert {"train_tokens_per_s", "setup_s", "step_mfu_pct", "xent_roofline",
            "attn_device_ms", "mlp_device_ms", "scope_unattributed_pct",
            "setup_compile_s"} <= listed
    for name in NEW_METRICS:  # the new metrics list the new cell alone
        assert harness.entry(manifest, "per_layer", name)["workloads"] == [
            CELL]


SCOPE_METRICS = ("attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "cast_device_ms",
                 "scope_unattributed_pct")


def test_run_py_lists_the_scope_metrics_for_the_new_cell(tmp_path):
    """What test_bench_scopes.py's
    test_run_py_lists_the_scope_metrics_for_the_train_cells asks of the train
    cells; its exact ``workloads`` lists cannot hold once a third cell
    exists, so that test sees the GPT-2 cells alone (tests/conftest.py) and
    the whole lists are pinned here."""
    from benchmarks import run

    manifest = harness.load_manifest()
    env = types.SimpleNamespace(
        trace_dir=str(tmp_path), rehearsal=True, config={}, traffic={},
        cell=harness.entry(manifest, "workloads", CELL))
    metrics, parsed = run.layer_metrics(env, manifest, CELL, {}, "cpu")
    assert parsed is None
    for name in SCOPE_METRICS:
        assert metrics[name] == {
            "value": None, "unit": "%" if name.endswith("pct") else "ms"}
        entry = harness.entry(manifest, "per_layer", name)
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["better"] == "lower"
        assert entry["workloads"] == [
            "gpt2-medium.train.1chip", "gpt2-large.train.fsdp4", CELL]
