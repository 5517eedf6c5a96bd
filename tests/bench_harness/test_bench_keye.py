"""The Keye-VL-2.0 family's benchmark files: a rehearsal of
``drivers/train_family_aux.py`` on the tiny configuration (its manifest is
``rehearsal-keye.json``; ``run.py --rehearsal`` reads the accepted
``rehearsal.json``, which this PR may not edit, so the child process points
it at the new file, as ``test_bench_kanana.py`` does), the readers of the
five new per-layer metrics on made-up telemetry and a made-up trace, the
kernels' cost, and the entries of ``BENCHMARK.json``."""

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

from benchmarks import flops, flops_keye_vl2, harness, scopes  # noqa: E402
from benchmarks import scopes_dsa, trace as trace_lib  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
MANIFEST = os.path.join(HERE, "rehearsal-keye.json")
CELL = "keye-vl2-30b.train.dsa8k"
KANANA = "kanana2-30b.train.ep8share"
NEW_METRICS = ("dsa_index_device_ms", "dsa_select_device_ms",
               "dsa_flash_roofline", "dsa_selected_pairs_pct",
               "dsa_blocks_computed_pct")
MOE_METRICS = ("moe_route_device_ms", "moe_experts_device_ms",
               "moe_experts_roofline", "moe_held_rows_pct",
               "moe_load_max_over_mean", "moe_buffer_used_pct")
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
def test_rehearsal_of_the_aux_driver_prints_a_well_formed_line(trace):
    proc = rehearse("--workload", "keye-tiny.train", "--seed", "2147483999",
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
    assert checks["routing_agrees"] and checks["selection_agrees"]
    assert len(checks["flipped_keys_share"]) == 2
    assert len(checks["missed_keys_share"]) == 2
    # the first loss carries the two layers' L_I; the limit of ln(rows) is
    # put on what is left of it
    assert checks["reference_index_loss"] > 0.2
    assert checks["first_lm_loss"] == pytest.approx(
        checks["first_loss"] - checks["reference_index_loss"])
    assert checks["first_loss_near_ln_vocab"]
    assert checks["params"] > 0 and checks["steps"] > 10
    counted = checks["family"]["select_counters"]
    assert len(counted) == 2 and all(
        0 < c["selected_pairs"] < c["causal_pairs"] for c in counted.values())


@pytest.mark.parametrize("others_pass,index_loss,want", [
    (True, 1.2, True), (True, 0.7, False), (False, 1.2, False)])
def test_aux_driver_ands_its_check_to_train_familys_verdict(
        monkeypatch, others_pass, index_loss, want):
    """``train_family.run`` runs with its limit on the whole first loss
    lifted, so its verdict is that of its other checks; the limit on the
    first loss less L_I is and-ed to it."""
    import math
    import types

    from benchmarks.drivers import train_family, train_family_aux

    limit = train_family.FIRST_LOSS_TOL
    seen = {}

    def run(env):
        seen["limit"] = train_family.FIRST_LOSS_TOL
        return {"correct": others_pass, "checks": {
            "first_loss": math.log(512) + 1.2 + 0.05,
            "reference_index_loss": index_loss}}

    monkeypatch.setattr(train_family, "run", run)
    env = types.SimpleNamespace(
        config={}, family=types.SimpleNamespace(vocab_rows=lambda cfg: 512))
    result = train_family_aux.run(env)
    assert seen["limit"] == math.inf
    assert train_family.FIRST_LOSS_TOL == limit
    assert result["correct"] is want
    assert result["checks"]["first_loss_near_ln_vocab"] is (
        index_loss == 1.2)


def ctx_of(telemetry, config=None, trace=None, peaks=None):
    return harness.LayerContext(
        trace=trace, telemetry=telemetry, config=config or {}, traffic={},
        cell={"name": "no-such-cell"}, peaks=peaks, values={})


COUNTED = {"select_counters": {
    "residual/main/multi_head_attention_gqa": {
        "steps": 10.0, "queries": 10 * 8192.0,
        "causal_pairs": 10 * 33558528.0, "selected_pairs": 10 * 14681088.0,
        "blocks_total": 10 * 80.0, "blocks_computed": 10 * 80.0},
    "residual_2/main/multi_head_attention_gqa": {
        "steps": 10.0, "queries": 10 * 8192.0,
        "causal_pairs": 10 * 33558528.0, "selected_pairs": 10 * 14681088.0,
        "blocks_total": 10 * 80.0, "blocks_computed": 10 * 40.0}}}


def reader(name):
    return harness.load_module(harness.load_manifest(), "layer_metrics", name)


def test_counter_metrics_read_the_programs_counters():
    ctx = ctx_of(COUNTED)
    assert reader("dsa_selected_pairs_pct").read(ctx) == pytest.approx(
        43.7477, rel=1e-5)
    assert reader("dsa_blocks_computed_pct").read(ctx) == pytest.approx(75.0)
    # the dense path counts no blocks: nothing to read
    dense = {"select_counters": {"a": dict(
        COUNTED["select_counters"]["residual/main/multi_head_attention_gqa"],
        blocks_total=0.0, blocks_computed=0.0)}}
    assert reader("dsa_blocks_computed_pct").read(ctx_of(dense)) is None
    assert reader("dsa_selected_pairs_pct").read(ctx_of(dense)) is not None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_counters_or_a_trace_reads_as_nothing(name):
    """The parent commit has neither the scopes nor the counters: every new
    reader returns None for it and raises nothing."""
    assert reader(name).read(ctx_of({})) is None
    assert reader(name).read(ctx_of({"select_counters": {}})) is None


def test_scope_split_takes_the_selection_out_of_the_indexer():
    gqa = ["residual_2", "main", "multi_head_attention_gqa"]
    assert scopes_dsa._inner(gqa + ["indexer", "qjd,sd->qjs"]) == "indexer"
    assert scopes_dsa._inner(gqa + ["indexer", "k_norm"]) == "indexer"
    assert scopes_dsa._inner(gqa + ["indexer", "select"]) == "select"
    assert scopes_dsa._inner(gqa + ["q_norm"]) is None
    assert scopes_dsa._inner(gqa) is None
    assert scopes_dsa._inner(["residual_3", "main", "moe", "route"]) is None
    # and the accepted grouping puts all of it under attention, by the prefix
    for rest in ([], ["indexer"], ["indexer", "select"], ["q_norm"]):
        assert scopes.group_of(gqa + rest) == "attention"
    # JAX's own wrappers inside the indexer's loop are dropped by the rule
    path, _ = scopes.scope_of(
        "jit(step)/jvp(residual_2)/main/multi_head_attention_gqa/indexer/"
        "while/body/closed_call/while/body/closed_call/jvp(select)/and")
    assert scopes_dsa._inner(path) == "select"


def fake_device(events):
    ops = [trace_lib.Event(name=f"%{n}.{i} = custom-call()", start=float(i),
                           end=float(i) + s) for i, (n, s) in
           enumerate(events)]
    return types.SimpleNamespace(devices=[trace_lib.DeviceTrace(
        ordinal=0, ops=ops, modules=[])])


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_selecting_flash_roofline_counts_the_selected_pairs():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl-2.0-30b-a3b.json"))
    costs = {k: flops_keye_vl2.dsa_flash_cost(k, 1, 8192, 32, 4, 128, 2048)
             for k in ("fwd", "dq", "dkv")}
    selected = 2.0 * 14_681_088 * 32 * 128
    assert [costs[k][0] for k in ("fwd", "dq", "dkv")] == [
        2 * selected, 3 * selected, 4 * selected]
    # q and o as wide as the 32 query heads, k and v as the 4 K/V heads
    assert costs["fwd"][1] == 8192 * 128 * 2 * (2 * 32 + 2 * 4)
    assert costs["dq"][1] == 8192 * 128 * 2 * (3 * 32 + 2 * 4)
    # with the selection idle and one K/V head a query head: the causal count
    t = 1024
    assert flops_keye_vl2.dsa_flash_cost("dq", 2, t, 4, 4, 64, 2048)[0] == (
        pytest.approx(flops.flash_cost("dq", 2, t, 4, 64)[0] * (t + 1) / t))
    least = {k: flops.least_seconds(*c, PEAKS) for k, c in costs.items()}
    assert all(bound == "compute" for _, bound in least.values())
    trace = fake_device([("dtpu_flash_fwd_sel", 5 * least["fwd"][0]),
                         ("dtpu_flash_dq_sel", 5 * least["dq"][0]),
                         ("dtpu_flash_dkv_sel", 5 * least["dkv"][0]),
                         ("dtpu_flash_fwd_packed", 1.0)])
    tel = {"rows_per_chip": 1, "seq_len": 8192}
    got = reader("dsa_flash_roofline").read(
        ctx_of(tel, config=cfg, trace=trace, peaks=PEAKS))
    assert got == pytest.approx(20.0)
    # a trace without the selecting kernels, or another family's
    # configuration: nothing to read
    plain = fake_device([("dtpu_flash_fwd_packed", 1.0)])
    assert reader("dsa_flash_roofline").read(
        ctx_of(tel, config=cfg, trace=plain, peaks=PEAKS)) is None
    assert reader("dsa_flash_roofline").read(
        ctx_of(tel, config={"n_head": 16}, trace=trace, peaks=PEAKS)) is None


def test_the_configuration_keeps_every_published_number():
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    manifest = harness.load_manifest()
    entry = harness.entry(manifest, "configs", "keye-vl-2.0-30b-a3b")
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    for key, value in catalog.items():
        assert cfg[key] == value, key
    # What test_bench_traffic.py's test_config_files_state_their_departures
    # asks of every entry and, pinned to GPT-2's keys, cannot ask of this one
    # (tests/conftest.py hands that test the GPT-2 entries alone).
    assert cfg["source"] == entry["source"]
    assert cfg["family"] == "keye_vl2"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128,
        "num_local_experts": 128, "vocab_size": 151936}
    assert cfg["num_experts"] == cfg["num_local_experts"]
    assert cfg["assumed"]["vocab_rows_held"] % 128 == 0
    assert cfg["assumed"]["vocab_rows_held"] >= cfg["vocab_size"]
    assert cfg["assumed"]["lr_warmup_steps"] == 2000
    assert json.dumps(cfg)  # plain data
    assert cfg["deployment"]["chips_per_layer"] == 8
    assert cfg["deployment"]["router_experts"] == 128
    # the guide's floors: four layers of the one-layer period, 8 experts, an
    # eighth of the rows; no width is among the reduced keys
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= 151936
    assert all(len(v) <= 200 for v in (entry["why"], entry["source"]))


def test_the_cell_reports_what_issue_32_lists():
    manifest = harness.load_manifest()
    cell = harness.entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train-b1-t8192-z05"
    assert cell is manifest["workloads"][-1] and len(cell["why"]) <= 200
    traffic = harness.load_json(harness.find_file(
        manifest, "traffic", cell["traffic"]))
    assert (traffic["global_batch"], traffic["seq_len"],
            traffic["zipf_exponent"], traffic["distinct_batches"],
            traffic["learning_rate"]) == (1, 8192, 0.5, 64, 1e-4)
    assert traffic["driver"] == "train_family_aux"
    assert traffic["expect_kernels"] == [
        "dtpu_flash_fwd_sel", "dtpu_flash_dq_sel", "dtpu_flash_dkv_sel",
        "dtpu_gmm", "dtpu_moe_rows_gather", "dtpu_moe_rows_sum",
        "dtpu_xent_fwd", "dtpu_xent_bwd"]
    listed = {m["name"] for s in ("end_to_end", "per_layer")
              for m in harness.metrics_of(manifest, s, CELL)}
    assert set(NEW_METRICS) | set(MOE_METRICS) <= listed
    assert {"flash_roofline", "mla_flash_roofline",
            "exposed_collective_pct"}.isdisjoint(listed)
    assert {"train_tokens_per_s", "setup_s", "step_mfu_pct", "xent_roofline",
            "attn_device_ms", "mlp_device_ms", "scope_unattributed_pct",
            "setup_compile_s"} <= listed
    for name in NEW_METRICS:  # the new metrics list the new cell alone
        entry = harness.entry(manifest, "per_layer", name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_tokens_per_s"
    for name in MOE_METRICS:  # the kanana cell's, the new cell appended
        assert harness.entry(manifest, "per_layer", name)["workloads"] == [
            KANANA, CELL]
    # everything new sits at the end of its list
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(
        NEW_METRICS)
    assert manifest["configs"][-1]["name"] == "keye-vl-2.0-30b-a3b"


SCOPE_METRICS = ("attn_device_ms", "mlp_device_ms", "head_loss_device_ms",
                 "optimizer_device_ms", "cast_device_ms",
                 "scope_unattributed_pct")


def test_run_py_lists_the_scope_metrics_for_the_keye_cell(tmp_path):
    """What test_bench_kanana.py asserts of its cell's scope metrics, with
    the whole ``workloads`` lists as they are now (that test sees them
    without this family's cell: tests/conftest.py)."""
    from benchmarks import run

    manifest = harness.load_manifest()
    env = types.SimpleNamespace(
        trace_dir=str(tmp_path), rehearsal=True, config={}, traffic={},
        cell=harness.entry(manifest, "workloads", CELL))
    metrics, parsed = run.layer_metrics(env, manifest, CELL, {}, "cpu")
    assert parsed is None
    assert set(NEW_METRICS) <= set(metrics)
    for name in SCOPE_METRICS:
        assert metrics[name] == {
            "value": None, "unit": "%" if name.endswith("pct") else "ms"}
        entry = harness.entry(manifest, "per_layer", name)
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["better"] == "lower"
        assert entry["workloads"] == [
            "gpt2-medium.train.1chip", "gpt2-large.train.fsdp4", KANANA,
            CELL]
