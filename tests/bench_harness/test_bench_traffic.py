"""The benchmark's traffic generator, FLOP counts, peaks table and manifest."""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops, harness, traffic  # noqa: E402
from benchmarks.drivers.serve import prefill_buckets  # noqa: E402

TRAIN_MIXES = ("train-b8-t1024", "train-b16-t1024")
# The serving mixes of ISSUE 22 (no cell runs them yet: PERF.md section 7)
# under the deployment it names; the generator is tested on them.
ENGINE = {"max_slots": 48, "block_size": 16, "max_len": 1024,
          "prefill_chunk": 256}
SERVE_MIXES = {
    "decode-heavy": {
        "prompt_len": {"median": 96, "sigma": 0.6, "min": 16, "max": 256},
        "output_len": {"median": 224, "sigma": 0.5, "min": 64, "max": 640},
        "max_total_len": 1024, "zipf_exponent": 1.0},
    "prefill-heavy": {
        "prompt_len": {"median": 512, "sigma": 0.5, "min": 128, "max": 960},
        "output_len": {"median": 16, "sigma": 0.5, "min": 4, "max": 48},
        "max_total_len": 1024, "zipf_exponent": 1.0},
}


def load(kind, name):
    return harness.load_json(os.path.join(ROOT, "benchmarks", kind,
                                          name + ".json"))


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


@pytest.mark.parametrize("mix", sorted(SERVE_MIXES))
def test_serve_requests_repeat_for_a_seed_and_differ_for_another(mix):
    params = SERVE_MIXES[mix]
    a = traffic.serve_requests(params, 50257, 7, 120)
    b = traffic.serve_requests(params, 50257, 7, 120)
    c = traffic.serve_requests(params, 50257, 8, 120)
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(a, b))
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, c))
    # stratified: every seed sends the same lengths, in another order
    assert sorted(p.size for p, _ in a) == sorted(p.size for p, _ in c)
    assert [p.size for p, _ in a] != [p.size for p, _ in c]


@pytest.mark.parametrize("mix", sorted(SERVE_MIXES))
def test_every_request_fits_and_lands_in_a_warmed_bucket(mix):
    params = SERVE_MIXES[mix]
    for seed in (0, 1):
        for prompt, new in traffic.serve_requests(params, 50257, seed, 300):
            assert prompt.size + new <= ENGINE["max_len"]
            assert params["prompt_len"]["min"] <= prompt.size
            assert prompt.size <= params["prompt_len"]["max"]
            assert 1 <= new <= params["output_len"]["max"]
            assert prompt.min() >= 0 and prompt.max() < 50257
            buckets = prefill_buckets(prompt.size, ENGINE["prefill_chunk"],
                                      ENGINE["max_len"])
            assert set(buckets) <= {64, 128, 192, 256}


def test_stratified_lengths_follow_the_distribution():
    spec = SERVE_MIXES["decode-heavy"]["output_len"]
    got = traffic.lengths(spec, 1001, np.random.default_rng(0))
    assert int(np.median(got)) == spec["median"]
    assert got.min() == spec["min"] and got.max() == spec["max"]
    # log-normal: as many lengths under median / e^sigma as over median x
    # e^sigma (15.9% each), within the rounding to whole tokens
    lo = np.mean(got < spec["median"] / np.exp(spec["sigma"]))
    hi = np.mean(got > spec["median"] * np.exp(spec["sigma"]))
    assert abs(lo - 0.159) < 0.01 and abs(hi - 0.159) < 0.01


@pytest.mark.parametrize("mix", TRAIN_MIXES)
def test_train_batches(mix):
    params = dict(load("traffic", mix), distinct_batches=2)
    x, y = traffic.train_batches(params, 50257, 3)
    rows = 2 * params["global_batch"]
    assert x.shape == y.shape == (rows, params["seq_len"])
    assert np.array_equal(x[:, 1:], y[:, :-1])  # next-token labels
    assert x.min() >= 0 and x.max() < 50257
    x2, _ = traffic.train_batches(params, 50257, 3)
    x3, _ = traffic.train_batches(params, 50257, 4)
    assert np.array_equal(x, x2) and not np.array_equal(x, x3)
    # Zipf: the most frequent id is far more frequent than uniform
    assert np.mean(x == 0) > 100.0 / 50257


def test_flops_against_a_hand_count_for_gpt2_medium():
    cfg = load("configs", "gpt2-medium")
    # wte 50304*1024, wpe 1024*1024, 24 blocks of 12,596,224, final LN
    # 2048, untied biased head 50304*1024 + 50304.
    assert flops.param_count(cfg, 50304) == 406_432_896
    # 24 * (4*1024^2 + 2*1024*4096) + 50304*1024 matmul weights, x2; plus
    # causal attention 24 * 0.5 * 4 * 1024 * 1024.
    assert flops.forward_flops_per_token(cfg, 50304, 1024) == 757_334_016
    assert flops.train_flops_per_token(cfg, 50304, 1024) == 2_272_002_048
    ops, nbytes = flops.flash_cost("fwd", 8, 1024, 16, 64)
    assert ops == 2 * (2 * 8 * 16 * 1024 * 1024 * 64) / 2
    assert nbytes == 4 * 8 * 1024 * 1024 * 2


def test_gpt2_large_needs_sharding():
    cfg = load("configs", "gpt2-large")
    n = flops.param_count(cfg, 50304)
    assert 835e6 < n < 842e6
    assert 16 * n > 13e9  # masters, gradients, Adam moments: over one chip


def test_peaks_table():
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchmarkError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_least_seconds_names_the_bound():
    peaks = harness.peaks_for("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, peaks) == (1.0, "memory")


def test_percentile():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile([0, 10], 90) == 9
    with pytest.raises(harness.BenchmarkError):
        harness.percentile([], 50)


# ------------------------------------------------------------- manifest --
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
    used = {c["config"] for c in cells}
    for cfg in manifest["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert cfg["name"] in used
        assert any(cfg["file"].startswith(p + "/") for p in manifest["paths"])
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))


def test_manifest_metrics(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    cells = {c["name"] for c in manifest["workloads"]}
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # found by its name alone: one reader file per metric
        harness.find_file(manifest, "layer_metrics", m["name"], (".py",))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = harness.metrics_of(manifest, "end_to_end", cell)
        assert {"setup_s"} < {m["name"] for m in mine}
        layers = harness.metrics_of(manifest, "per_layer", cell)
        assert layers
        assert all(m["moves"] in {e["name"] for e in mine} for m in layers)


def test_every_cell_finds_its_files(manifest):
    for cell in manifest["workloads"]:
        mix = harness.load_json(harness.find_file(
            manifest, "traffic", cell["traffic"]))
        harness.find_file(manifest, "drivers", mix["driver"], (".py",))
        cfg = harness.load_json(os.path.join(ROOT, harness.entry(
            manifest, "configs", cell["config"])["file"]))
        harness.find_file(manifest, "families", cfg["family"], (".py",))
        harness.find_file(manifest, "reference", cfg["family"], (".py",))


def test_config_files_state_their_departures(manifest):
    for cfg in manifest["configs"]:
        body = harness.load_json(os.path.join(ROOT, cfg["file"]))
        assert body["source"] == cfg["source"]
        assert body["reduced"] == cfg["reduced"] == []
        assert body["n_embd"] // body["n_head"] == 64
        assert body["assumed"]["vocab_rows_held"] % 128 == 0
        assert json.dumps(body)  # plain data
