"""``benchmarks/run.py --rehearsal``: each driver end to end on the CPU, a
well-formed last line with every metric null; and the device gate."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "benchmarks", "run.py")


def run(*args, devices=1):
    """The benchmark as a child process on the CPU: one XLA thread and a low
    priority, so that it takes no cores from the timing-sensitive tests the
    other workers run meanwhile."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", OMP_NUM_THREADS="1")
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        "--xla_cpu_multi_thread_eigen=false "
                        "intra_op_parallelism_threads=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, RUN, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900,
                          preexec_fn=lambda: os.nice(15))


@pytest.mark.parametrize("cell,trace,devices", [
    ("gpt2-tiny.train", 0, 1), ("gpt2-tiny.train", 1, 1),
    ("gpt2-tiny.train.fsdp", 0, 4),
    ("gpt2-tiny.serve", 0, 1), ("gpt2-tiny.serve", 1, 1),
])
def test_rehearsal_prints_a_well_formed_line(cell, trace, devices):
    proc = run("--workload", cell, "--seed", "3", "--seconds", "1",
               "--trace", str(trace), "--rehearsal", devices=devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, proc.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    manifest = json.load(open(os.path.join(
        ROOT, "tests", "bench_harness", "rehearsal.json")))
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in manifest[section]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    # no number of a CPU run is written under the name of a device metric
    assert all(m["value"] is None and m["unit"]
               for m in line["metrics"].values())


def test_without_a_tpu_the_benchmark_exits_non_zero_and_prints_nothing():
    proc = run("--workload", "gpt2-medium.train.1chip", "--seed", "0",
               "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not 'tpu'" in proc.stderr


def test_an_unknown_cell_is_refused():
    proc = run("--workload", "no-such-cell", "--rehearsal")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
