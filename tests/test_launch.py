"""Launcher tests: gang spawn, config injection, result/error gather.

These reproduce the reference's launcher semantics without Spark
(SURVEY.md §7 hard parts): barrier-style gang scheduling
(/root/reference/README.md:179), rank + peer-list injection
(README.md:180-183), and tryCatch-style error-as-result rows
(README.md:176, 221).
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from distributed_tpu.launch import LocalLauncher

REPO = str(Path(__file__).resolve().parent.parent)


def write_worker(tmp_path, body):
    script = tmp_path / "worker.py"
    script.write_text(
        textwrap.dedent(
            f"""
            import os, sys, json
            sys.path.insert(0, {REPO!r})
            """
        )
        + textwrap.dedent(body)
    )
    return str(script)


@pytest.mark.smoke
def test_config_injection_and_results(tmp_path):
    script = write_worker(
        tmp_path,
        """
        from distributed_tpu.cluster import from_env
        from distributed_tpu.launch import report_result
        spec = from_env()
        report_result({"rank": spec.index, "n": spec.num_processes,
                       "peers": spec.workers})
        """,
    )
    results = LocalLauncher().run([sys.executable, script], 3, timeout=60)
    assert len(results) == 3
    assert all(r.ok for r in results)
    ranks = sorted(r.value["rank"] for r in results)
    assert ranks == [0, 1, 2]
    assert all(r.value["n"] == 3 for r in results)
    # Every worker sees the same rank-ordered peer list (README.md:84-114).
    peers = {tuple(r.value["peers"]) for r in results}
    assert len(peers) == 1


def test_error_capture_as_result_row(tmp_path):
    script = write_worker(
        tmp_path,
        """
        from distributed_tpu.cluster import from_env
        spec = from_env()
        if spec.index == 1:
            raise RuntimeError("boom on worker 1")
        from distributed_tpu.launch import report_result
        report_result("fine")
        """,
    )
    results = LocalLauncher().run([sys.executable, script], 2, timeout=60, grace=5)
    by_rank = {r.index: r for r in results}
    assert by_rank[0].ok and by_rank[0].value == "fine"
    assert not by_rank[1].ok
    assert "boom on worker 1" in by_rank[1].log_tail


def test_cli_end_to_end(tmp_path):
    script = write_worker(
        tmp_path,
        """
        from distributed_tpu.cluster import from_env
        from distributed_tpu.launch import report_result
        report_result(from_env().index * 10)
        """,
    )
    out = tmp_path / "results.json"
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tpu.launch",
         "--num-workers", "2", "--results-json", str(out), script],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = json.loads(out.read_text())
    assert sorted(r["value"] for r in rows) == [0, 10]


@pytest.mark.slow
def test_distributed_training_via_launcher(tmp_path):
    """Full stack: gang launch -> jax.distributed over CPU processes -> DP
    train -> identical metrics on every worker (the reference's invariant,
    README.md:226-232)."""
    script = write_worker(
        tmp_path,
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import distributed_tpu as dtpu
        from distributed_tpu.launch import report_result

        spec = dtpu.cluster.initialize()
        x, y = dtpu.data.synthetic_images(256, (28, 28), 10, 0)
        x = x[..., None].astype(np.float32) / 255.0

        strategy = dtpu.DataParallel()
        with strategy.scope():
            m = dtpu.Model(dtpu.models.mnist_cnn())
            m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
        hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=2,
                     steps_per_epoch=3, verbose=0, seed=0)
        report_result({"rank": spec.index,
                       "acc": hist.metrics["accuracy"][-1],
                       "loss": hist.metrics["loss"][-1]})
        """,
    )
    results = LocalLauncher().run([sys.executable, script], 2, timeout=300)
    assert all(r.ok for r in results), [(r.index, r.error, r.log_tail[-500:]) for r in results]
    accs = {r.value["acc"] for r in results}
    losses = {r.value["loss"] for r in results}
    assert len(accs) == 1 and len(losses) == 1  # replicas in lockstep


# @slow (tier-1 budget, PR 17): ~7s hung-worker wait; config
# injection, error-capture, and CLI end-to-end stay in-tier, and the
# restart-after-hang path is already @slow alongside this.
@pytest.mark.slow
def test_liveness_timeout_kills_hung_worker(tmp_path):
    """A worker that goes silent (SIGSTOP — alive but not beating) is
    killed with a 'liveness timeout' row within liveness_timeout, and its
    peers are gang-killed within grace — instead of everyone burning the
    full run timeout (VERDICT r4 missing #3)."""
    import time as _time

    script = write_worker(
        tmp_path,
        """
        import signal, time
        from distributed_tpu.cluster.config import from_env
        from distributed_tpu.launch import heartbeat, report_result

        spec = from_env()
        for i in range(400):
            heartbeat(min_interval=0.0)
            time.sleep(0.05)
            if spec.index == 1 and i == 8:
                signal.raise_signal(signal.SIGSTOP)
        report_result({"rank": spec.index})
        """,
    )
    t0 = _time.time()
    results = LocalLauncher().run(
        [sys.executable, script], 2,
        timeout=300, grace=2.0, liveness_timeout=2.0,
    )
    elapsed = _time.time() - t0
    by_rank = {r.index: r for r in results}
    assert not by_rank[1].ok
    assert "liveness timeout" in by_rank[1].error, by_rank[1].error
    assert not by_rank[0].ok  # gang semantics took the survivor too
    assert "peer failure" in by_rank[0].error, by_rank[0].error
    # The whole point: detection happened in ~liveness_timeout+grace,
    # not the 300s run timeout (generous bound for slow CI).
    assert elapsed < 60, elapsed


@pytest.mark.slow
def test_hung_worker_triggers_restart_and_resume(tmp_path):
    """End-to-end elastic recovery from a HANG (not a crash): worker 1
    SIGSTOPs itself mid-epoch on the first attempt; the liveness probe
    treats the stalled heartbeat as a failure, run_with_restart relaunches
    the gang, and ModelCheckpoint(restore=True) finishes the run with
    weights bit-identical to an uninterrupted one."""
    import time as _time

    marker = tmp_path / "hung_once"
    body = f"""
        import os, signal
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import distributed_tpu as dtpu
        from distributed_tpu.launch import report_result
        from distributed_tpu.training.callbacks import Callback, ModelCheckpoint

        spec = dtpu.cluster.initialize()
        x, y = dtpu.data.synthetic_images(512, (28, 28), 10, 0)
        x = x[..., None].astype(np.float32) / 255.0

        CKPT = os.environ["TEST_CKPT_DIR"]
        MARKER = {str(marker)!r}

        class HangOnce(Callback):
            # Worker 1 goes silent mid-epoch-2 on the first attempt only:
            # SIGSTOP freezes the process without killing it — exactly the
            # failure mode exit-code monitoring cannot see.
            def on_batch_end(self, model, step, logs):
                if (spec.index == 1 and step == 5
                        and not os.path.exists(MARKER)):
                    open(MARKER, "w").close()
                    signal.raise_signal(signal.SIGSTOP)

        strategy = dtpu.DataParallel()
        with strategy.scope():
            m = dtpu.Model(dtpu.models.mnist_cnn())
            m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
        cbs = [ModelCheckpoint(CKPT, save_freq=3, restore=True), HangOnce()]
        hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=3,
                     steps_per_epoch=4, verbose=0, seed=0, callbacks=cbs)
        leaf = np.asarray(
            jax.tree_util.tree_leaves(m.params)[0]).ravel()[:4]
        report_result({{"rank": spec.index,
                       "loss": hist.metrics["loss"][-1],
                       "acc": hist.metrics["accuracy"][-1],
                       "leaf": [float(v) for v in leaf],
                       "epochs": hist.epoch}})
        """
    script = write_worker(tmp_path, body)

    from distributed_tpu.launch import run_with_restart

    env = {"TEST_CKPT_DIR": str(tmp_path / "ckpt")}
    t0 = _time.time()
    results = run_with_restart(
        LocalLauncher(env_extra=env), [sys.executable, script], 2,
        max_restarts=2, restart_backoff=0.1, timeout=600, grace=5,
        liveness_timeout=5.0,
    )
    elapsed = _time.time() - t0
    assert all(r.ok for r in results), [
        (r.index, r.error, r.log_tail[-600:]) for r in results
    ]
    assert marker.exists()  # the hang actually happened
    # Liveness (not the 600s timeout) must have driven the recovery.
    assert elapsed < 300, elapsed

    # Uninterrupted reference run: fresh checkpoint dir, no hang.
    marker.touch()  # HangOnce disarmed
    env2 = {"TEST_CKPT_DIR": str(tmp_path / "ckpt_ref")}
    ref = LocalLauncher(env_extra=env2).run(
        [sys.executable, script], 2, timeout=600
    )
    assert all(r.ok for r in ref), [
        (r.index, r.error, r.log_tail[-600:]) for r in ref
    ]
    got = {r.index: r.value for r in results}
    want = {r.index: r.value for r in ref}
    for rank in (0, 1):
        assert got[rank]["loss"] == want[rank]["loss"]
        assert got[rank]["acc"] == want[rank]["acc"]
        assert got[rank]["leaf"] == want[rank]["leaf"]


@pytest.mark.slow
def test_auto_restart_resumes_from_checkpoint(tmp_path):
    """Elastic recovery (the reference's self-documented gap, README.md:400):
    worker 1 dies mid-train on the first attempt; run_with_restart relaunches
    the gang, ModelCheckpoint(restore=True) resumes from the last complete
    checkpoint, and the finished run's weights + metrics are bit-identical
    to an uninterrupted run (the (seed, pass)-keyed resume math)."""
    marker = tmp_path / "died_once"
    body = f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import distributed_tpu as dtpu
        from distributed_tpu.launch import report_result
        from distributed_tpu.training.callbacks import Callback, ModelCheckpoint

        spec = dtpu.cluster.initialize()
        x, y = dtpu.data.synthetic_images(512, (28, 28), 10, 0)
        x = x[..., None].astype(np.float32) / 255.0

        CKPT = os.environ["TEST_CKPT_DIR"]
        MARKER = {str(marker)!r}

        class DieOnce(Callback):
            # Worker 1 hard-exits mid-epoch-2 on the first attempt only.
            def on_batch_end(self, model, step, logs):
                if (spec.index == 1 and step == 5
                        and not os.path.exists(MARKER)):
                    open(MARKER, "w").close()
                    os._exit(17)

        strategy = dtpu.DataParallel()
        with strategy.scope():
            m = dtpu.Model(dtpu.models.mnist_cnn())
            m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
        cbs = [ModelCheckpoint(CKPT, save_freq=3, restore=True), DieOnce()]
        hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=3,
                     steps_per_epoch=4, verbose=0, seed=0, callbacks=cbs)
        leaf = np.asarray(
            jax.tree_util.tree_leaves(m.params)[0]).ravel()[:4]
        report_result({{"rank": spec.index,
                       "loss": hist.metrics["loss"][-1],
                       "acc": hist.metrics["accuracy"][-1],
                       "leaf": [float(v) for v in leaf],
                       "epochs": hist.epoch}})
        """
    script = write_worker(tmp_path, body)

    from distributed_tpu.launch import run_with_restart

    env = {"TEST_CKPT_DIR": str(tmp_path / "ckpt")}
    results = run_with_restart(
        LocalLauncher(env_extra=env), [sys.executable, script], 2,
        max_restarts=2, restart_backoff=0.1, timeout=300, grace=5,
    )
    assert all(r.ok for r in results), [
        (r.index, r.error, r.log_tail[-600:]) for r in results
    ]
    assert marker.exists()  # the failure actually happened

    # Uninterrupted reference run: fresh checkpoint dir, no killing.
    marker.touch()  # DieOnce disarmed
    env2 = {"TEST_CKPT_DIR": str(tmp_path / "ckpt_ref")}
    ref = LocalLauncher(env_extra=env2).run(
        [sys.executable, script], 2, timeout=300
    )
    assert all(r.ok for r in ref), [
        (r.index, r.error, r.log_tail[-600:]) for r in ref
    ]
    got = {r.index: r.value for r in results}
    want = {r.index: r.value for r in ref}
    for rank in (0, 1):
        assert got[rank]["loss"] == want[rank]["loss"]
        assert got[rank]["acc"] == want[rank]["acc"]
        assert got[rank]["leaf"] == want[rank]["leaf"]


@pytest.mark.slow
def test_explicit_coordinator_gathers_real_worker_list(tmp_path):
    """initialize(coordinator=...) must return a REAL rank-ordered worker
    list on every process (gathered collectively), not placeholders."""
    script = write_worker(
        tmp_path,
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import distributed_tpu as dtpu
        from distributed_tpu.cluster import from_env
        from distributed_tpu.launch import report_result

        env_spec = from_env()
        spec = dtpu.cluster.initialize(
            coordinator=env_spec.coordinator,
            num_processes=env_spec.num_processes,
            process_id=env_spec.index,
        )
        report_result({"rank": spec.index, "workers": spec.workers})
        """,
    )
    results = LocalLauncher().run([sys.executable, script], 2, timeout=120)
    assert all(r.ok for r in results), [
        (r.index, r.error, r.log_tail[-500:]) for r in results
    ]
    for r in results:
        workers = r.value["workers"]
        assert len(workers) == 2
        assert not any(w.startswith("?") for w in workers)
        host0 = workers[0].rsplit(":", 1)[0]
        assert host0 not in ("", "?")
    # identical list on both ranks (collective gather)
    assert results[0].value["workers"] == results[1].value["workers"]


@pytest.mark.slow
def test_spark_barrier_flow_end_to_end(tmp_path):
    """The reference's full Spark-barrier workflow without Spark
    (/root/reference/README.md:170-247): gang-scheduled workers receive a
    barrier-style peer list + own rank, build their cluster spec with
    from_barrier (strip the scheduler's ports, re-port 8000+seq,
    README.md:180-183), train data-parallel, and return max accuracy AS A
    STRING per worker (README.md:220) — except rank 0, which returns the
    base64-encoded HDF5 model (README.md:236-247). The driver collects one
    row per worker, checks the replica-identical-accuracy invariant
    (README.md:226-232), and decodes rank 0's row into a model file."""
    script = write_worker(
        tmp_path,
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import numpy as np
        import distributed_tpu as dtpu
        from distributed_tpu.cluster import from_barrier, from_env
        from distributed_tpu.launch import report_result

        # The gang launcher plays Spark's barrier: its injected spec is the
        # stand-in for barrier$address / barrier$partition. Re-derive a
        # Spark-shaped peer list (scheduler-owned ports) and rebuild the
        # spec the way the reference's closure does.
        injected = from_env()
        barrier_addresses = [
            f"{w.rsplit(':', 1)[0]}:{7077 + i}"
            for i, w in enumerate(injected.workers)
        ]
        spec = from_barrier(barrier_addresses, injected.index,
                            base_port=23840)
        os.environ["DTPU_CONFIG"] = spec.to_json()
        spec = dtpu.cluster.initialize()

        x, y = dtpu.data.synthetic_images(256, (28, 28), 10, 0)
        x = x[..., None].astype(np.float32) / 255.0
        strategy = dtpu.DataParallel()
        with strategy.scope():
            m = dtpu.Model(dtpu.models.mnist_cnn())
            m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
        hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=2,
                     steps_per_epoch=3, verbose=0, seed=0)
        acc = str(max(hist.metrics["accuracy"]))
        if spec.index == 0:
            import tempfile
            path = os.path.join(tempfile.mkdtemp(), "trained-0.hdf5")
            dtpu.checkpoint.export_hdf5(path, m.params)
            report_result({"row": dtpu.checkpoint.artifact_encode(path),
                           "acc": acc})
        else:
            report_result({"row": acc, "acc": acc})
        """,
    )
    results = LocalLauncher().run([sys.executable, script], 2, timeout=300)
    assert all(r.ok for r in results), [
        (r.index, r.error, r.log_tail[-500:]) for r in results
    ]
    by_rank = {r.index: r for r in results}
    assert len(by_rank) == 2  # one row per worker, like collect()
    # Replica-identity invariant: identical accuracy strings on all workers.
    accs = {r.value["acc"] for r in results}
    assert len(accs) == 1, accs
    # Rank 0's row is the artifact; decode it like the reference's driver.
    from distributed_tpu.checkpoint import artifact_decode, import_hdf5

    out = tmp_path / "model.hdf5"
    artifact_decode(by_rank[0].value["row"], str(out))
    params, _ = import_hdf5(str(out))
    assert "conv2d" in params and "dense" in params
    # Rank 1's row is a parseable accuracy in [0, 1] (README.md:226-232).
    assert 0.0 <= float(by_rank[1].value["row"]) <= 1.0


def test_tpu_host_workers_get_one_chip_each_or_are_refused(
        tmp_path, monkeypatch):
    """On a TPU host every local worker is pinned to its own chip through
    libtpu's per-process environment (ran on a v5e 2x2 host in PR 21);
    a worker count the launcher cannot pin is refused before anything is
    spawned — never N workers fighting over the chips."""
    from distributed_tpu.launch import core

    script = tmp_path / "worker.py"  # no package import: 8 quick spawns
    script.write_text(textwrap.dedent(
        """
        import json, os
        keys = ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_ADDRESSES",
                "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID")
        with open(os.environ["DTPU_RESULT_FILE"], "w") as f:
            json.dump({"value": {k: os.environ.get(k) for k in keys}}, f)
        """
    ))
    script = str(script)
    monkeypatch.delenv("JAX_PLATFORMS")  # workers not held to the CPU
    monkeypatch.setattr(core, "_tpu_chip_count", lambda: 4)
    results = LocalLauncher().run([sys.executable, script], 4, timeout=60)
    assert all(r.ok for r in results)
    addresses = {r.value["TPU_PROCESS_ADDRESSES"] for r in results}
    assert len(addresses) == 1 and len(addresses.pop().split(",")) == 4
    for i, r in enumerate(results):
        assert r.value["TPU_VISIBLE_CHIPS"] == r.value["CLOUD_TPU_TASK_ID"] == str(i)
        assert r.value["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert r.value["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert len({r.value["TPU_PROCESS_PORT"] for r in results}) == 4

    with pytest.raises(ValueError, match=r"DataParallel\(\)"):
        LocalLauncher().run([sys.executable, script], 2, timeout=60)
    # CPU-held gangs (every other launcher test) are never restricted.
    results = LocalLauncher(env_extra={"JAX_PLATFORMS": "cpu"}).run(
        [sys.executable, script], 2, timeout=60)
    assert all(r.ok and r.value["TPU_VISIBLE_CHIPS"] is None for r in results)
