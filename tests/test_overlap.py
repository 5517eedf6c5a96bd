"""Overlapped host<->device execution (ISSUE 3): double-buffered input
prefetch + async checkpointing.

The acceptance bar: fit() with prefetch depth 2 and async checkpoints is
BIT-IDENTICAL to the synchronous path — including kill-restart-resume
through the supervisor — while the prefetch producer and checkpoint
writer threads never leak (conftest's autouse teardown asserts that after
every test here). The wall-clock win is not measured on the chip;
these tests pin the correctness half of the contract.
"""

import os
import signal
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import jax

import distributed_tpu as dtpu
from distributed_tpu.checkpoint import core as ckpt_core
from distributed_tpu.data.prefetch import DevicePrefetcher
from distributed_tpu.resilience import PreemptionHandler
from distributed_tpu.training.callbacks import (
    LambdaCallback,
    ModelCheckpoint,
)
from distributed_tpu.utils.profiler import StepTimer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_data(n=256, seed=0):
    x, y = dtpu.data.synthetic_images(n, (28, 28), 10, seed)
    return x[..., None].astype(np.float32) / 255.0, y.astype(np.int32)


def make_model(K=None, momentum=0.9):
    m = dtpu.Model(dtpu.models.mnist_cnn())
    m.compile(
        optimizer=dtpu.optim.SGD(0.05, momentum=momentum),
        loss="sparse_categorical_crossentropy",
        metrics=["accuracy"],
        steps_per_execution=K,
    )
    return m


def assert_params_equal(a, b):
    for p, q in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


# ------------------------------------------------------ DevicePrefetcher ----
class TestDevicePrefetcher:
    def test_serves_in_order_and_counts_steps(self):
        staged = []
        pf = DevicePrefetcher(lambda k: ("item", k), [2, 2, 1], depth=2)
        for want in (2, 2, 1):
            k, item = pf.get()
            assert k == want and item == ("item", want)
            staged.append(k)
        pf.close()
        assert pf.unconsumed_steps == 0

    def test_depth0_is_synchronous(self):
        calls = []
        pf = DevicePrefetcher(lambda k: calls.append(k), [1, 1, 1], depth=0)
        assert pf._thread is None  # no producer thread at depth 0
        pf.get()
        assert calls == [1]  # staged inline, exactly on demand
        pf.close()

    def test_early_close_reports_unconsumed_steps(self):
        # A slow consumer stops after one of four dispatches: the producer
        # staged ahead (depth 2) and those source steps must be reported
        # so a seekable source can rewind.
        produced = []

        def stage(k):
            produced.append(k)
            return k

        pf = DevicePrefetcher(stage, [3, 3, 3, 3], depth=2)
        k, _ = pf.get()
        time.sleep(0.3)  # let the producer fill the ring
        pf.close()
        assert k == 3
        assert pf.unconsumed_steps == sum(produced) - 3 > 0

    def test_producer_error_reraised_with_type(self):
        class Boom(RuntimeError):
            pass

        def stage(k):
            raise Boom("host prep failed")

        pf = DevicePrefetcher(stage, [1, 1], depth=2)
        with pytest.raises(Boom, match="host prep failed"):
            pf.get()
        pf.close()
        # depth 0: same contract, inline.
        pf0 = DevicePrefetcher(stage, [1, 1], depth=0)
        with pytest.raises(Boom):
            pf0.get()
        pf0.close()

    def test_close_is_idempotent_and_joins_thread(self):
        pf = DevicePrefetcher(lambda k: k, [1] * 8, depth=2)
        pf.get()
        pf.close()
        pf.close()
        assert not any(
            t.name == "dtpu-prefetch" and t.is_alive()
            for t in threading.enumerate()
        )


# ------------------------------------------------------- fit() overlap -----
class TestFitPrefetchParity:
    @pytest.mark.smoke
    def test_depth2_bitexact_vs_depth0_array_path(self):
        """ACCEPTANCE (parity half): prefetch depth 2 produces identical
        losses AND bit-identical final params to the synchronous path."""
        x, y = small_data()
        a, b = make_model(), make_model()
        ha = a.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=6,
                   verbose=0, seed=0, prefetch=0)
        hb = b.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=6,
                   verbose=0, seed=0, prefetch=2)
        assert ha.history["loss"] == hb.history["loss"]
        assert ha.history["accuracy"] == hb.history["accuracy"]
        assert_params_equal(a, b)

    # @slow (tier-1 budget, PR 17): ~5s prefetch x K x tail cross-
    # product; depth-2 bit-exactness and the tail schedule stay in-tier
    # in this class — this pins only the three-way composition.
    @pytest.mark.slow
    def test_depth2_bitexact_under_multi_step_with_tail(self):
        """Prefetch composes with steps_per_execution=K, including the
        tail dispatch smaller than K (steps_per_epoch=5, K=4 -> 4+1)."""
        x, y = small_data()
        a, b = make_model(4), make_model(4)
        a.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=5, verbose=0,
              seed=0, prefetch=0)
        b.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=5, verbose=0,
              seed=0, prefetch=2)
        assert a.step == b.step == 10
        assert_params_equal(a, b)

    def test_depth2_bitexact_pipeline_source(self):
        x, y = dtpu.data.synthetic_images(256, (28, 28), 10, seed=2)

        def run(depth):
            m = make_model(momentum=0.0)
            with dtpu.data.Pipeline(x[..., None], y, 32, seed=5,
                                    use_native=False) as p:
                m.fit(p, epochs=2, verbose=0, prefetch=depth)
            return m

        assert_params_equal(run(0), run(2))

    def test_prefetch_env_default_and_zero(self, monkeypatch):
        """fit(prefetch=None) reads DTPU_PREFETCH_DEPTH (default 2); the
        loop accepts 0 and negative values clamp to synchronous."""
        x, y = small_data(n=64)
        monkeypatch.setenv("DTPU_PREFETCH_DEPTH", "0")
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0)
        assert m.step == 2
        m2 = make_model()
        m2.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
               seed=0, prefetch=-3)
        assert_params_equal(m, m2)

    def test_stop_training_rewinds_seekable_source(self):
        """A mid-epoch stop leaves the prefetcher holding staged batches;
        a seekable source is rewound so its cursor equals the steps the
        model actually trained — resume alignment preserved."""
        x, y = dtpu.data.synthetic_images(256, (28, 28), 10, seed=3)
        p = dtpu.data.Pipeline(x[..., None], y, 32, seed=1,
                               use_native=False)
        m = make_model()
        stop = LambdaCallback(
            on_batch_end=lambda mm, s, logs: setattr(
                mm, "stop_training", s >= 3)
        )
        m.fit(p, epochs=2, verbose=0, callbacks=[stop], prefetch=2)
        assert m.step == 3
        assert p.steps_emitted == 3  # rewound past the staged lookahead
        p.close()

    def test_telemetry_attributes_stall_buckets(self):
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=4, verbose=0,
              seed=0, prefetch=2)
        t = m.last_fit_telemetry
        assert set(t) >= {"input_wait", "dispatch", "checkpoint_wait",
                          "total_seconds", "input_stall_fraction"}
        assert t["dispatch"] > 0  # donated dispatches wait on the device
        assert 0.0 <= t["input_stall_fraction"] <= 1.0
        assert t["total_seconds"] >= t["input_wait"]
        assert m._stall_timer is None  # detached at fit end


# -------------------------------------------------- async checkpointing ----
class TestAsyncCheckpointer:
    def test_async_save_lands_after_wait_and_restores(self, tmp_path):
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=3, verbose=0,
              seed=0, prefetch=0)
        ck = dtpu.Checkpointer(tmp_path, async_save=True)
        ck.save(m)
        ck.wait()
        assert ck.all_steps() == [3]
        assert ck.latest_step() == 3
        restored = make_model()
        assert ck.restore_into(restored) == 3
        assert_params_equal(m, restored)

    def test_async_snapshot_is_donation_safe(self, tmp_path):
        """The step that runs AFTER save() donates the params buffers the
        snapshot copied — the written checkpoint must hold the values at
        save time, not the post-donation ones."""
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=3, verbose=0,
              seed=0, prefetch=0)
        want = [np.asarray(l).copy()
                for l in jax.tree_util.tree_leaves(m.params)]
        ck = dtpu.Checkpointer(tmp_path, async_save=True)
        ck.save(m)  # returns before the write; snapshot taken on device
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=3, verbose=0,
              seed=0, prefetch=0)  # donates the original buffers
        ck.wait()
        tree, meta = ckpt_core.load_npz(tmp_path / "ckpt-3.npz")
        got = jax.tree_util.tree_leaves(tree["params"])
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)

    def test_newer_save_waits_out_older_write(self, tmp_path):
        """Same step family: save(step=N+1) must not race the in-flight
        write of step N for the latest pointer."""
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0, prefetch=0)
        ck = dtpu.Checkpointer(tmp_path, keep=5, async_save=True)
        ck.save(m, step=2)
        ck.save(m, step=4)  # waits out the step-2 writer first
        ck.wait()
        assert ck.all_steps() == [2, 4]
        assert ck._read_latest_pointer() == 4

    def test_writer_error_surfaces_at_wait(self, tmp_path, monkeypatch):
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0, prefetch=0)
        ck = dtpu.Checkpointer(tmp_path, async_save=True)

        def boom(*a, **kw):
            raise OSError("disk full")

        monkeypatch.setattr(ckpt_core, "save_npz", boom)
        ck.save(m)
        with pytest.raises(OSError, match="disk full"):
            ck.wait()
        ck.wait()  # error is consumed, not re-raised forever

    def test_corrupt_latest_fallback_still_works(self, tmp_path):
        """PR 2's corrupt-latest fallback composes with the async writer:
        auto-restore skips a clobbered newest file and falls back."""
        x, y = small_data(n=128)
        ck = dtpu.Checkpointer(tmp_path, async_save=True)
        m = make_model()
        for steps in (2, 2):
            m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=steps,
                  verbose=0, seed=0, prefetch=0)
            ck.save(m)
        ck.wait()
        assert ck.all_steps() == [2, 4]
        (tmp_path / "ckpt-4.npz").write_bytes(b"torn garbage")
        restored = make_model()
        assert ck.restore_into(restored) == 2

    def test_fit_parity_async_ckpt_plus_prefetch(self, tmp_path):
        """ACCEPTANCE: fit with prefetch depth 2 + async ModelCheckpoint
        matches the fully synchronous run bit-exactly, and the directory
        is complete (flushed) the moment fit returns."""
        x, y = small_data()
        a = make_model()
        a.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=4, verbose=0,
              seed=1, prefetch=0,
              callbacks=[ModelCheckpoint(tmp_path / "sync",
                                         save_freq="epoch")])
        b = make_model()
        b.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=4, verbose=0,
              seed=1, prefetch=2,
              callbacks=[ModelCheckpoint(tmp_path / "async",
                                         save_freq="epoch",
                                         async_save=True)])
        assert_params_equal(a, b)
        # Writer flushed at train end: both dirs hold the same steps NOW.
        assert (dtpu.Checkpointer(tmp_path / "sync").all_steps()
                == dtpu.Checkpointer(tmp_path / "async").all_steps()
                == [4, 8])
        ra = make_model()
        dtpu.Checkpointer(tmp_path / "async").restore_into(ra)
        assert_params_equal(a, ra)

    def test_sharded_async_is_supported_and_buddy_needs_sharded(
            self, tmp_path):
        """The old sharded+async restriction is LIFTED (ISSUE 13: the
        shard write backgrounds, the cross-host commit defers to the next
        main-thread wait — tests/test_sharded_checkpoint.py pins the
        mechanics); the buddy tier still requires the sharded format."""
        ModelCheckpoint(tmp_path, sharded=True, async_save=True)  # no raise
        dtpu.checkpoint.ShardedCheckpointer(tmp_path).wait()  # no-op
        with pytest.raises(ValueError, match="sharded=True"):
            ModelCheckpoint(tmp_path, buddy=tmp_path / "store")


# ------------------------------------------------------- preemption flush ---
class TestPreemptionFlush:
    def test_preemption_flushes_async_writers_before_marker(self, tmp_path):
        """SIGTERM with an async ModelCheckpoint live: every background
        write lands, THEN the final checkpoint saves synchronously — the
        newest step on disk is the preemption step, complete and
        loadable, before fit returns (in-process mode stands in for the
        exit-75 path, same flush ordering)."""
        x, y = small_data()
        send = LambdaCallback(
            on_batch_end=lambda m, s, logs: (
                os.kill(os.getpid(), signal.SIGTERM) if s == 5 else None
            )
        )
        handler = PreemptionHandler(tmp_path, exit_code=None)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=4, verbose=0,
              seed=7, prefetch=2,
              callbacks=[ModelCheckpoint(tmp_path, save_freq=2,
                                         async_save=True), send, handler])
        assert handler.triggered and m.step == 5
        ck = dtpu.Checkpointer(tmp_path)
        assert ck.latest_step() == 5
        assert ck.is_valid(5)  # complete npz, not a torn async tail
        restored = make_model()
        assert ck.restore_into(restored) == 5

    def test_wait_all_async_is_global_barrier(self, tmp_path):
        x, y = small_data(n=128)
        m = make_model()
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0, prefetch=0)
        cks = [dtpu.Checkpointer(tmp_path / f"d{i}", async_save=True)
               for i in range(3)]
        for ck in cks:
            ck.save(m)
        ckpt_core.wait_all_async()
        for ck in cks:
            assert ck.all_steps() == [2]
        assert not any(
            t.name == "dtpu-ckpt-writer" and t.is_alive()
            for t in threading.enumerate()
        )


# ----------------------------------------------- supervisor end to end -----
OVERLAP_WORKER = """
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import distributed_tpu as dtpu
    from distributed_tpu.launch import report_result
    from distributed_tpu.resilience import FaultInjector
    from distributed_tpu.training.callbacks import ModelCheckpoint

    CKPT = os.environ["TEST_CKPT_DIR"]
    x, y = dtpu.data.synthetic_images(256, (28, 28), 10, 0)
    x = x[..., None].astype(np.float32) / 255.0
    m = dtpu.Model(dtpu.models.mnist_cnn())
    m.compile(optimizer=dtpu.optim.SGD(0.05), metrics=["accuracy"])
    cbs = [ModelCheckpoint(CKPT, save_freq=3, restore=True,
                           async_save=os.environ.get("TEST_ASYNC") == "1")]
    fault = FaultInjector.from_env()
    if fault is not None:
        cbs.append(fault)
    hist = m.fit(x, y.astype(np.int32), batch_size=64, epochs=2,
                 steps_per_epoch=4, verbose=0, seed=0, callbacks=cbs,
                 prefetch=int(os.environ.get("TEST_PREFETCH", "2")))
    leaf = np.asarray(jax.tree_util.tree_leaves(m.params)[0]).ravel()[:4]
    report_result({{"loss": hist.metrics["loss"][-1],
                   "leaf": [float(v) for v in leaf]}})
    """


# @slow (tier-1 budget, PR 16): ~11s subprocess e2e; the supervised
# kill-restart-resume path stays in tier-1 via test_resilience.py's
# parity acceptance, and prefetch/async resume correctness is covered
# by the in-process resume tests above.
@pytest.mark.slow
def test_supervisor_kill_restart_resume_with_overlap(tmp_path):
    """ACCEPTANCE (end to end): a supervised worker running fit with
    prefetch depth 2 + async checkpoints is fault-killed mid-run; the
    supervisor restarts it, the checkpoint resumes, and the final params
    match a fully synchronous uninterrupted run bit-for-bit."""
    from distributed_tpu.launch import LocalLauncher
    from distributed_tpu.resilience import RestartPolicy, Supervisor
    from distributed_tpu.utils.events import EventLog

    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(OVERLAP_WORKER.format(repo=REPO)))

    # Reference: synchronous (prefetch 0, sync saves), uninterrupted.
    ref = LocalLauncher(env_extra={
        "TEST_CKPT_DIR": str(tmp_path / "ck_ref"),
        "TEST_PREFETCH": "0",
        "TEST_ASYNC": "0",
    }).run([sys.executable, str(script)], 1, timeout=300)
    assert ref[0].ok, (ref[0].error, ref[0].log_tail[-600:])

    log = EventLog(tmp_path / "events.jsonl")
    sup = Supervisor(
        [sys.executable, str(script)], 1,
        policy=RestartPolicy(max_restarts=2, backoff=0.05, backoff_max=0.1),
        checkpoint_dir=tmp_path / "ck",
        event_log=log,
        env_extra={
            "TEST_CKPT_DIR": str(tmp_path / "ck"),
            "TEST_PREFETCH": "2",
            "TEST_ASYNC": "1",
            "DTPU_FAULT": "kill:at_step=5",  # mid-epoch-2 (4 steps/epoch)
            "DTPU_FAULT_MARKER": str(tmp_path / "fault_once"),
        },
    )
    out = sup.run(timeout=300, grace=5)
    assert out.ok, [(r.index, r.error, r.log_tail[-600:])
                    for r in out.results]
    assert out.attempts == 2 and out.restarts_used == 1
    value = out.results[0].value
    assert value["loss"] == pytest.approx(ref[0].value["loss"], rel=1e-6)
    np.testing.assert_allclose(value["leaf"], ref[0].value["leaf"],
                               rtol=1e-6)
    restart = next(e for e in log.read() if e["event"] == "restart")
    # The async save at step 3 was fully flushed before the kill at 5.
    assert restart["resume_step"] == 3
