"""ZeRO-sharded data parallelism: ZeRO-1 / FSDP / grad accumulation.

Parity contract (ISSUE 4): ``ZeroDataParallel`` and ``FSDP`` change WHERE
model state lives, never what gets computed — per-step losses must match
plain ``DataParallel`` on the same batches; and ``fit(grad_accum=M)`` must
take the same optimizer trajectory as the equivalent M-times-bigger batch.
All on a 2-device slice of the 8-device CPU sim, small and short: the
tier-1 budget has ~30s of headroom total.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import distributed_tpu as dtpu
from distributed_tpu.utils.profiler import tree_bytes_per_device


def _data(n=128):
    x, y = dtpu.data.synthetic_images(n, (28, 28), 10, seed=11)
    return x[..., None].astype(np.float32) / 255.0, y.astype(np.int32)


def _model(strategy, **compile_kw):
    with strategy.scope():
        m = dtpu.Model(dtpu.models.mnist_cnn())
        m.compile(optimizer=dtpu.optim.Adam(1e-3),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"], **compile_kw)
    return m


def _step_losses(model, x, y, steps, batch=32, **fit_kw):
    """Per-optimizer-step losses via the on_batch_end log (a device scalar;
    float() syncs once per step — 10 tiny steps, cheap)."""
    losses = []
    cb = dtpu.callbacks.LambdaCallback(
        on_batch_end=lambda m, s, logs: losses.append(float(logs["loss"]))
    )
    model.fit(x, y, batch_size=batch, epochs=1, steps_per_epoch=steps,
              verbose=0, seed=5, shuffle=False, callbacks=[cb], **fit_kw)
    return losses


@pytest.fixture(scope="module")
def two_dev(devices):
    return devices[:2]


@pytest.fixture(scope="module")
def dp_run(two_dev):
    """Reference DataParallel run shared by the parity tests: per-step
    losses over 10 steps plus the fit telemetry (memory accounting)."""
    x, y = _data()
    m = _model(dtpu.DataParallel(devices=two_dev))
    losses = _step_losses(m, x, y, steps=10)
    return {"losses": losses, "telemetry": m.last_fit_telemetry,
            "x": x, "y": y}


class TestZero1:
    def test_opt_state_sharded_params_replicated(self, two_dev):
        strategy = dtpu.ZeroDataParallel(devices=two_dev)
        m = _model(strategy)
        m.build((28, 28, 1))
        assert m.params["dense"]["kernel"].sharding.spec == PartitionSpec()
        mu = m.opt_state.inner_state[0].mu["dense"]["kernel"]
        nu = m.opt_state.inner_state[0].nu["dense"]["kernel"]
        assert mu.sharding.spec == PartitionSpec("data", None)
        assert nu.sharding.spec == PartitionSpec("data", None)
        # each device holds half the rows of every Adam moment
        shapes = {s.data.shape for s in mu.addressable_shards}
        assert shapes == {(mu.shape[0] // 2, mu.shape[1])}
        # scalars (inject_hyperparams' learning_rate, the step count) and
        # indivisible shapes replicate
        lr = dtpu.optim.get_hyperparam(m.opt_state, "learning_rate")
        assert lr.sharding.spec == PartitionSpec()

    def test_matches_dp(self, dp_run, two_dev):
        """ZeRO-1 only re-places the optimizer update: same batch sharding,
        same all-reduced gradient, elementwise update math. Losses match
        DataParallel to the last float32 ULP or two (measured max diff
        2.4e-7 at step 10 — resharding changes XLA's fusion grouping, so
        strict bit equality is not a stable contract, ULP-level is)."""
        m = _model(dtpu.ZeroDataParallel(devices=two_dev))
        losses = _step_losses(m, dp_run["x"], dp_run["y"], steps=10)
        np.testing.assert_allclose(losses, dp_run["losses"],
                                   rtol=1e-6, atol=1e-7)

    def test_memory_telemetry_shows_the_win(self, dp_run, two_dev):
        """fit telemetry reports measured per-device model-state bytes;
        on Adam, ZeRO-1 over 2 devices must cut them (3x params -> 2x)."""
        m = _model(dtpu.ZeroDataParallel(devices=two_dev))
        _step_losses(m, dp_run["x"], dp_run["y"], steps=1)
        mine = m.last_fit_telemetry["model_state_bytes_per_device"]
        ref = dp_run["telemetry"]["model_state_bytes_per_device"]
        assert mine < ref * 0.75, (mine, ref)
        # allocator stats are backend-dependent; the key must exist (None
        # on XLA:CPU, a peak-bytes dict on HBM backends)
        assert "device_memory" in m.last_fit_telemetry


class TestFSDPOverData:
    def test_params_and_opt_sharded_over_data(self, two_dev):
        m = _model(dtpu.FSDP(devices=two_dev))
        m.build((28, 28, 1))
        k = m.params["dense"]["kernel"]
        assert k.sharding.spec == PartitionSpec("data", None)
        mu = m.opt_state.inner_state[0].mu["dense"]["kernel"]
        assert mu.sharding.spec == PartitionSpec("data", None)

    def test_matches_dp(self, dp_run, two_dev):
        # Param-sharded matmuls may legitimately regroup reductions
        # (contraction-dim shards psum partial products), so the contract
        # is float-tight, not bitwise.
        m = _model(dtpu.FSDP(devices=two_dev))
        losses = _step_losses(m, dp_run["x"], dp_run["y"], steps=10)
        np.testing.assert_allclose(losses, dp_run["losses"],
                                   rtol=2e-5, atol=2e-6)


class TestStateBytesAcrossTheMesh:
    """Measured per-device model state (params + Adam moments, from the
    shard buffers) of one LM on all 8 devices: what replication pays and
    each ZeRO stage saves, as exact ratios."""

    @staticmethod
    def _state_bytes(strategy):
        with strategy.scope():
            m = dtpu.Model(dtpu.models.transformer_lm(
                64, num_layers=2, d_model=64, num_heads=4, max_len=64))
            m.compile(optimizer=dtpu.optim.Adam(1e-3),
                      loss="sparse_categorical_crossentropy")
        m.build((64,))
        return tree_bytes_per_device(
            m.params, m.state, m.opt_state)["max_bytes_per_device"]

    @pytest.fixture(scope="class")
    def replicated(self, devices):
        return self._state_bytes(dtpu.DataParallel())

    @pytest.mark.parametrize("strategy_cls,saving", [
        # params whole on every device, both moments in eighths
        (dtpu.ZeroDataParallel, 3 / (1 + 2 / 8)),
        # params and both moments in eighths
        (dtpu.FSDP, 8.0),
    ])
    def test_adam_state_ratio_against_replicated(self, replicated,
                                                 strategy_cls, saving):
        # Every leaf's leading dimension divides by 8, so what keeps the
        # ratios from exact is Adam's replicated step count alone.
        sharded = self._state_bytes(strategy_cls())
        assert replicated / sharded == pytest.approx(saving, rel=1e-3)


class TestGradAccum:
    def test_matches_equivalent_big_batch(self, dp_run, two_dev):
        """fit(grad_accum=4) at batch 32 == one 32-row batch per step: the
        same rows, the same mean gradient (f32-accumulated), one optimizer
        update. Losses match the big-batch run to the last ULP or two
        (the cross-microbatch mean regroups one f32 reduction; measured
        max diff 2.4e-7 over 10 steps)."""
        m = _model(dtpu.DataParallel(devices=two_dev))
        losses = _step_losses(m, dp_run["x"], dp_run["y"], steps=10,
                              grad_accum=4)
        np.testing.assert_allclose(losses, dp_run["losses"],
                                   rtol=1e-6, atol=1e-7)

    def test_composes_with_steps_per_execution(self, dp_run, two_dev):
        """K=2 fused dispatch x M=2 accumulation: one [K*M, micro, ...]
        staging, K optimizer steps per dispatch, same losses."""
        m = _model(dtpu.DataParallel(devices=two_dev),
                   steps_per_execution=2)
        h = m.fit(dp_run["x"], dp_run["y"], batch_size=32, epochs=1,
                  steps_per_epoch=10, verbose=0, seed=5, shuffle=False,
                  grad_accum=2)
        ref = float(np.mean(dp_run["losses"]))
        assert abs(h.history["loss"][0] - ref) < 1e-6
        assert m.step == 10  # optimizer steps, not microbatches

    def test_composes_with_zero1(self, dp_run, two_dev):
        m = _model(dtpu.ZeroDataParallel(devices=two_dev))
        losses = _step_losses(m, dp_run["x"], dp_run["y"], steps=3,
                              grad_accum=2)
        # Same contract as test_matches_equivalent_big_batch: the
        # cross-microbatch mean regroups one f32 reduction (2 ULP at 2.3,
        # 4.8e-7, on jaxlib 0.9.0's XLA:CPU).
        np.testing.assert_allclose(losses, dp_run["losses"][:3],
                                   rtol=1e-6, atol=1e-7)

    def test_validation(self, two_dev):
        x, y = _data(64)
        m = _model(dtpu.DataParallel(devices=two_dev))
        with pytest.raises(ValueError, match="grad_accum"):
            m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=1,
                  verbose=0, grad_accum=0)
        with pytest.raises(ValueError, match="divide"):
            m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=1,
                  verbose=0, grad_accum=5)


class TestCheckpointUnderSharding:
    def test_zero1_resumes_with_live_learning_rate(self, two_dev, tmp_path):
        """Regression for the inject_hyperparams round-trip under sharded
        optimizer state: a ZeRO-1 run whose LR was changed at runtime must
        resume with THAT learning rate (not the compile-time one), with
        the moments coming back data-sharded."""
        x, y = _data(64)
        m = _model(dtpu.ZeroDataParallel(devices=two_dev))
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0)
        m.set_learning_rate(3.3e-4)
        ck = dtpu.Checkpointer(tmp_path)
        ck.save(m)

        m2 = _model(dtpu.ZeroDataParallel(devices=two_dev))
        assert ck.restore_into(m2) == 2
        assert abs(m2.get_learning_rate() - 3.3e-4) < 1e-9
        mu = m2.opt_state.inner_state[0].mu["dense"]["kernel"]
        assert mu.sharding.spec == PartitionSpec("data", None)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(
                m.opt_state.inner_state[0].mu["dense"]["kernel"])),
            np.asarray(jax.device_get(mu)),
        )
        # and training continues from the restored state
        m2.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=1, verbose=0,
               seed=0)
        assert m2.step == 3

    def test_restore_across_strategy_change(self, two_dev, tmp_path):
        """A checkpoint is strategy-portable: save under replicated DP,
        restore into FSDP (and back) — values identical, placement the
        LIVE strategy's."""
        x, y = _data(64)
        m = _model(dtpu.DataParallel(devices=two_dev))
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0)
        ck = dtpu.Checkpointer(tmp_path)
        ck.save(m)

        m2 = _model(dtpu.FSDP(devices=two_dev))
        ck.restore_into(m2)
        assert m2.params["dense"]["kernel"].sharding.spec == \
            PartitionSpec("data", None)
        e1 = m.evaluate(x, y, batch_size=32, verbose=0)
        e2 = m2.evaluate(x, y, batch_size=32, verbose=0)
        assert abs(e1["loss"] - e2["loss"]) < 1e-6

    def test_sharded_checkpointer_roundtrips_zero1(self, two_dev, tmp_path):
        """ShardedCheckpointer writes each unique shard block once and
        rebuilds under the live sharding — including ZeRO-1's data-sharded
        moments and the replicated hyperparams."""
        x, y = _data(64)
        m = _model(dtpu.ZeroDataParallel(devices=two_dev))
        m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0)
        m.set_learning_rate(7e-4)
        sk = dtpu.ShardedCheckpointer(tmp_path)
        sk.save(m)
        m2 = _model(dtpu.ZeroDataParallel(devices=two_dev))
        assert sk.restore_into(m2) == 2
        assert abs(m2.get_learning_rate() - 7e-4) < 1e-9
        mu = m2.opt_state.inner_state[0].mu["dense"]["kernel"]
        assert mu.sharding.spec == PartitionSpec("data", None)
