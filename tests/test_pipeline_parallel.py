"""Pipeline parallelism: PipelinedBlocks + DataPipelineParallel.

Beyond-reference capability (SURVEY.md §2c "Pipeline parallelism: NO"):
the GPipe microbatch schedule must match single-device numerics exactly
(same stacked params, scan vs schedule), shard one-stage-per-rank, and
train end-to-end through fit/evaluate on the 8-device CPU sim.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

import distributed_tpu as dtpu
from distributed_tpu import nn

VOCAB = 64


def _lm(num_layers=4, **kw):
    kw.setdefault("d_model", 32)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_len", 16)
    return dtpu.models.transformer_lm(
        VOCAB, num_layers=num_layers, pipeline=True, **kw
    )


def _copy_task(n, t, seed=0):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, VOCAB, size=n)
    pos = np.arange(t + 1)[None, :]
    toks = (starts[:, None] + pos) % VOCAB
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def _mlp_block():
    return nn.Sequential(
        [nn.Dense(16, activation="gelu"), nn.Dense(8)], name="main"
    )


class TestPipelinedBlocksLayer:
    def test_scan_matches_unrolled(self):
        layer = nn.PipelinedBlocks(_mlp_block, 3)
        params, state, out = layer.init(jax.random.PRNGKey(0), (8,))
        assert out == (8,)
        assert state == {}
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
        y, _ = layer.apply(params, state, x)
        # unrolled reference: apply each stage's slice in order
        h = x
        block = _mlp_block()
        block.init(jax.random.PRNGKey(0), (8,))  # finalize names
        for i in range(3):
            p_i = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            h, _ = block.apply(p_i, {}, h)
        np.testing.assert_allclose(np.asarray(y), np.asarray(h),
                                   rtol=1e-5, atol=1e-6)

    def test_stage_params_differ(self):
        layer = nn.PipelinedBlocks(_mlp_block, 2)
        params, _, _ = layer.init(jax.random.PRNGKey(0), (8,))
        kernel = params["blocks"]["dense"]["kernel"]
        assert not np.allclose(kernel[0], kernel[1])  # distinct stage init

    def test_shape_changing_block_rejected(self):
        bad = lambda: nn.Dense(5)
        with pytest.raises(ValueError, match="preserve shape"):
            nn.PipelinedBlocks(bad, 2).init(jax.random.PRNGKey(0), (8,))

    def test_stateful_block_rejected(self):
        bad = lambda: nn.BatchNorm()
        with pytest.raises(ValueError, match="stateless"):
            nn.PipelinedBlocks(bad, 2).init(jax.random.PRNGKey(0), (8,))

    def test_hints(self):
        layer = nn.PipelinedBlocks(_mlp_block, 2)
        assert layer.sharding_hints() == {"blocks": "pipe"}

    def test_dtype_changing_block_carries(self):
        # bf16-compute blocks in an f32 stream: output cast back to carry
        # dtype, like any mixed-precision layer.
        mk = lambda: nn.Dense(8, dtype=jnp.bfloat16)
        layer = nn.PipelinedBlocks(mk, 2)
        params, state, _ = layer.init(jax.random.PRNGKey(0), (8,))
        y, _ = layer.apply(params, state, jnp.zeros((4, 8), jnp.float32))
        assert y.dtype == jnp.float32

    def test_num_microbatches_validated(self, devices):
        with pytest.raises(ValueError, match="num_microbatches"):
            dtpu.DataPipelineParallel(pipeline_parallel=2, num_microbatches=0)

    def test_dropout_block_trains_under_pp(self, devices):
        mk = lambda: nn.Sequential(
            [nn.Dense(16, activation="gelu"), nn.Dropout(0.1), nn.Dense(8)],
            name="main",
        )
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
        with strategy.scope():
            model = dtpu.Model(nn.Sequential(
                [nn.PipelinedBlocks(mk, 2), nn.Dense(4)]))
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=32).astype(np.int32)
        hist = model.fit(x, y, batch_size=16, epochs=2, verbose=0)
        assert all(np.isfinite(hist.history["loss"]))


class TestDataPipelineParallel:
    def test_param_shardings(self, devices):
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
        with strategy.scope():
            model = dtpu.Model(_lm())
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        model.build((16,))
        stacked = model.params["pipelined_blocks"]["blocks"]
        for leaf in jax.tree_util.tree_leaves(stacked):
            assert leaf.sharding.spec[0] == "pipe", leaf.sharding
        # non-pipelined params replicated
        emb = model.params["embedding"]["table"]
        assert emb.sharding.spec == PartitionSpec()

    # pp4 @slow (tier-1 budget, PR 16): each pipeline width compiles its
    # own ~7s program and the parity property is identical; pp2 @slow
    # too since PR 19 — TestInterleavedSchedule::
    # test_parity_bubble_and_telemetry pins the SAME pp2 gpipe-vs-
    # single-device parity at the tighter rtol 2e-5 in-tier, so this
    # cell's coverage is retained there (and here via -m slow when
    # touching the schedule).
    @pytest.mark.slow
    @pytest.mark.parametrize("pp,mb", [
        (2, 2),
        (4, 4),
    ], ids=["pp2", "pp4"])
    def test_pp_matches_single_device(self, devices, pp, mb):
        x, y = _copy_task(64, 16, seed=3)

        def train(strategy):
            def mk():
                m = dtpu.Model(_lm())
                m.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy",
                          metrics=["accuracy"])
                return m

            if strategy is None:
                model = mk()
            else:
                with strategy.scope():
                    model = mk()
            hist = model.fit(x, y, batch_size=32, epochs=2, verbose=0,
                             seed=7, shuffle=False)
            return hist.history["loss"]

        ref = train(None)
        pipe = train(dtpu.DataPipelineParallel(
            pipeline_parallel=pp, num_microbatches=mb))
        np.testing.assert_allclose(ref, pipe, rtol=2e-4, atol=2e-5)

    def test_evaluate_under_pp(self, devices):
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
        with strategy.scope():
            model = dtpu.Model(_lm())
            model.compile(optimizer=dtpu.optim.Adam(1e-3),
                          loss="sparse_categorical_crossentropy",
                          metrics=["accuracy"])
        model.build((16,))
        x, y = _copy_task(32, 16, seed=5)
        ref = dtpu.Model(_lm())
        ref.compile(optimizer=dtpu.optim.Adam(1e-3),
                    loss="sparse_categorical_crossentropy",
                    metrics=["accuracy"])
        ref.build((16,))
        want = ref.evaluate(x, y, batch_size=8, verbose=0)
        got = model.evaluate(x, y, batch_size=8, verbose=0)
        assert got["loss"] == pytest.approx(want["loss"], rel=1e-4)

    def test_blocks_not_divisible_by_stages(self, devices):
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=4)
        with strategy.scope():
            model = dtpu.Model(_lm(num_layers=3))
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        x, y = _copy_task(32, 16)
        with pytest.raises(ValueError, match="not divisible"):
            model.fit(x, y, batch_size=16, epochs=1, verbose=0)

    def test_batch_not_divisible_by_microbatches(self, devices):
        strategy = dtpu.DataPipelineParallel(
            pipeline_parallel=2, num_microbatches=3)
        with strategy.scope():
            model = dtpu.Model(_lm(num_layers=2))
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        x, y = _copy_task(32, 16)
        with pytest.raises(ValueError, match="microbatches"):
            model.fit(x, y, batch_size=16, epochs=1, verbose=0)

    # @slow (tier-1 budget, PR 17): ~7s convergence drive; pipeline
    # numerics stay in-tier via TestInterleavedSchedule::
    # test_parity_bubble_and_telemetry (rtol 2e-5, since PR 19) and
    # copy-task convergence of the same stack stays in-tier via
    # TestTransformerTraining::test_learns_copy_task (test_transformer.py).
    @pytest.mark.slow
    def test_learns_copy_task(self, devices):
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
        with strategy.scope():
            model = dtpu.Model(_lm())
            model.compile(optimizer=dtpu.optim.Adam(1e-2),
                          loss="sparse_categorical_crossentropy",
                          metrics=["accuracy"])
        x, y = _copy_task(256, 16)
        hist = model.fit(x, y, batch_size=64, epochs=6, verbose=0, seed=1)
        assert hist.history["accuracy"][-1] > 0.7, hist.history


class TestInterleavedSchedule:
    """The virtual-stage schedule: each pipe rank holds ``interleave``
    non-contiguous stage chunks and activations circulate ``interleave``
    laps over the full ring, shrinking the bubble from (n-1)/(M+n-1) to
    (n-1)/(vM+n-1) at the SAME microbatch count."""

    def test_schedule_validation(self):
        with pytest.raises(ValueError, match="schedule"):
            nn.PipelinedBlocks(_mlp_block, 4, schedule="zigzag")
        with pytest.raises(ValueError, match="interleave"):
            nn.PipelinedBlocks(_mlp_block, 4, schedule="gpipe",
                               interleave=2)
        with pytest.raises(ValueError, match="interleave"):
            nn.PipelinedBlocks(_mlp_block, 4, schedule="interleaved",
                               interleave=1)

    def test_blocks_divisible_by_stages_times_interleave(self, devices):
        # 6 blocks cannot chunk into 2 ranks x 2 virtual stages.
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=2)
        with strategy.scope():
            model = dtpu.Model(_lm(num_layers=6,
                                   pipeline_schedule="interleaved",
                                   pipeline_interleave=2))
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        x, y = _copy_task(32, 16)
        with pytest.raises(ValueError, match="not divisible"):
            model.fit(x, y, batch_size=16, epochs=1, verbose=0)

    def test_microbatches_must_cover_stages(self, devices):
        # v > 1 re-injects lap outputs at rank 0 slot (t - n) mod M, which
        # needs M >= n — fewer microbatches than ranks must raise loudly.
        strategy = dtpu.DataPipelineParallel(pipeline_parallel=4,
                                             num_microbatches=2)
        with strategy.scope():
            model = dtpu.Model(_lm(num_layers=8,
                                   pipeline_schedule="interleaved",
                                   pipeline_interleave=2))
            model.compile(optimizer=dtpu.optim.SGD(0.1),
                          loss="sparse_categorical_crossentropy")
        x, y = _copy_task(32, 16)
        with pytest.raises(ValueError, match="num_microbatches"):
            model.fit(x, y, batch_size=16, epochs=1, verbose=0)

    def _train(self, schedule, interleave, *, strategy, grad_accum=1,
               precision=None, x=None, y=None):
        def mk():
            m = dtpu.Model(_lm(pipeline_schedule=schedule,
                               pipeline_interleave=interleave))
            m.compile(optimizer=dtpu.optim.SGD(0.1),
                      loss="sparse_categorical_crossentropy",
                      precision=precision)
            return m

        if strategy is None:
            model = mk()
        else:
            with strategy.scope():
                model = mk()
        hist = model.fit(x, y, batch_size=32, epochs=2, verbose=0, seed=7,
                         shuffle=False, grad_accum=grad_accum)
        return hist.history["loss"], model

    def test_parity_bubble_and_telemetry(self, devices, tmp_path,
                                         monkeypatch):
        """The tentpole's acceptance triple in one compile budget: the
        interleaved schedule's loss trajectory matches gpipe AND the
        single-device sequential path at rtol 2e-5; its telemetry bubble
        is strictly below gpipe's at the same M; and the fit emits the
        schedule/bubble events with the declared keys."""
        monkeypatch.setenv("DTPU_EVENT_LOG",
                           str(tmp_path / "events.jsonl"))
        x, y = _copy_task(64, 16, seed=3)
        ref, _ = self._train("gpipe", 1, strategy=None, x=x, y=y)
        gp, m_gp = self._train(
            "gpipe", 1, x=x, y=y,
            strategy=dtpu.DataPipelineParallel(pipeline_parallel=2,
                                               num_microbatches=4))
        il, m_il = self._train(
            "interleaved", 2, x=x, y=y,
            strategy=dtpu.DataPipelineParallel(pipeline_parallel=2,
                                               num_microbatches=4))
        np.testing.assert_allclose(gp, ref, rtol=2e-5)
        np.testing.assert_allclose(il, ref, rtol=2e-5)
        tg = m_gp.last_fit_telemetry["pipeline"]
        ti = m_il.last_fit_telemetry["pipeline"]
        assert tg == {"schedule": "gpipe", "interleave": 1, "num_stages": 2,
                      "num_microbatches": 4, "ticks": 5,
                      "bubble_fraction": 0.2}
        assert ti == {"schedule": "interleaved", "interleave": 2,
                      "num_stages": 2, "num_microbatches": 4, "ticks": 9,
                      "bubble_fraction": round(1 / 9, 6)}
        assert ti["bubble_fraction"] < tg["bubble_fraction"]
        import json as _json
        rows = [_json.loads(l) for l in
                (tmp_path / "events.jsonl").read_text().splitlines()]
        sched = [r for r in rows
                 if r["event"] == "pipeline_schedule_selected"]
        bub = [r for r in rows if r["event"] == "bubble_report"]
        assert {s["schedule"] for s in sched} == {"gpipe", "interleaved"}
        assert {b["bubble_fraction"] for b in bub} == {0.2, round(1 / 9, 6)}

    # Heavy matrix cells @slow (tier-1 budget): each is another pair of
    # ~5s pipeline compiles and the parity property is the one the base
    # cell above already pins; grad_accum and precision only re-route the
    # SAME schedule through the accumulation scan / cast policy.
    @pytest.mark.slow
    @pytest.mark.parametrize("grad_accum,precision,rtol", [
        (2, None, 2e-5),
        # bf16 compute reorders reductions between the schedules, so the
        # parity band is the compute dtype's, not f32's.
        (1, "mixed_bfloat16", 2e-2),
    ], ids=["accum2", "bf16"])
    def test_parity_matrix_heavy(self, devices, grad_accum, precision,
                                 rtol):
        x, y = _copy_task(64, 16, seed=3)
        gp, _ = self._train(
            "gpipe", 1, x=x, y=y, grad_accum=grad_accum,
            precision=precision,
            strategy=dtpu.DataPipelineParallel(pipeline_parallel=2,
                                               num_microbatches=4))
        il, _ = self._train(
            "interleaved", 2, x=x, y=y, grad_accum=grad_accum,
            precision=precision,
            strategy=dtpu.DataPipelineParallel(pipeline_parallel=2,
                                               num_microbatches=4))
        np.testing.assert_allclose(il, gp, rtol=rtol)
