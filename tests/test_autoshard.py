"""Auto-shard planner (parallel/auto_shard.py; docs/API.md "Autotuned
sharding"): the unified comm schema every strategy now reports, abstract
byte accounting (live == dry-run), feasibility pruning under a synthetic
HBM cap (replicated state over it, FSDP's under it), plan determinism, and
``compile(strategy="auto")`` end-to-end on a 2-device mesh. The measured-
shortlist path (``measure=True``) is @slow — in-tier planner tests stay
estimate-only (no dispatch sweeps) per the tier-1 time budget.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu.parallel import (
    Candidate,
    Feasibility,
    Plan,
    plan_sharding,
)
from distributed_tpu.parallel.strategy import _params_sharding_tree
from distributed_tpu.utils.profiler import tree_bytes_per_device

SEQ = 16
LM_KW = dict(vocab=128, num_layers=1, d_model=32, num_heads=2, max_len=SEQ)


def _lm(**overrides):
    kw = dict(LM_KW)
    kw.update(overrides)
    vocab = kw.pop("vocab")
    mod = dtpu.models.transformer_lm(vocab, **kw)
    if mod.name is None:
        mod.name = mod.default_name()
    return mod


def _compiled_auto_model(module, **compile_kw):
    m = dtpu.Model(module)
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
              **compile_kw)
    return m


# ------------------------------------------------------------- comm schema --
class TestCommSchema:
    KEYS = {
        "gathered_param_bytes_per_device",
        "grad_reduce_bytes_per_device",
        "activation_reduce_bytes_per_token_per_device",
        "pipeline_hop_bytes_per_token_per_device",
    }

    def _strategies(self):
        return {
            "single_device": dtpu.SingleDevice(),
            "dp": dtpu.DataParallel(),
            "zero1": dtpu.ZeroDataParallel(),
            "fsdp": dtpu.FSDP(),
            "tp": dtpu.DataTensorParallel(model_parallel=2),
            "pp": dtpu.DataPipelineParallel(pipeline_parallel=2),
        }

    def test_unified_keys_across_all_strategies(self):
        """Satellite 1: SingleDevice/DP/ZeRO-1/FSDP/TP return the SAME
        keys — zeros where a collective doesn't apply — so planner rows
        compare apples-to-apples."""
        mod = _lm()
        params, _, _ = mod.init(jax.random.PRNGKey(0), (SEQ,))
        hints = mod.sharding_hints()
        for name, strat in self._strategies().items():
            est = strat.comm_bytes_estimate(params, hints=hints)
            assert set(est) == self.KEYS, name
            assert all(v >= 0 for v in est.values()), name
        single = dtpu.SingleDevice().comm_bytes_estimate(params)
        assert all(v == 0 for v in single.values())
        dp = dtpu.DataParallel().comm_bytes_estimate(params)
        assert dp["gathered_param_bytes_per_device"] == 0
        assert dp["grad_reduce_bytes_per_device"] > 0
        assert dp["activation_reduce_bytes_per_token_per_device"] == 0

    def test_int8_priced_in_every_strategy(self):
        """Satellite 1: int8 weight leaves price at 1 byte/elem in DP's
        grad reduce and ZeRO-1's gather too, not just FSDP gathers."""
        from distributed_tpu import quant

        mod = _lm()
        params, _, _ = mod.init(jax.random.PRNGKey(0), (SEQ,))
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), params)
        qtree = quant.quantize_tree(host)
        for strat in (dtpu.DataParallel(), dtpu.ZeroDataParallel(),
                      dtpu.FSDP()):
            f32 = strat.comm_bytes_estimate(host)
            q = strat.comm_bytes_estimate(qtree)
            for key in ("grad_reduce_bytes_per_device",
                        "gathered_param_bytes_per_device"):
                if f32[key]:
                    # int8 payloads + f32 scales/biases: strictly below
                    # f32, and below half (weights dominate this tree).
                    assert q[key] < f32[key] * 0.5, (type(strat), key)

    def test_tp_prices_activation_reduces_and_shard_grads(self):
        mod = _lm()
        params, _, _ = mod.init(jax.random.PRNGKey(0), (SEQ,))
        hints = mod.sharding_hints()
        tp = dtpu.DataTensorParallel(model_parallel=2)
        est = tp.comm_bytes_estimate(params, hints=hints)
        dp = dtpu.DataParallel().comm_bytes_estimate(params)
        # Megatron row-parallel matmuls all-reduce activations...
        assert est["activation_reduce_bytes_per_token_per_device"] > 0
        # ...never gather their weights...
        assert est["gathered_param_bytes_per_device"] == 0
        # ...and TP-sharded leaves reduce shard-sized gradient pieces.
        assert 0 < est["grad_reduce_bytes_per_device"] \
            < dp["grad_reduce_bytes_per_device"]
        # Without hints the estimate degenerates to DP's (cannot know
        # which leaves shard).
        blind = tp.comm_bytes_estimate(params)
        assert blind["grad_reduce_bytes_per_device"] == \
            dp["grad_reduce_bytes_per_device"]


# --------------------------------------------------- abstract byte parity --
class TestAbstractBytes:
    def _abstract(self, mod, tx):
        key = jax.random.PRNGKey(0)
        params, state = jax.eval_shape(
            lambda k: mod.init(k, (SEQ,))[:2], key)
        opt = jax.eval_shape(tx.init, params)
        return params, state, opt

    @pytest.mark.parametrize("strategy_cls",
                             [dtpu.FSDP, dtpu.ZeroDataParallel])
    def test_live_equals_abstract_on_sharded_tree(self, strategy_cls):
        """Satellite 2: tree_bytes_per_device over abstract SDS trees with
        the strategy's shardings attached must equal the LIVE measurement
        of the same tree placed for real — the contract that lets the
        planner price candidates without materializing them."""
        from distributed_tpu.parallel.auto_shard import _attach_shardings

        strategy = strategy_cls()
        with strategy.scope():
            m = _compiled_auto_model(_lm())
        m.build((SEQ,))
        live = tree_bytes_per_device(m.params, m.state, m.opt_state)

        mod = _lm()
        hints = mod.sharding_hints()
        params, state, opt = self._abstract(mod, m.tx)
        params_sh = _params_sharding_tree(strategy, params, hints)
        state_sh = _params_sharding_tree(strategy, state, None)
        opt_sh = strategy.opt_state_sharding(opt, params, hints)
        predicted = tree_bytes_per_device(
            _attach_shardings(params, params_sh),
            _attach_shardings(state, state_sh),
            _attach_shardings(opt, opt_sh),
        )
        assert predicted["max_bytes_per_device"] == \
            live["max_bytes_per_device"]
        assert predicted["total_bytes"] == live["total_bytes"]

    def test_opt_state_sharding_matches_eager_init(self):
        """The opt_state_sharding seam predicts exactly the placement
        init_opt_state produces eagerly (specs compared leaf-by-leaf)."""
        for strategy in (dtpu.FSDP(), dtpu.ZeroDataParallel()):
            with strategy.scope():
                m = _compiled_auto_model(_lm())
            m.build((SEQ,))
            mod = _lm()
            params, _, opt = self._abstract(mod, m.tx)
            predicted = strategy.opt_state_sharding(
                opt, params, mod.sharding_hints())
            for live_leaf, pred_sh in zip(
                jax.tree_util.tree_leaves(m.opt_state),
                jax.tree_util.tree_leaves(predicted),
            ):
                assert live_leaf.sharding.spec == pred_sh.spec, (
                    type(strategy).__name__, live_leaf.shape)

    def test_abstract_leaf_without_sharding_counts_once(self):
        sds = jax.ShapeDtypeStruct((8, 4), jnp.float32)
        out = tree_bytes_per_device({"a": sds})
        assert out["max_bytes_per_device"] == out["total_bytes"] == 128


# ------------------------------------------------------------- feasibility --
class TestFeasibility:
    def test_predicate(self):
        f = Feasibility(hbm_cap_bytes=1000)
        assert f.check(900, 100) is None
        reason = f.check(900, 200)
        assert reason is not None and "hbm_cap 1000" in reason
        assert Feasibility(None).check(10**15) is None

    def test_cap_prunes_replicated_keeps_fsdp(self):
        """The model that only sharding can hold: under a cap between
        the replicated and FSDP footprints, replicated DP is pruned WITH
        rationale and FSDP survives + wins (estimate-only — no tree is
        materialized)."""
        mod = _lm(vocab=512, d_model=64)
        pre = plan_sharding(mod, (SEQ,), optimizer="adam", batch_size=16,
                            grad_accums=(1,), steps_per_execution=(1,),
                            include_tp=False)
        by = {r["config"]["strategy"]: r for r in pre.candidates}
        cap = (by["dp"]["state_bytes_per_device"]
               + by["fsdp"]["state_bytes_per_device"]) // 2
        plan = plan_sharding(mod, (SEQ,), optimizer="adam", batch_size=16,
                             hbm_cap_bytes=cap, grad_accums=(1,),
                             steps_per_execution=(1,), include_tp=False)
        assert plan.chosen["config"]["strategy"] == "fsdp"
        pruned = {r["config"]["strategy"]: r for r in plan.pruned
                  if "config" in r}
        assert "dp" in pruned and "single_device" in pruned
        assert f"hbm_cap {cap}" in pruned["dp"]["reason"]
        assert pruned["dp"]["state_bytes_per_device"] > cap
        # The tie band (zero1 also fits here) broke toward HBM headroom.
        assert plan.tie_break in ("hbm_headroom", "simplicity")

    def test_no_feasible_candidate_raises(self):
        with pytest.raises(ValueError, match="NO feasible"):
            plan_sharding(_lm(), (SEQ,), optimizer="adam", batch_size=16,
                          hbm_cap_bytes=16)

    def test_batch_indivisible_prunes_data_parallel(self):
        # batch 3 divides by no multi-device replica count on the 8-dev
        # sim: every row-sharding strategy is pruned with the batch
        # rationale. Without TP the only survivor is single_device; with
        # TP allowed, a full-TP mesh (data axis 1) legitimately rescues
        # the batch and still uses every device.
        plan = plan_sharding(_lm(), (SEQ,), optimizer="adam", batch_size=3,
                             grad_accums=(1,), steps_per_execution=(1,),
                             include_tp=False)
        assert plan.chosen["config"]["strategy"] == "single_device"
        reasons = [r["reason"] for r in plan.pruned]
        assert any("not divisible" in r for r in reasons)
        plan_tp = plan_sharding(_lm(), (SEQ,), optimizer="adam",
                                batch_size=3, grad_accums=(1,),
                                steps_per_execution=(1,))
        assert plan_tp.chosen["config"] == {
            "strategy": "tp", "model_parallel": 8, "pipeline_parallel": 1,
            "num_microbatches": 1, "precision": None,
            "grad_accum": 1, "steps_per_execution": 1,
        }


# ------------------------------------------------------------------ ranking --
class TestRanking:
    def test_uncapped_small_shape_picks_dp(self):
        """The second acceptance row: when everything fits, replication is
        free and ZeRO/FSDP only ADD gather traffic — plain DP must win."""
        plan = plan_sharding(_lm(vocab=512, d_model=64), (SEQ,),
                             optimizer="adam", batch_size=16,
                             grad_accums=(1,), steps_per_execution=(1,))
        assert plan.chosen["config"]["strategy"] == "dp"
        assert plan.chosen["reason"] is None

    def test_plan_deterministic(self):
        import json

        kw = dict(optimizer="adam", batch_size=16)
        a = plan_sharding(_lm(), (SEQ,), **kw).summary()
        b = plan_sharding(_lm(), (SEQ,), **kw).summary()
        assert json.dumps(a, sort_keys=True) == json.dumps(b,
                                                           sort_keys=True)

    def test_cost_rows_and_pinned_dimensions(self):
        plan = plan_sharding(_lm(), (SEQ,), optimizer="adam", batch_size=16,
                             precisions=("mixed_bfloat16",),
                             grad_accums=(2,), steps_per_execution=(4,))
        cfg = plan.chosen["config"]
        assert cfg["precision"] == "mixed_bfloat16"
        assert cfg["grad_accum"] == 2
        assert cfg["steps_per_execution"] == 4
        for row in plan.candidates:
            assert row["est_step_seconds"] > 0
            assert set(row["cost_breakdown"]) == {"compute_s", "comm_s",
                                                  "dispatch_s"}


# ------------------------------------------------------ pipeline third axis --
class TestPipelinePlanner:
    """DP x TP x PP: the planner's third axis. All estimate-only (no
    dispatch, no mesh commit) per the in-tier planner budget."""

    # Every dim indivisible by any 8-divisor: _largest_divisible_spec
    # degrades DP/ZeRO/FSDP to full replication and the pipelined stack's
    # 'pipe' hints leave TP nothing to shard — depth is the ONLY axis
    # that still splits state.
    AWKWARD = dict(vocab=331, num_layers=4, d_model=36, num_heads=2,
                   d_ff=84, max_len=33, pipeline=True)

    def _plan(self, mod, **kw):
        kw.setdefault("optimizer", "adam")
        kw.setdefault("batch_size", 16)
        kw.setdefault("grad_accums", (1,))
        kw.setdefault("steps_per_execution", (1,))
        return plan_sharding(mod, (SEQ,), **kw)

    def test_pipeline_hop_priced_exactly(self):
        """Satellite 1: DataPipelineParallel's comm_bytes_estimate prices
        the boundary activation ppermute instead of inheriting DP's
        zero-pipeline-traffic row: min stacked block width x itemsize x
        ceil-ish hop count (M+n-2)//M per token."""
        mod = _lm(num_layers=4, pipeline=True)
        params, _, _ = mod.init(jax.random.PRNGKey(0), (SEQ,))
        hints = mod.sharding_hints()
        pp = dtpu.DataPipelineParallel(pipeline_parallel=2,
                                       num_microbatches=4)
        est = pp.comm_bytes_estimate(params, hints=hints)
        assert set(est) == TestCommSchema.KEYS
        # d_model=32 f32 over pp2/M4: 32 * 4 * (4 + 2 - 2) // 4.
        assert est["pipeline_hop_bytes_per_token_per_device"] == 128
        # Stage-sharded grads reduce 1/n-sized pieces over the data axis.
        dp = dtpu.DataParallel().comm_bytes_estimate(params, hints=hints)
        assert dp["pipeline_hop_bytes_per_token_per_device"] == 0
        assert 0 < est["grad_reduce_bytes_per_device"] \
            < dp["grad_reduce_bytes_per_device"]

    def test_pp_rows_gated_on_pipe_hints(self):
        from distributed_tpu.parallel.auto_shard import (
            _hints_have_pipe, _pipe_stage_count,
        )

        flat = _lm()
        assert not _hints_have_pipe(flat.sharding_hints())
        labels = [r["label"] for r in self._plan(flat).candidates]
        assert not any(l.startswith("pp") for l in labels)

        piped = _lm(num_layers=4, pipeline=True)
        hints = piped.sharding_hints()
        assert _hints_have_pipe(hints)
        params, _, _ = piped.init(jax.random.PRNGKey(0), (SEQ,))
        assert _pipe_stage_count(params, hints) == 4
        plan = self._plan(piped)
        rows = ([r["label"] for r in plan.candidates]
                + [r["label"] for r in plan.pruned])
        assert any(l.startswith("pp2") for l in rows), rows
        assert any(l.startswith("pp4") for l in rows), rows
        # The explicit opt-out drops the axis entirely.
        off = self._plan(piped, include_pp=False)
        rows_off = ([r["label"] for r in off.candidates]
                    + [r["label"] for r in off.pruned])
        assert not any(l.startswith("pp") for l in rows_off)

    def test_capped_awkward_dims_pick_pp2(self):
        """The acceptance scenario: under a cap that only a 2-stage
        pipeline fits, the planner picks pp2 and prunes every flat
        layout WITH the hbm_cap rationale."""
        mod = _lm(**self.AWKWARD)
        pre = self._plan(mod)
        need = {}
        for r in pre.candidates + [p for p in pre.pruned
                                   if "state_bytes_per_device" in p]:
            need[r["label"]] = (r["state_bytes_per_device"]
                               + r["activation_bytes_per_device"])
        pp2 = min(v for k, v in need.items() if k.startswith("pp2"))
        rest = min(v for k, v in need.items() if not k.startswith("pp2"))
        assert pp2 < rest, need  # depth is the only axis that helps
        cap = (pp2 + rest) // 2
        plan = self._plan(mod, hbm_cap_bytes=cap)
        cfg = plan.chosen["config"]
        assert cfg["strategy"] == "pp" and cfg["pipeline_parallel"] == 2
        pruned = {r["config"]["strategy"] for r in plan.pruned
                  if "config" in r and "hbm_cap" in r["reason"]}
        assert {"dp", "zero1", "fsdp"} <= pruned
        # Deterministic: same inputs, byte-identical summary.
        import json
        again = self._plan(mod, hbm_cap_bytes=cap)
        assert json.dumps(plan.summary(), sort_keys=True) == \
            json.dumps(again.summary(), sort_keys=True)

    def test_pp_divisibility_pruned_with_rationale(self):
        # 6 stages over pp4 can't place evenly; the row must be pruned
        # with the stage rationale, not crash or silently vanish.
        mod = _lm(num_layers=6, pipeline=True)
        plan = self._plan(mod)
        pruned = {r["label"]: r["reason"] for r in plan.pruned}
        pp4 = [v for k, v in pruned.items() if k.startswith("pp4")]
        assert pp4 and all("stages" in r for r in pp4), pruned


# ----------------------------------------------------------- compile("auto") --
class TestAutoCompile:
    def _tokens(self, n, vocab=LM_KW["vocab"]):
        rng = np.random.default_rng(0)
        tok = rng.integers(0, vocab, (n, SEQ + 1)).astype(np.int32)
        return tok[:, :-1], tok[:, 1:]

    def test_end_to_end_on_2dev_mesh(self, tmp_path, monkeypatch):
        """compile(strategy="auto") on a 2-device mesh: plans at build,
        commits a working strategy, trains, and records the plan in
        last_fit_telemetry AND the JSONL event log."""
        log_path = tmp_path / "events.jsonl"
        monkeypatch.setenv("DTPU_EVENT_LOG", str(log_path))
        devices = jax.devices()[:2]
        m = _compiled_auto_model(
            _lm(), strategy="auto",
            auto_options=dict(batch_size=16, devices=devices),
        )
        m.build((SEQ,))
        assert m.last_plan is not None
        chosen = m.last_plan.chosen["config"]
        assert chosen["strategy"] in ("dp", "zero1", "fsdp", "single_device")
        mesh = getattr(m.strategy, "mesh", None)
        if mesh is not None:
            assert mesh.devices.size == 2
        x, y = self._tokens(64)
        hist = m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=3,
                     verbose=0, seed=0)
        assert np.isfinite(hist.history["loss"][-1])
        tele = m.last_fit_telemetry
        assert tele["plan"]["chosen"]["config"] == chosen
        assert isinstance(tele["plan"]["pruned"], list)
        from distributed_tpu.utils.events import read_events

        kinds = [e["event"] for e in read_events(log_path)]
        assert "auto_shard_plan" in kinds

    def test_pinned_precision_and_k_survive_planning(self):
        m = _compiled_auto_model(
            _lm(), strategy="auto", precision="mixed_bfloat16",
            steps_per_execution=2,
            auto_options=dict(batch_size=16, devices=jax.devices()[:2]),
        )
        m.build((SEQ,))
        assert m.precision is not None
        assert m.precision.name == "mixed_bfloat16"
        assert m.steps_per_execution == 2
        cfg = m.last_plan.chosen["config"]
        assert cfg["precision"] == "mixed_bfloat16"
        assert cfg["steps_per_execution"] == 2

    def test_auto_under_cap_commits_fsdp_and_trains(self):
        """The capped acceptance row through the USER path, scaled down:
        a synthetic cap that replicated state cannot fit commits FSDP and
        the model trains under it."""
        pre = plan_sharding(_lm(), (SEQ,), optimizer="adam", batch_size=16,
                            grad_accums=(1,), steps_per_execution=(1,),
                            include_tp=False)
        by = {r["config"]["strategy"]: r for r in pre.candidates}
        cap = (by["dp"]["state_bytes_per_device"]
               + by["fsdp"]["state_bytes_per_device"]) // 2
        m = _compiled_auto_model(
            _lm(), strategy="auto", hbm_cap_bytes=cap,
            auto_options=dict(batch_size=16, grad_accums=(1,),
                              steps_per_execution=(1,), include_tp=False),
        )
        m.build((SEQ,))
        assert m.last_plan.chosen["config"]["strategy"] == "fsdp"
        assert isinstance(m.strategy, dtpu.FSDP)
        x, y = self._tokens(32)
        hist = m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=2,
                     verbose=0, seed=0)
        assert np.isfinite(hist.history["loss"][-1])

    def test_compile_strategy_instance_replaces_scope(self):
        m = _compiled_auto_model(_lm(), strategy=dtpu.FSDP())
        assert isinstance(m.strategy, dtpu.FSDP)
        m.build((SEQ,))
        x, y = self._tokens(32)
        hist = m.fit(x, y, batch_size=16, epochs=1, steps_per_epoch=2,
                     verbose=0, seed=0)
        assert np.isfinite(hist.history["loss"][-1])

    def test_compile_strategy_rejects_garbage(self):
        m = dtpu.Model(_lm())
        with pytest.raises(ValueError, match="strategy must be"):
            m.compile(optimizer="adam", strategy="autoo")


# ------------------------------------------------------------- measured path --
@pytest.mark.slow
def test_measured_shortlist_commits_fastest():
    """measure=True: the top-k shortlist is timed with short REAL
    dispatches, timings land in plan.measured, and the committed config is
    the fastest measured one."""
    m = _compiled_auto_model(
        _lm(), strategy="auto", measure=True,
        auto_options=dict(batch_size=16, grad_accums=(1,),
                          steps_per_execution=(1,), include_tp=False,
                          top_k=2),
    )
    m.build((SEQ,))
    plan = m.last_plan
    assert plan.tie_break == "measured"
    assert plan.measured and len(plan.measured) == 2
    timed = [r for r in plan.measured if r["seconds_per_step"] is not None]
    assert timed, plan.measured
    fastest = min(timed, key=lambda r: r["seconds_per_step"])
    assert plan.chosen["config"] == fastest["config"]
    rng = np.random.default_rng(0)
    tok = rng.integers(0, LM_KW["vocab"], (32, SEQ + 1)).astype(np.int32)
    hist = m.fit(tok[:, :-1], tok[:, 1:], batch_size=16, epochs=1,
                 steps_per_epoch=2, verbose=0, seed=0)
    assert np.isfinite(hist.history["loss"][-1])
