"""Mixed-precision policies (ISSUE 5): bf16/f16 compute over f32 masters.

The contract under test: a ``compile(precision=...)`` policy changes the
dtype the forward/backward COMPUTES in, never where the truth lives —
params and optimizer state stay float32 master weights, gradients come
back f32 through the cast's VJP, accumulation stays f32, and checkpoints
persist the masters so f32<->mixed round-trips are exact. Loss curves
under ``mixed_bfloat16`` track the f32 reference to bf16 rounding
(measured max rel diff ~5e-4 over 10 steps on this config; the 5e-3
tolerance is 10x slack), identically across every data-parallel strategy.
``mixed_float16`` adds dynamic loss scaling; the skip-step path is
exercised both at the optax-transform level (injected inf gradient) and
end-to-end (an overflowing initial scale must halve per step while params
stay untouched). Small and short throughout: tier-1 has ~40s of headroom.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distributed_tpu as dtpu
from distributed_tpu import optim, precision

VOCAB, T, B = 64, 16, 8


def _data(n=128, seed=3):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, VOCAB, (n, T + 1), dtype=np.int64)
    return tok[:, :-1].astype(np.int32), tok[:, 1:].astype(np.int32)


def _lm(strategy, **compile_kw):
    with strategy.scope():
        m = dtpu.Model(dtpu.models.transformer_lm(
            VOCAB, num_layers=1, d_model=32, num_heads=2, max_len=T))
        m.compile(optimizer=dtpu.optim.Adam(1e-3),
                  loss="sparse_categorical_crossentropy", **compile_kw)
    return m


def _step_losses(model, x, y, steps=10, **fit_kw):
    losses = []
    cb = dtpu.callbacks.LambdaCallback(
        on_batch_end=lambda m, s, logs: losses.append(float(logs["loss"]))
    )
    model.fit(x, y, batch_size=B, epochs=1, steps_per_epoch=steps,
              verbose=0, seed=5, shuffle=False, callbacks=[cb], **fit_kw)
    return np.asarray(losses)


def _assert_f32_masters(model):
    """Params AND optimizer state are f32 masters regardless of policy."""
    for leaf in jax.tree_util.tree_leaves((model.params, model.opt_state)):
        if jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
            assert jnp.result_type(leaf) == jnp.float32, leaf.dtype


@pytest.fixture(scope="module")
def two_dev(devices):
    return devices[:2]


@pytest.fixture(scope="module")
def lm_data():
    return _data()


@pytest.fixture(scope="module")
def f32_run(two_dev, lm_data):
    """f32 reference (no policy at all — the pre-policy default path):
    per-step losses over 10 steps. Strategies are loss-identical at f32
    (test_zero pins that at ULP level), so one reference serves every
    mixed-vs-f32 comparison."""
    x, y = lm_data
    m = _lm(dtpu.DataParallel(devices=two_dev))
    return _step_losses(m, x, y)


# ---------------------------------------------------------------- policy unit
class TestPolicy:
    def test_presets(self):
        p = dtpu.Policy("mixed_bfloat16")
        assert p.param_dtype == jnp.float32
        assert p.compute_dtype == jnp.bfloat16
        assert p.output_dtype == jnp.float32
        assert not p.loss_scaling  # bf16 keeps f32's exponent range
        assert dtpu.Policy("mixed_float16").loss_scaling
        f32 = dtpu.Policy("float32")
        assert not f32.needs_compute_cast

    def test_get(self):
        assert precision.get(None) is None
        p = dtpu.Policy("mixed_bfloat16")
        assert precision.get(p) is p
        assert precision.get("mixed_bfloat16").compute_dtype == jnp.bfloat16
        with pytest.raises(ValueError, match="bfloat16"):
            precision.get("bf16_but_misspelled")
        with pytest.raises(TypeError, match="Policy"):
            precision.get(7)

    def test_resolve_dtype_scope_and_override(self):
        assert precision.resolve_dtype(None) is None
        with dtpu.Policy("mixed_bfloat16").scope():
            assert precision.resolve_dtype(None) == jnp.bfloat16
            # an explicit per-layer dtype= always wins over the policy
            assert precision.resolve_dtype(jnp.float32) == jnp.float32
        assert precision.current_policy() is None  # scope restored

    def test_cast_to_compute_respects_hints_and_ints(self):
        p = dtpu.Policy("mixed_bfloat16")
        tree = {"a": {"kernel": jnp.ones((2, 2), jnp.float32)},
                "pinned": {"kernel": jnp.ones((2, 2), jnp.float32)},
                "steps": jnp.zeros((), jnp.int32)}
        cast = p.cast_to_compute(tree, {"pinned": jnp.float32})
        assert cast["a"]["kernel"].dtype == jnp.bfloat16
        assert cast["pinned"]["kernel"].dtype == jnp.float32  # layer's own
        assert cast["steps"].dtype == jnp.int32  # non-floating untouched

    def test_grad_accum_helpers(self):
        params = {"w": jnp.ones((2,), jnp.bfloat16),
                  "n": jnp.zeros((), jnp.int32)}
        acc = precision.grad_accum_init(params)
        assert acc["w"].dtype == jnp.float32  # f32 even for bf16 grads
        assert acc["n"].dtype == jnp.int32
        precision.assert_f32_accumulator(acc)
        with pytest.raises(AssertionError, match="float32"):
            precision.assert_f32_accumulator({"w": jnp.zeros(2, jnp.bfloat16)})
        back = precision.cast_like(acc, params)
        assert back["w"].dtype == jnp.bfloat16


# ------------------------------------------------------- policy x strategy --
STRATEGIES = ["single", "dp", "zero1", "fsdp"]


def _strategy(name, two_dev):
    return {
        "single": lambda: dtpu.SingleDevice(),
        "dp": lambda: dtpu.DataParallel(devices=two_dev),
        "zero1": lambda: dtpu.ZeroDataParallel(devices=two_dev),
        "fsdp": lambda: dtpu.FSDP(devices=two_dev),
    }[name]()


class TestLossParity:
    @pytest.mark.parametrize("strat", STRATEGIES)
    def test_mixed_bfloat16_tracks_f32(self, strat, two_dev, lm_data,
                                       f32_run):
        """bf16 compute over f32 masters: the loss curve matches the f32
        reference to bf16 rounding on EVERY strategy — the policy is a
        compute-dtype lever, orthogonal to where state lives. The FSDP
        case also checks the fit telemetry: the policy name lands in it
        and the collective-byte estimate counts bytes at the dtype they
        MOVE in — the per-layer param all-gathers are exactly half under
        bf16 (every gathered leaf is floating)."""
        x, y = lm_data
        m = _lm(_strategy(strat, two_dev), precision="mixed_bfloat16")
        losses = _step_losses(m, x, y)
        np.testing.assert_allclose(losses, f32_run, rtol=5e-3)
        _assert_f32_masters(m)
        if strat == "fsdp":
            tele = m.last_fit_telemetry
            assert tele["precision"] == "mixed_bfloat16"
            mixed = tele["comm_bytes_estimate"]
            f32 = m.strategy.comm_bytes_estimate(m.params)  # master dtype
            assert mixed["gathered_param_bytes_per_device"] > 0
            assert (f32["gathered_param_bytes_per_device"]
                    == 2 * mixed["gathered_param_bytes_per_device"])
            assert (f32["grad_reduce_bytes_per_device"]
                    == 2 * mixed["grad_reduce_bytes_per_device"])


class TestComposition:
    # @slow (tier-1 budget, PR 17): ~6s composition cross-product; loss
    # parity per strategy stays in-tier (TestLossParity) as does plain
    # grad_accum (test_zero.py) — this pins only their product.
    @pytest.mark.slow
    def test_grad_accum_under_mixed(self, two_dev, lm_data, f32_run):
        """fit(grad_accum=2) under bf16: microbatch grads arrive bf16-
        computed but accumulate in f32 (the in-jit assert in
        _accum_train_step_body enforces it at trace time), so the curve
        still tracks the f32 reference."""
        x, y = lm_data
        m = _lm(dtpu.DataParallel(devices=two_dev),
                precision="mixed_bfloat16")
        losses = _step_losses(m, x, y, grad_accum=2)
        np.testing.assert_allclose(losses, f32_run, rtol=5e-3)
        _assert_f32_masters(m)

    # @slow (tier-1 budget, PR 17): ~7s composition cross-product; loss
    # parity per strategy stays in-tier (TestLossParity) as does plain
    # steps_per_execution (test_multi_step.py) — product only here.
    @pytest.mark.slow
    def test_steps_per_execution_under_mixed(self, two_dev, lm_data,
                                             f32_run):
        """K=2 fused dispatch composes: the multi-step scan casts inside
        each fused step, epoch loss matches the reference mean. (K=2, not
        larger: the scan unrolls fully on XLA:CPU, so compile time scales
        with K — tier-1 budget.)"""
        x, y = lm_data
        m = _lm(dtpu.DataParallel(devices=two_dev),
                precision="mixed_bfloat16", steps_per_execution=2)
        h = m.fit(x, y, batch_size=B, epochs=1, steps_per_epoch=10,
                  verbose=0, seed=5, shuffle=False)
        assert np.isclose(h.history["loss"][0], f32_run.mean(), rtol=5e-3)
        assert m.step == 10


# ------------------------------------------------------------- loss scaling --
class TestLossScaling:
    def _tx(self, **kw):
        return optim.dynamic_loss_scaling(optax.sgd(0.1), **kw)

    def test_finite_step_applies_unscaled(self):
        tx = self._tx(init_scale=8.0)
        params = {"w": jnp.ones((3,), jnp.float32)}
        state = tx.init(params)
        assert float(state.scale) == 8.0
        grads = {"w": jnp.full((3,), 2.0 * 8.0)}  # SCALED by the step body
        updates, state = jax.jit(tx.update)(grads, state, params)
        # sgd(0.1) on the unscaled gradient 2.0
        np.testing.assert_allclose(np.asarray(updates["w"]), -0.2, rtol=1e-6)
        assert float(state.scale) == 8.0

    def test_nonfinite_skips_and_halves(self):
        tx = self._tx(init_scale=8.0)
        params = {"w": jnp.ones((3,), jnp.float32)}
        state = tx.init(params)
        inner0 = jax.device_get(state.inner_state)
        grads = {"w": jnp.array([1.0, jnp.inf, 1.0])}
        updates, state = jax.jit(tx.update)(grads, state, params)
        np.testing.assert_array_equal(np.asarray(updates["w"]), 0.0)
        assert float(state.scale) == 4.0  # halved
        assert int(state.growth_count) == 0
        # the wrapped transform's state was NOT advanced by the bad step
        for a, b in zip(jax.tree_util.tree_leaves(inner0),
                        jax.tree_util.tree_leaves(
                            jax.device_get(state.inner_state))):
            np.testing.assert_array_equal(a, b)

    def test_growth_after_interval(self):
        tx = self._tx(init_scale=4.0, growth_interval=2)
        params = {"w": jnp.ones((2,), jnp.float32)}
        state = tx.init(params)
        good = {"w": jnp.ones((2,), jnp.float32)}
        _, state = tx.update(good, state, params)
        assert float(state.scale) == 4.0 and int(state.growth_count) == 1
        _, state = tx.update(good, state, params)
        assert float(state.scale) == 8.0 and int(state.growth_count) == 0

    def test_loss_scale_value(self):
        tx = self._tx()
        state = tx.init({"w": jnp.ones(2)})
        assert optim.loss_scale_value(state) is state.scale
        assert optim.loss_scale_value(optax.sgd(0.1).init({"w": jnp.ones(2)})
                                      ) is None

    def test_f16_overflow_skips_step_end_to_end(self, two_dev, lm_data):
        """Injected overflow through the REAL jitted train path: an
        initial scale of 2^126 makes scale*loss overflow f32 (and the f16
        backward overflow regardless), so every step must take the skip
        branch — zero updates (params bit-identical to init), scale
        halved per step."""
        x, y = lm_data
        pol = dtpu.Policy("mixed_float16")
        pol.initial_loss_scale = 2.0 ** 126
        m = _lm(dtpu.DataParallel(devices=two_dev), precision=pol)
        m.build((T,), seed=1)
        p0 = jax.device_get(m.params)
        losses = _step_losses(m, x, y, steps=4)
        assert np.all(np.isfinite(losses))  # reported loss is pre-scale
        scale = float(jax.device_get(optim.loss_scale_value(m.opt_state)))
        assert scale == 2.0 ** 122  # halved on each of the 4 steps
        for a, b in zip(jax.tree_util.tree_leaves(p0),
                        jax.tree_util.tree_leaves(jax.device_get(m.params))):
            np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------- checkpoint --
class TestCheckpointRoundTrip:
    # @slow (tier-1 budget, PR 17): ~7s cast-roundtrip drive; the
    # mixed-tracks-f32 loss-parity tests stay in-tier, and
    # `pytest tests/test_precision.py` (no marker filter) still runs this.
    @pytest.mark.slow
    def test_mixed_to_f32_and_back(self, two_dev, lm_data, tmp_path):
        """Checkpoints hold the f32 masters, so save-under-mixed /
        restore-under-f32 (and the reverse) is EXACT — same bytes, same
        step cursor, training continues."""
        x, y = lm_data
        m = _lm(dtpu.DataParallel(devices=two_dev),
                precision="mixed_bfloat16")
        m.fit(x, y, batch_size=B, epochs=1, steps_per_epoch=2, verbose=0,
              seed=0)
        ck = dtpu.Checkpointer(tmp_path / "a")
        ck.save(m)

        m2 = _lm(dtpu.DataParallel(devices=two_dev), precision="float32")
        assert ck.restore_into(m2) == 2
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(m.params)),
                        jax.tree_util.tree_leaves(jax.device_get(m2.params))):
            np.testing.assert_array_equal(a, b)
        _assert_f32_masters(m2)
        m2.fit(x, y, batch_size=B, epochs=1, steps_per_epoch=1, verbose=0,
               seed=0)
        assert m2.step == 3

        # And the reverse direction: f32 save -> mixed restore is the
        # same masters, placed and castable (no extra fit needed — the
        # mixed train path is exercised throughout this file).
        ck2 = dtpu.Checkpointer(tmp_path / "b")
        ck2.save(m2)
        m3 = _lm(dtpu.DataParallel(devices=two_dev),
                 precision="mixed_bfloat16")
        assert ck2.restore_into(m3) == 3
        for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(m2.params)),
                        jax.tree_util.tree_leaves(jax.device_get(m3.params))):
            np.testing.assert_array_equal(a, b)

    def test_f16_loss_scale_and_lr_survive(self, two_dev, lm_data, f32_run,
                                           tmp_path):
        """The live loss scale is optimizer state (LossScaleState is a
        pytree NamedTuple), so it checkpoints leaf-for-leaf; and the
        wrapper stays transparent to set_hyperparam — a runtime LR change
        round-trips through it. The training run doubles as the f16
        happy-path check: at the default 2^15 scale nothing overflows on
        this model, every step applies (scaled then exactly unscaled
        grads — pure dtype rounding remains), and the losses track the
        f32 reference."""
        x, y = lm_data
        m = _lm(dtpu.DataParallel(devices=two_dev),
                precision="mixed_float16")
        losses = _step_losses(m, x, y, steps=2)
        np.testing.assert_allclose(losses, f32_run[:2], rtol=5e-3)
        _assert_f32_masters(m)
        m.set_learning_rate(3.3e-4)
        ck = dtpu.Checkpointer(tmp_path)
        ck.save(m)
        m2 = _lm(dtpu.DataParallel(devices=two_dev),
                 precision="mixed_float16")
        ck.restore_into(m2)
        assert float(jax.device_get(optim.loss_scale_value(m2.opt_state))
                     ) == 2.0 ** 15
        assert abs(m2.get_learning_rate() - 3.3e-4) < 1e-9


# ----------------------------------------------------------------- generate --
class TestGenerate:
    def test_bf16_policy_greedy_parity_and_cache_dtype(self, lm_data):
        """Same seed -> same f32 masters; greedy decode under the bf16
        policy emits the SAME tokens as f32 on this model, and the KV
        cache dtype comes from the policy (no abstract trace). Also the
        model-boundary output cast: predict() under a mixed policy hands
        back output_dtype (f32) — downstream numpy never sees bf16."""
        prompt = np.array([[5, 9, 2]], np.int32)
        f32 = _lm(dtpu.SingleDevice())
        f32.build((T,), seed=7)
        mix = _lm(dtpu.SingleDevice(), precision="mixed_bfloat16")
        mix.build((T,), seed=7)
        want = f32.generate(prompt, 8, temperature=0.0)
        got = mix.generate(prompt, 8, temperature=0.0)
        np.testing.assert_array_equal(want, got)
        assert f32._decode_dtype == jnp.float32
        assert mix._decode_dtype == jnp.bfloat16
        out = mix.predict(lm_data[0][:B], batch_size=B)
        assert out.dtype == np.float32


# ---------------------------------------------------- per-layer dtype= wins --
class TestPerLayerOverride:
    def test_explicit_dtype_layer_keeps_master_precision(self, lm_data):
        """A layer constructed with dtype=f32 under a bf16 policy: its
        params are EXEMPT from the policy cast (dtype_hints), so it
        computes from full-precision masters while its neighbors run
        bf16 — per-layer dtype= overrides the policy exactly."""
        x, y = lm_data
        seq = dtpu.nn.Sequential([
            dtpu.nn.Embedding(VOCAB, 32, name="emb"),
            dtpu.nn.Dense(32, activation="relu", dtype=jnp.float32,
                          name="pinned"),
            dtpu.nn.Dense(VOCAB, name="head"),
        ])
        with dtpu.SingleDevice().scope():
            m = dtpu.Model(seq)
            m.compile(optimizer=dtpu.optim.Adam(1e-3),
                      loss="sparse_categorical_crossentropy",
                      precision="mixed_bfloat16")
        m.build((T,))
        assert m._dtype_hints == {"pinned": jnp.float32}
        cast = m.precision.cast_to_compute(m.params, m._dtype_hints)
        assert cast["emb"]["table"].dtype == jnp.bfloat16
        assert cast["head"]["kernel"].dtype == jnp.bfloat16
        assert cast["pinned"]["kernel"].dtype == jnp.float32
        m.fit(x, y, batch_size=B, epochs=1, steps_per_epoch=1, verbose=0,
              seed=0)
        _assert_f32_masters(m)
