"""Online post-training: rollout packing, the policy-gradient loss, the
baseline/KL state, and the closed loop end-to-end.

The serving-side halves (logprob capture, per-request RNG determinism,
the update_weights staleness contract) are pinned in test_serving.py;
here the focus is the trainer side and the loop that joins them. Kept
lean per the tier-1 budget: one module-scoped tiny LM + engine, every
PostTrainer test reuses the same compiled shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import optim, rl
from distributed_tpu.serving import Engine


@pytest.fixture(scope="module")
def lm():
    model = dtpu.Model(dtpu.models.transformer_lm(
        32, num_layers=1, d_model=16, num_heads=2, max_len=64))
    model.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    model.build((32,))
    return model


@pytest.fixture(scope="module")
def sampling_engine(lm):
    """Shared across the loop tests: a fresh Engine pays its own
    prefill/decode compiles, and the loop's correctness never depends on
    which engine instance carries it (update_weights re-snapshots)."""
    return Engine(lm, max_slots=2, block_size=8, max_len=64,
                  temperature=1.0, seed=3)


def _prompts(n=4, size=4, vocab=32, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (size,)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------- packing --
def test_pack_rollouts_alignment():
    """Targets shift by one, the mask selects exactly the positions whose
    TARGET is a completion token, and rollout logprobs land index-aligned
    with those positions."""
    r = rl.Rollout(
        tokens=np.array([7, 8, 9, 1, 2, 3], np.int64),  # prompt 3, gen 3
        prompt_len=3,
        logprobs=np.array([-0.5, -1.0, -1.5]),
        advantage=2.0,
    )
    x, y = rl.pack_rollouts([r], train_len=8, kl_coef=0.25)
    assert x.shape == (1, 7) and y.shape == (1, 7, 5)
    np.testing.assert_array_equal(x[0], [7, 8, 9, 1, 2, 0, 0])
    np.testing.assert_array_equal(y[0, :, 0], [8, 9, 1, 2, 3, 0, 0])
    np.testing.assert_array_equal(y[0, :, 3], [0, 0, 1, 1, 1, 0, 0])
    np.testing.assert_allclose(y[0, 2:5, 1], 2.0)  # advantage on mask
    np.testing.assert_allclose(y[0, 2:5, 2], [-0.5, -1.0, -1.5])
    assert np.all(y[0, :, 4] == 0.25)  # kl coef rides the batch
    with pytest.raises(ValueError, match="train_len"):
        rl.pack_rollouts([r], train_len=5)
    with pytest.raises(ValueError, match="logprobs"):
        rl.pack_rollouts(
            [rl.Rollout(r.tokens, 3, np.array([-0.5]))], train_len=8
        )


def test_rl_loss_gradient_direction():
    """REINFORCE sanity: with positive advantage the loss gradient must
    INCREASE the chosen token's logit relative to the rest; the KL term
    is zero on-policy and >= 0 off-policy (k3 estimator)."""
    loss = rl.rl_loss()
    logits = jnp.zeros((1, 2, 4))
    y = np.zeros((1, 2, 5), np.float32)
    y[0, 0] = [2, 1.0, float(np.log(0.25)), 1.0, 0.0]  # on-policy ref
    y = jnp.asarray(y)
    g = jax.grad(lambda l: loss(l, y))(logits)
    assert g[0, 0, 2] < 0  # push chosen logit UP (minimizing loss)
    assert np.all(np.asarray(g[0, 0, [0, 1, 3]]) > 0)
    assert np.allclose(g[0, 1], 0.0)  # masked position contributes nothing
    # KL term: on-policy (ref == current) contributes exactly 0, any
    # drift contributes positively.
    ykl = np.zeros((1, 2, 5), np.float32)
    ykl[0, 0] = [2, 0.0, float(np.log(0.25)), 1.0, 1.0]
    on = float(loss(logits, jnp.asarray(ykl)))
    assert abs(on) < 1e-6
    ykl[0, 0, 2] = float(np.log(0.5))  # reference more confident
    off = float(loss(logits, jnp.asarray(ykl)))
    assert off > 0


def test_rl_loss_ppo_clip_matches_reinforce_on_policy():
    """On-policy the clipped surrogate IS the ratio-1 REINFORCE direction
    (gradient magnitudes differ off-policy only when clipping engages)."""
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((1, 3, 4)),
                         jnp.float32)
    lp = jax.nn.log_softmax(logits, -1)
    y = np.zeros((1, 3, 5), np.float32)
    for t in range(2):
        tok = t + 1
        y[0, t] = [tok, 1.5, float(lp[0, t, tok]), 1.0, 0.0]
    y = jnp.asarray(y)
    g_plain = jax.grad(lambda l: rl.rl_loss()(l, y))(logits)
    g_clip = jax.grad(lambda l: rl.rl_loss(ppo_clip=0.2)(l, y))(logits)
    np.testing.assert_allclose(np.asarray(g_plain), np.asarray(g_clip),
                               atol=1e-6)


# ---------------------------------------------------------- optim state --
def test_ema_baseline_and_adaptive_kl():
    b = optim.EmaBaseline(decay=0.5)
    assert b.value is None
    assert b.update(4.0) == 4.0  # cold start adopts the mean
    assert b.update(0.0) == 2.0
    s = b.state_dict()
    b2 = optim.EmaBaseline()
    b2.load_state(s)
    assert b2.value == 2.0 and b2.decay == 0.5
    with pytest.raises(ValueError):
        optim.EmaBaseline(decay=1.0)

    k = optim.AdaptiveKLCoef(init_coef=0.1, target=0.01, factor=2.0,
                             tolerance=1.5)
    assert k.update(0.10) == pytest.approx(0.2)   # overshoot: grow
    assert k.update(0.001) == pytest.approx(0.1)  # timid: shrink
    assert k.update(0.01) == pytest.approx(0.1)   # in band: hold
    k2 = optim.AdaptiveKLCoef()
    k2.load_state(k.state_dict())
    assert k2.coef == pytest.approx(0.1)


# ------------------------------------------------------------- the loop --
def test_post_trainer_requires_sampling_engine(lm):
    greedy = Engine(lm, max_slots=1, block_size=8, max_len=64)
    with pytest.raises(ValueError, match="temperature"):
        rl.PostTrainer(lm, greedy)


def test_post_trainer_closed_loop_improves_and_syncs(lm, sampling_engine):
    """The end-to-end gate at test scale: rewards improve from the first
    iteration to the last, every iteration hot-swaps (weights_version
    marches), the measured KL is finite and positive, and the engine
    really serves the trained weights (its snapshot equals the trainer's
    masters after sync)."""
    engine = sampling_engine
    pt = rl.PostTrainer(
        lm, engine, reward_fn=rl.length_penalized_logprob(0.0),
        learning_rate=1e-2, kl_coef=0.01, seed=0,
    )
    rows = pt.train(_prompts(4, seed=0), iterations=3, num_samples=4,
                    max_new_tokens=16, train_epochs=2)
    rewards = [r["reward_mean"] for r in rows]
    assert rewards[-1] > rewards[0], rewards
    assert [r["weights_version"] for r in rows] == [1, 2, 3]
    assert all(r["kl"] is not None and np.isfinite(r["kl"]) for r in rows)
    assert all(r["weight_sync_s"] >= 0 for r in rows)
    assert pt.baseline.value is not None
    # The engine's served snapshot IS the trainer's masters post-sync.
    for a, b in zip(jax.tree_util.tree_leaves(engine._params),
                    jax.tree_util.tree_leaves(lm.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # History rows carry the loop's three couplings.
    for key in ("rollout_tokens_per_sec", "train_steps_per_sec",
                "weight_sync_s"):
        assert rows[-1][key] > 0
    # An AdaptiveKLCoef plugs in where the float goes and is driven by
    # the measured post-update KL, with no recompile: the coef rides in
    # the packed batch (y channel 4), not the trace — same shapes, same
    # compiled step.
    ctl = optim.AdaptiveKLCoef(init_coef=0.05, target=1e-4, factor=2.0)
    pt.kl = ctl
    row = pt.iterate(_prompts(4, seed=0), num_samples=4,
                     max_new_tokens=16, train_epochs=2)
    # Any real update at lr 1e-2 overshoots a 1e-4 KL target: coef grew.
    assert ctl.coef == pytest.approx(0.1)
    assert row["kl_coef"] == pytest.approx(0.1)


@pytest.mark.slow
def test_post_trainer_composes_with_mesh_strategy_and_grad_accum():
    """The heavy matrix: the SAME loop with a DataParallel trainer over
    the 8-device CPU sim and grad_accum microbatching — the fit-path
    composition the tentpole claims (strategies/accum ride under the rl
    loss unchanged) — improving reward and hot-swapping every
    iteration."""
    strategy = dtpu.DataParallel()
    with strategy.scope():
        model = dtpu.Model(dtpu.models.transformer_lm(
            32, num_layers=1, d_model=16, num_heads=2, max_len=64))
        model.compile(optimizer="adam",
                      loss="sparse_categorical_crossentropy")
        model.build((32,))
    engine = Engine(model, max_slots=2, block_size=8, max_len=64,
                    temperature=1.0, seed=3)
    pt = rl.PostTrainer(model, engine, learning_rate=1e-2, kl_coef=0.01,
                        grad_accum=2, seed=0)
    rows = pt.train(_prompts(4, seed=0), iterations=3, num_samples=4,
                    max_new_tokens=16, train_epochs=2)
    rewards = [r["reward_mean"] for r in rows]
    assert rewards[-1] > rewards[0], rewards
    assert [r["weights_version"] for r in rows] == [1, 2, 3]
    # The swap re-placed the trained masters under the live strategy.
    for a, b in zip(jax.tree_util.tree_leaves(engine._params),
                    jax.tree_util.tree_leaves(model.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
