"""compile(head_chunks=C): fused chunked head-loss (round 5).

The full (tokens, vocab) logits tensor never materializes — the head and
the loss (and sum-count metrics) run chunk-by-chunk under a rematerialized
lax.scan. These tests pin numerical equivalence with the plain step on the
CPU sim; the capability it exists for (T=65,536 on one 16 GB chip, where
bf16 logits alone would be 4.3 GB) was measured on the real chip
(docs/PERF_ROUNDS_1-5.md, round-5 long-context table).
"""

import numpy as np
import pytest

import jax
import distributed_tpu as dtpu


def _make(head_chunks, metrics=("accuracy",)):
    m = dtpu.Model(
        dtpu.models.transformer_lm(
            64, num_layers=2, d_model=16, num_heads=2, max_len=32
        )
    )
    m.compile(
        optimizer=dtpu.optim.SGD(0.1),
        loss="sparse_categorical_crossentropy",
        metrics=list(metrics),
        head_chunks=head_chunks,
    )
    m.build((32,))
    return m


def _data(n=8):
    rng = np.random.default_rng(0)
    return (
        rng.integers(0, 64, (n, 32)).astype(np.int32),
        rng.integers(0, 64, (n, 32)).astype(np.int32),
    )


def test_chunked_train_matches_plain():
    x, y = _data()
    ma, mb = _make(None), _make(4)
    ha = ma.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    hb = mb.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    np.testing.assert_allclose(
        ha.history["loss"], hb.history["loss"], rtol=1e-5
    )
    np.testing.assert_allclose(
        ha.metrics["accuracy"], hb.metrics["accuracy"], rtol=1e-5
    )
    for a, b in zip(jax.tree_util.tree_leaves(ma.params),
                    jax.tree_util.tree_leaves(mb.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_chunked_eval_matches_plain_with_padding():
    """A padded final batch exercises the per-token mask path: pad tokens
    must not contribute to loss or accuracy."""
    x, y = _data()
    ma, mb = _make(None), _make(4)
    ea = ma.evaluate(x[:5], y[:5], batch_size=8, verbose=0)
    eb = mb.evaluate(x[:5], y[:5], batch_size=8, verbose=0)
    assert ea["loss"] == pytest.approx(eb["loss"], abs=1e-4)
    assert ea["accuracy"] == pytest.approx(eb["accuracy"], abs=1e-6)


def test_chunked_head_under_data_parallel(devices):
    """head_chunks composes with the DP strategy: batch sharded on 'data',
    chunked scan inside the jitted step."""
    x, y = _data(16)
    strategy = dtpu.DataParallel()
    with strategy.scope():
        m = dtpu.Model(
            dtpu.models.transformer_lm(
                64, num_layers=1, d_model=16, num_heads=2, max_len=32
            )
        )
        m.compile(optimizer=dtpu.optim.SGD(0.1),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"], head_chunks=4)
    h = m.fit(x, y, batch_size=16, epochs=1, verbose=0, seed=0)
    assert np.isfinite(h.history["loss"][0])
    for leaf in jax.tree_util.tree_leaves(m.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_head_chunks_validation():
    with pytest.raises(ValueError, match="integer >= 1"):
        _make(0)
    # Non-sequential module fails at compile, not at first step.
    from distributed_tpu import nn as dnn

    m = dtpu.Model(dnn.Dense(4))
    with pytest.raises(ValueError, match="Sequential"):
        m.compile(optimizer=dtpu.optim.SGD(0.1), head_chunks=2)
    # Token count not divisible by C fails with a clear message.
    m2 = _make(5)
    x, y = _data()
    with pytest.raises(ValueError, match="divide the token count"):
        m2.fit(x, y, batch_size=8, epochs=1, verbose=0)


# @slow (tier-1 budget, PR 17): ~10s interrupted-run drive;
# chunked-vs-plain parity and chunked-under-DP stay in-tier, and the
# resume math itself is pinned by the callback restore tests.
@pytest.mark.slow
def test_chunked_head_checkpoint_resume(tmp_path):
    """head_chunks composes with the resume math: a run interrupted after
    a checkpoint and restarted finishes bit-identical to an uninterrupted
    one (the chunked step rebuilds from the restored state)."""
    from distributed_tpu.training.callbacks import ModelCheckpoint

    x, y = _data(16)
    ref = _make(4)
    ref.fit(x, y, batch_size=8, epochs=3, verbose=0, seed=0)

    m1 = _make(4)
    m1.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0,
           callbacks=[ModelCheckpoint(tmp_path, save_freq="epoch")])
    m2 = _make(4)
    m2.fit(x, y, batch_size=8, epochs=3, verbose=0, seed=0,
           callbacks=[ModelCheckpoint(tmp_path, save_freq="epoch",
                                      restore=True)])
    for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(m2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_head_generate_unaffected():
    """generate() rides the decode path (head applied per token), which
    head_chunks must not disturb. Both models keep their bit-identical
    INIT params (no training — the plain and chunked train steps differ
    at float precision, which would make greedy-argmax equality flaky);
    this isolates generate() itself from the head_chunks compile flag."""
    x, _ = _data()
    ma, mb = _make(None), _make(4)
    out_a = ma.generate(x[:1, :8], max_new_tokens=6, temperature=0.0)
    out_b = mb.generate(x[:1, :8], max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


# @slow (tier-1 budget, PR 17): ~8s composition cross-product; chunked
# head numerics and plain grad-accum composition stay in-tier, and the
# K x chunks x accum x clip matrix is already @slow (PR 15 retag) in
# test_multi_step.py — this is the same surface minus K.
@pytest.mark.slow
def test_chunked_head_composes_with_accumulation_and_clip():
    """head_chunks x gradient_accumulation_steps x grad_clip: the chunked
    loss feeds the same optax pipeline (MultiSteps wrapping clip), so the
    composed run must match the plain step's composed run."""
    x, y = _data(16)

    def make(head_chunks):
        m = dtpu.Model(dtpu.models.transformer_lm(
            64, num_layers=2, d_model=16, num_heads=2, max_len=32))
        m.compile(optimizer=dtpu.optim.SGD(0.1),
                  loss="sparse_categorical_crossentropy", metrics=[],
                  grad_clip=1.0, gradient_accumulation_steps=2,
                  head_chunks=head_chunks)
        m.build((32,))
        return m

    ma, mb = make(None), make(4)
    ha = ma.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    hb = mb.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    np.testing.assert_allclose(ha.history["loss"], hb.history["loss"],
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(ma.params),
                    jax.tree_util.tree_leaves(mb.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_chunked_head_with_pallas_xent_loss():
    """The benchmark's loss (Pallas fused xent, interpret mode on CPU) rides
    the same chunked path."""
    x, y = _data()
    m = dtpu.Model(
        dtpu.models.transformer_lm(
            64, num_layers=1, d_model=16, num_heads=2, max_len=32
        )
    )
    m.compile(optimizer=dtpu.optim.SGD(0.1),
              loss="pallas_sparse_categorical_crossentropy",
              metrics=[], head_chunks=2)
    m.build((32,))
    h = m.fit(x, y, batch_size=8, epochs=1, verbose=0, seed=0)
    assert np.isfinite(h.history["loss"][0])
