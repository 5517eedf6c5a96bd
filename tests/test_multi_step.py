"""compile(steps_per_execution=K): multi-step fused train execution.

One jitted dispatch runs K optimizer steps as a lax.scan over a
[K, batch, ...] super-batch, with loss/metric sums accumulated on device
and params/state/opt_state donated across the whole dispatch. These tests
pin numerical parity with the K=1 loop (same batch order, same per-step
RNG fold), composition with the other compile levers, and the K-step
granularity contract for callbacks/checkpoint resume. The capability it
exists for — amortizing per-step host dispatch overhead — is not measured
on the chip (docs/API.md "Multi-step execution").
"""

import numpy as np
import pytest

import jax
import distributed_tpu as dtpu
from distributed_tpu.training.callbacks import ModelCheckpoint


def small_data(n=512, seed=0):
    x, y = dtpu.data.synthetic_images(n, (28, 28), 10, seed)
    return x[..., None].astype(np.float32) / 255.0, y.astype(np.int32)


def make_model(K=None, momentum=0.0):
    m = dtpu.Model(dtpu.models.mnist_cnn())
    m.compile(
        optimizer=dtpu.optim.SGD(0.05, momentum=momentum),
        loss="sparse_categorical_crossentropy",
        metrics=["accuracy"],
        steps_per_execution=K,
    )
    return m


def assert_params_close(a, b, rtol=2e-5, atol=2e-6):
    for p, q in zip(jax.tree_util.tree_leaves(a.params),
                    jax.tree_util.tree_leaves(b.params)):
        np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                   rtol=rtol, atol=atol)


@pytest.mark.smoke
def test_k8_matches_k1_losses_and_params():
    """Acceptance parity: K=8 matches K=1 losses and params to fp32
    tolerance over 16 steps (2 epochs x 8), same shuffled batch order."""
    x, y = small_data()
    a, b = make_model(None), make_model(8)
    ha = a.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=8,
               verbose=0, seed=0)
    hb = b.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=8,
               verbose=0, seed=0)
    np.testing.assert_allclose(ha.history["loss"], hb.history["loss"],
                               rtol=1e-5)
    np.testing.assert_allclose(
        ha.history["accuracy"], hb.history["accuracy"], rtol=1e-5
    )
    assert a.step == b.step == 16
    assert_params_close(a, b)


def test_epoch_tail_shorter_than_k():
    """steps_per_epoch not divisible by K: the tail runs as a smaller
    final dispatch — every batch trains exactly once, in order."""
    x, y = small_data()
    a, b = make_model(None), make_model(4)
    ha = a.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=5,
               verbose=0, seed=0)
    hb = b.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=5,
               verbose=0, seed=0)
    assert b.step == 10
    np.testing.assert_allclose(ha.history["loss"], hb.history["loss"],
                               rtol=1e-5)
    assert_params_close(a, b)


def test_k_larger_than_epoch():
    """K > steps_per_epoch degrades to one whole-epoch dispatch."""
    x, y = small_data(n=128)
    m = make_model(32)
    h = m.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=3, verbose=0,
              seed=0)
    assert m.step == 6
    assert np.isfinite(h.history["loss"]).all()


# @slow (tier-1 budget, PR 12): 9s composition matrix — each mechanism
# keeps its own in-tier pin (K8==K1 above, chunked head in
# test_chunked_head, grad_accum in test_zero, clip in test_fit).
@pytest.mark.slow
def test_composes_with_head_chunks_accumulation_and_clip():
    """steps_per_execution x head_chunks x gradient_accumulation_steps x
    grad_clip: the scanned body is the SAME chunked step the K=1 path
    jits, and the MultiSteps accumulator rides the opt_state through the
    scan carry — the composed run matches the unfused composed run."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (16, 32)).astype(np.int32)
    y = rng.integers(0, 64, (16, 32)).astype(np.int32)

    def make(K):
        m = dtpu.Model(dtpu.models.transformer_lm(
            64, num_layers=2, d_model=16, num_heads=2, max_len=32))
        m.compile(optimizer=dtpu.optim.SGD(0.1),
                  loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"], grad_clip=1.0,
                  gradient_accumulation_steps=2, head_chunks=4,
                  steps_per_execution=K)
        m.build((32,))
        return m

    a, b = make(None), make(4)
    ha = a.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    hb = b.fit(x, y, batch_size=8, epochs=2, verbose=0, seed=0)
    np.testing.assert_allclose(ha.history["loss"], hb.history["loss"],
                               rtol=1e-5)
    assert_params_close(a, b, rtol=2e-4, atol=2e-5)


# @slow (tier-1 budget, PR 17): ~5s composition cross-product; K under
# single device stays in-tier (test_k8_matches_k1_losses_and_params) and
# DP/pipeline numerics in their own suites — product only here.
@pytest.mark.slow
def test_under_data_parallel_with_pipeline(devices):
    """The stacked super-batch shards (None, 'data') under DP — K
    replicated, rows sharded — and fit(pipeline) collates through
    Pipeline.next_k. Parity with the K=1 pipeline run, and replicas stay
    bit-identical (the fused all-reduce runs inside the scan)."""
    x, y = dtpu.data.synthetic_images(512, (28, 28), 10, seed=2)

    def make(K):
        with dtpu.DataParallel().scope():
            m = dtpu.Model(dtpu.models.mnist_cnn())
            m.compile(optimizer=dtpu.optim.Adam(1e-3),
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"], steps_per_execution=K)
        return m

    a, b = make(None), make(4)
    ha = a.fit(dtpu.data.Pipeline(x[..., None], y, 64, seed=0), epochs=1,
               verbose=0)
    hb = b.fit(dtpu.data.Pipeline(x[..., None], y, 64, seed=0), epochs=1,
               verbose=0)
    np.testing.assert_allclose(ha.history["loss"], hb.history["loss"],
                               rtol=1e-5)
    assert_params_close(a, b, rtol=2e-4, atol=2e-5)
    for leaf in jax.tree_util.tree_leaves(b.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_stacked_put_batch_sharding(devices):
    """put_batch(stacked=True) shards dim 1 (the batch rows), replicating
    the leading K dim, for the DataParallel family."""
    strat = dtpu.DataParallel()
    arr = np.zeros((4, 16, 3), np.float32)
    placed = strat.put_batch({"x": arr}, stacked=True)["x"]
    assert placed.shape == (4, 16, 3)
    spec = placed.sharding.spec
    assert spec[0] is None and spec[1] == "data", spec
    # Single shard holds all K steps of its row slice.
    assert placed.addressable_shards[0].data.shape == (4, 2, 3)


# @slow (tier-1 budget, PR 17): ~9s resume drive; K-aligned cursor math
# stays in-tier via the epoch/tail schedule units, and checkpoint-resume
# under chunking stays in-tier via test_chunked_head_checkpoint_resume
# (the K x save_freq boundary matrix is already @slow per PR 15).
@pytest.mark.slow
def test_checkpoint_resume_k_aligned(tmp_path):
    """ModelCheckpoint resume under K: the restored cursor is K-aligned
    (every dispatch advances K full steps), and the resumed run replays
    no batch — bit-identical to an uninterrupted run, momentum included."""
    x, y = small_data()
    ref = make_model(4, momentum=0.9)
    ref.fit(x, y, batch_size=64, epochs=3, steps_per_epoch=4, verbose=0,
            seed=3)

    m1 = make_model(4, momentum=0.9)
    m1.fit(x, y, batch_size=64, epochs=2, steps_per_epoch=4, verbose=0,
           seed=3, callbacks=[ModelCheckpoint(tmp_path, save_freq="epoch")])
    m2 = make_model(4, momentum=0.9)
    ck = ModelCheckpoint(tmp_path, save_freq="epoch", restore=True)
    m2.fit(x, y, batch_size=64, epochs=3, steps_per_epoch=4, verbose=0,
           seed=3, callbacks=[ck])
    assert ck.ckpt.all_steps()[-1] % 4 == 0  # saves land on K boundaries
    assert m2.step == 12
    for p, q in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(m2.params)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


def test_int_save_freq_crosses_boundaries(tmp_path):
    """An int save_freq fires when the K-strided step counter CROSSES a
    boundary (step % freq == 0 may never be observed under K-jumps), and
    the saved steps are K-aligned."""
    x, y = small_data(n=128)
    ck = ModelCheckpoint(tmp_path, save_freq=6)
    m = make_model(4)
    m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=16, verbose=0,
          seed=0, callbacks=[ck])  # dispatches end at steps 4, 8, 12, 16
    saved = ck.ckpt.all_steps()
    assert saved == [8, 12], saved  # crossings of 6 and 12
    assert all(s % 4 == 0 for s in saved)


# @slow (tier-1 budget, PR 12): 9s tail x save_freq edge matrix;
# boundary-crossing saves and K-aligned resume each keep their own
# in-tier tests (test_int_save_freq_crosses_boundaries,
# test_checkpoint_resume_k_aligned).
@pytest.mark.slow
def test_tail_dispatch_with_save_freq_inside_it(tmp_path):
    """next_k tail behavior x checkpointing: steps_per_epoch=10 with K=4
    runs dispatches of 4, 4, 2 — the save_freq=5 boundary falls INSIDE
    fused dispatches both times (at raw steps 5 and 15), so saves must
    land at the K-strided crossings (8, 10->no: boundary 10 is crossed at
    the tail dispatch, 18 at the second epoch's mid dispatch, 20 at its
    tail), each checkpoint complete and restorable."""
    x, y = small_data(n=512)
    ck = ModelCheckpoint(tmp_path, save_freq=5, keep=10)
    m = make_model(4, momentum=0.9)
    m.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=10, verbose=0,
          seed=0, callbacks=[ck])
    # Dispatch ends: 4, 8, 10 | 14, 18, 20. save_freq=5 buckets crossed
    # at 8 (bucket 1), 10 (2), 18 (3), 20 (4) — never at a raw multiple
    # of 5, because 5 and 15 sit inside fused dispatches.
    assert ck.ckpt.all_steps() == [8, 10, 18, 20]
    # The tail-boundary checkpoint restores into a bit-exact resume.
    ref = make_model(4, momentum=0.9)
    ref.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=10, verbose=0,
            seed=0)
    resumed = make_model(4, momentum=0.9)
    ck.ckpt.restore_into(resumed, step=10)
    resumed.fit(x, y, batch_size=32, epochs=2, steps_per_epoch=10,
                verbose=0, seed=0, initial_epoch=1)
    for p, q in zip(jax.tree_util.tree_leaves(ref.params),
                    jax.tree_util.tree_leaves(resumed.params)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


def test_tail_smaller_than_k_via_pipeline_next_k():
    """Pipeline.next_k serves the tail collation too: an epoch of 6 steps
    at K=4 pulls next_k(4) then next_k(2), and the pipeline cursor lands
    exactly at the epoch boundary (no over-read)."""
    x, y = dtpu.data.synthetic_images(256, (28, 28), 10, seed=4)
    p = dtpu.data.Pipeline(x[..., None], y, 32, seed=9, use_native=False)
    m = make_model(4)
    m.fit(p, epochs=1, steps_per_epoch=6, verbose=0)
    assert m.step == 6
    assert p.steps_emitted == 6
    p.close()


def test_callbacks_observe_monotonic_k_strided_step():
    x, y = small_data(n=256)
    seen = []
    cb = dtpu.callbacks.LambdaCallback(
        on_batch_end=lambda model, step, logs: seen.append(
            (step, model.step, float(np.asarray(logs["loss"])))
        )
    )
    m = make_model(4)
    m.fit(x, y, batch_size=32, epochs=1, steps_per_epoch=8, verbose=0,
          seed=0, callbacks=[cb])
    steps = [s for s, _, _ in seen]
    assert steps == [4, 8]
    assert all(s == ms for s, ms, _ in seen)  # step arg == model.step
    # The per-dispatch loss is the K-step mean — a finite scalar.
    assert all(np.isfinite(l) for _, _, l in seen)


def test_progress_line_at_k_granularity(capsys):
    """verbose=1 with K: the bar advances K steps per update and still
    lands on total/total at epoch end."""
    x, y = small_data(n=128)
    m = make_model(4)
    m.fit(x, y, batch_size=32, epochs=1, verbose=1, seed=0)
    out = capsys.readouterr().out
    assert "4/4" in out and "ETA" in out


def test_steps_per_execution_validation():
    m = dtpu.Model(dtpu.models.mnist_cnn())
    for bad in (0, -2, 2.5):
        with pytest.raises(ValueError, match="steps_per_execution"):
            m.compile(optimizer="sgd",
                      loss="sparse_categorical_crossentropy",
                      steps_per_execution=bad)
    # K=1 is the plain path, accepted and inert.
    m.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
              steps_per_execution=1)
    assert m.steps_per_execution == 1


def test_pipeline_next_k_matches_sequential_batches():
    """Pipeline.next_k(k) emits exactly the k batches k __next__ calls
    would, stacked, advancing the same cursor — on both the native and
    the pure-Python implementation."""
    x, y = dtpu.data.synthetic_images(256, (28, 28), 10, seed=5)
    for use_native in (None, False):
        kw = dict(batch_size=32, seed=7, shuffle=True,
                  use_native=use_native)
        a = dtpu.data.Pipeline(x[..., None], y, **kw)
        b = dtpu.data.Pipeline(x[..., None], y, **kw)
        xs, ys = a.next_k(3)
        assert xs.shape == (3, 32, 28, 28, 1) and ys.shape == (3, 32)
        for i in range(3):
            xb, yb = next(b)
            np.testing.assert_array_equal(xs[i], xb)
            np.testing.assert_array_equal(ys[i], yb)
        assert a.steps_emitted == 3
        # The cursor continues past the collated block.
        xa, _ = next(a)
        xb, _ = next(b)
        np.testing.assert_array_equal(xa, xb)
        with pytest.raises(ValueError, match="k >= 1"):
            a.next_k(0)


def test_step_timer_multi_step_tick():
    """StepTimer.tick(steps=K) counts K steps per fused dispatch so
    steps_per_sec reports per-step throughput; the single-step contract
    (warmup excluded) is unchanged."""
    import time

    from distributed_tpu.utils.profiler import StepTimer

    t = StepTimer(warmup=1)
    t.tick()            # warmup step: closes the window, starts the clock
    t.tick(steps=8)
    t.tick(steps=8)
    time.sleep(0.01)
    assert t.steps == 17
    rate = t.steps_per_sec
    assert rate > 0
    # 16 counted steps over >= 10ms: bounded above by 16 / 0.01.
    assert rate <= 16 / 0.01

    # A K-jump that lands past the warmup boundary starts the clock there.
    t2 = StepTimer(warmup=4)
    t2.tick(steps=8)
    assert t2._t0 is not None and t2.steps == 8
    assert t2.steps_per_sec == 0.0  # nothing counted yet
    t2.tick(steps=8)
    assert t2.steps_per_sec > 0


def test_predict_async_window_matches_blocking():
    """predict() keeps outputs on device behind a sliding fetch window;
    results are identical to per-batch fetching, including the padded
    remainder, and across window-boundary-sized inputs."""
    x, y = small_data(n=100)
    m = make_model(None)
    m.build((28, 28, 1))
    # 100 rows at batch 4 = 25 batches > the 16-batch window: exercises
    # the mid-loop drain, the final drain, and the padded last batch.
    preds = m.predict(x, batch_size=4)
    assert preds.shape == (100, 10)
    np.testing.assert_allclose(preds, m.predict(x, batch_size=64),
                               rtol=1e-5, atol=1e-5)


def test_predict_window_wrap_preserves_row_order():
    """Ordering regression for the sliding-window drain: when the batch
    count wraps past the 16-batch window (mid-loop pops interleave with
    fresh dispatches, then the tail drains in one batched wait), every
    output row must still correspond to ITS input row. Rows are made
    distinguishable by comparing against per-row single-batch predicts at
    window-straddling positions."""
    x, _ = small_data(n=18 * 4 + 2)  # 19 batches at batch 4: wraps + pad
    m = make_model(None)
    m.build((28, 28, 1))
    preds = m.predict(x, batch_size=4)
    assert preds.shape == (74, 10)
    # Spot rows on both sides of the window boundary (batch 15/16/18) and
    # inside the padded tail batch.
    for row in (0, 59, 63, 65, 72, 73):
        np.testing.assert_allclose(
            preds[row], m.predict(x[row:row + 1], batch_size=1)[0],
            rtol=1e-5, atol=1e-5,
        )
