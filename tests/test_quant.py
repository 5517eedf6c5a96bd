"""Int8 weight-only quantization (distributed_tpu.quant).

Pins: the per-channel symmetric scheme itself (bounded per-element error,
scale shapes, double-quantize guard), the serving surfaces from quantized
weights (predict / greedy generate / serving.Engine token parity), the
checkpoint round-trips the ISSUE names (f32 ckpt -> quantize-on-load, and
quantized q+scale trees through Checkpointer AND ShardedCheckpointer),
and the int8 collective accounting in Strategy.comm_bytes_estimate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import quant
from distributed_tpu.utils.profiler import tree_bytes_per_device

VOCAB, LAYERS, D, HEADS, MAXLEN = 96, 2, 32, 2, 64


def _lm():
    m = dtpu.Model(dtpu.models.transformer_lm(
        VOCAB, num_layers=LAYERS, d_model=D, num_heads=HEADS,
        max_len=MAXLEN))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    m.build((16,), seed=0)
    return m


def _lm_wide():
    # Build-only (no step ever traces): wide enough that the f32-kept 1-D
    # leaves and the per-channel scales are the ~1% dilution they are on
    # real serving shapes — the byte/collective gates are meaningless on
    # d=32 toys where biases are 5% of the tree.
    m = dtpu.Model(dtpu.models.transformer_lm(
        VOCAB, num_layers=2, d_model=128, num_heads=4, max_len=MAXLEN))
    m.compile(optimizer="adam", loss="sparse_categorical_crossentropy")
    m.build((16,), seed=0)
    return m


def _toks(b=4, t=16, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, (b, t)).astype(np.int32)


# ------------------------------------------------------------ the scheme --
def test_quantize_leaf_roundtrip_error_bound():
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (64, 24)))
    qd = quant.quantize_leaf(w)
    assert qd["q"].dtype == jnp.int8 and qd["q"].shape == w.shape
    assert qd["scale"].dtype == jnp.float32 and qd["scale"].shape == (24,)
    back = np.asarray(quant.dequantize(qd))
    # Symmetric round-to-nearest: error <= scale/2 per element.
    assert np.all(np.abs(back - w) <= np.asarray(qd["scale"]) / 2 + 1e-7)


def test_quantize_tree_selects_matrices_only():
    tree = {"kernel": jnp.ones((8, 4)), "bias": jnp.ones((4,)),
            "step": jnp.arange(3)}
    qt = quant.quantize_tree(tree)
    assert quant.is_quantized_leaf(qt["kernel"])
    assert not quant.is_quantized_leaf(qt["bias"])
    assert qt["bias"].dtype == jnp.float32
    assert qt["step"].dtype == tree["step"].dtype
    with pytest.raises(ValueError, match="already"):
        quant.quantize_tree(qt)


def test_zero_channel_scale_is_finite():
    w = np.zeros((8, 4), np.float32)
    w[:, 0] = 3.0
    qd = quant.quantize_leaf(w)
    assert np.all(np.isfinite(np.asarray(qd["scale"])))
    assert np.array_equal(np.asarray(quant.dequantize(qd)), w)


# -------------------------------------------------------- serving parity --
def test_predict_logits_bounded_and_top1():
    m = _lm()
    q = _lm()
    quant.quantize_model(q)
    x = _toks()
    ref = m.predict(x, batch_size=4)
    out = q.predict(x, batch_size=4)
    assert float(np.max(np.abs(out - ref))) < 0.25  # bounded logit error
    agree = float(np.mean(np.argmax(out, -1) == np.argmax(ref, -1)))
    assert agree >= 0.9  # top-1 agreement, teacher-forced


def test_greedy_generate_agreement():
    m = _lm()
    q = _lm()
    quant.quantize_model(q)
    x = _toks(b=2, t=8)
    g_ref = m.generate(x, 8, temperature=0.0)
    g_q = q.generate(x, 8, temperature=0.0)
    assert g_ref.shape == g_q.shape
    # Greedy decode re-feeds its own tokens, so one flipped near-tie can
    # fork the suffix — pin a high agreement fraction, not equality.
    assert float(np.mean(g_ref == g_q)) >= 0.8


def test_engine_serves_quantized_weights_token_exact():
    """Continuous-batching serving from int8 weights is token-identical
    to the quantized model's own generate() — the engine contract from
    test_serving, now over a quantized param tree."""
    import distributed_tpu.serving as serving

    q = _lm()
    quant.quantize_model(q)
    x = _toks(b=3, t=8, seed=2)
    engine = serving.Engine(q, max_slots=2, block_size=8, max_len=32)
    outs = engine.run([(x[i], 6) for i in range(3)])
    for i in range(3):
        ref = q.generate(x[i:i + 1], 6, temperature=0.0)[0]
        assert np.array_equal(outs[i], ref)


def test_fit_raises_on_quantized_model():
    q = _lm()
    quant.quantize_model(q)
    x = _toks()
    with pytest.raises(RuntimeError, match="quantized"):
        q.fit(x, x, batch_size=4, epochs=1, verbose=0)
    with pytest.raises(ValueError, match="already"):
        quant.quantize_model(q)


# --------------------------------------------------------- checkpointing --
def test_quantize_on_load_from_f32_checkpoint(tmp_path):
    """The serving flow: f32 training checkpoint -> restore -> quantize.
    Equals quantizing the original weights directly (quantization is a
    pure function of the f32 values)."""
    m = _lm()
    ckpt = dtpu.Checkpointer(tmp_path / "ck")
    ckpt.save(m, step=0)

    fresh = _lm()
    fresh.build((16,), seed=1)  # different init: restore must overwrite
    ckpt.restore_into(fresh)
    quant.quantize_model(fresh)

    direct = _lm()
    quant.quantize_model(direct)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(fresh.params)),
                    jax.tree_util.tree_leaves(jax.device_get(direct.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_quantized_checkpoint_roundtrip(tmp_path):
    """Quantized q + scale trees round-trip EXACTLY through Checkpointer
    (int8 payloads and f32 scales are both lossless in npz)."""
    q = _lm()
    quant.quantize_model(q)
    ckpt = dtpu.Checkpointer(tmp_path / "ck")
    ckpt.save(q, step=7)

    q2 = _lm()
    quant.quantize_model(q2)  # same weights -> same structure
    step = ckpt.restore_into(q2)
    assert step == 7
    assert quant.is_quantized(q2.params)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(q.params)),
                    jax.tree_util.tree_leaves(jax.device_get(q2.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_quantized_sharded_checkpoint_roundtrip(tmp_path):
    """Same exact round-trip through ShardedCheckpointer under FSDP: the
    int8 q leaves save/restore as per-process shard blocks."""
    strat = dtpu.FSDP()
    with strat.scope():
        q = _lm()
    quant.quantize_model(q)
    ckpt = dtpu.ShardedCheckpointer(tmp_path / "sck")
    ckpt.save(q, step=3)

    with strat.scope():
        q2 = _lm()
    quant.quantize_model(q2)
    assert ckpt.restore_into(q2) == 3
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(q.params)),
                    jax.tree_util.tree_leaves(jax.device_get(q2.params))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


# ------------------------------------------------- bytes and collectives --
def test_param_bytes_ratio():
    m = _lm_wide()
    host = jax.device_get(m.params)
    ratio = (quant.tree_param_bytes(host)
             / quant.tree_param_bytes(quant.quantize_tree(host)))
    # biases/norms/scales stay f32, so the ratio sits under the ideal 4x
    # but must clear the serving gate on even this small LM.
    assert ratio >= 3.5


def test_resident_bytes_of_the_serving_lm():
    """The serving LM (4 layers of 256, 512 rows) as the devices hold it:
    3,454,976 f32 parameters, of which quantize_model keeps the 14,336
    one-dimensional ones in f32 and gives the matrices' 3,440,640 a byte
    each and 10,240 f32 scales."""
    m = dtpu.Model(dtpu.models.transformer_lm(
        512, num_layers=4, d_model=256, num_heads=8, max_len=128))
    m.build((32,), seed=0)
    f32 = tree_bytes_per_device(m.params)["max_bytes_per_device"]
    quant.quantize_model(m)
    int8 = tree_bytes_per_device(m.params)["max_bytes_per_device"]
    assert (f32, int8) == (13819904, 3538944)  # 3.905 x


def test_fsdp_comm_bytes_int8(devices):
    strat = dtpu.FSDP()
    host = jax.device_get(_lm_wide().params)
    qtree = quant.quantize_tree(host)
    gk = "gathered_param_bytes_per_device"
    f32 = strat.comm_bytes_estimate(host)[gk]
    bf16 = strat.comm_bytes_estimate(host, compute_dtype=jnp.bfloat16)[gk]
    int8 = strat.comm_bytes_estimate(qtree, compute_dtype=jnp.bfloat16)[gk]
    assert f32 / int8 >= 3.5  # 4x on weights, diluted ~1% by f32 leaves
    assert bf16 / int8 >= 1.9  # 2x on weights (exact), same dilution
    # the q payloads themselves are priced at exactly 1 byte/elem
    one_kernel = {"k": host["dense"]["kernel"]}
    q_kernel = quant.quantize_tree(one_kernel)
    b_q = strat.comm_bytes_estimate(
        {"k": {"q": q_kernel["k"]["q"]}}, compute_dtype=jnp.bfloat16)[gk]
    b_bf16 = strat.comm_bytes_estimate(
        one_kernel, compute_dtype=jnp.bfloat16)[gk]
    assert b_bf16 == 2 * b_q


def test_quantized_model_under_fsdp_serves(devices):
    """Quantized weights place under FSDP (int8 shards + f32 scales) and
    the decode path still matches the single-device quantized model."""
    strat = dtpu.FSDP()
    with strat.scope():
        q = _lm_wide()
    quant.quantize_model(q)
    # q leaves actually sharded int8 on the mesh
    leaf = q.params["residual"]["main"]["multi_head_attention"]["wq"]
    assert leaf["q"].dtype == jnp.int8
    assert len({s.device for s in leaf["q"].addressable_shards}) == 8

    ref = _lm_wide()
    quant.quantize_model(ref)
    x = _toks(b=8, t=8, seed=5)
    np.testing.assert_allclose(
        q.predict(x, batch_size=8), ref.predict(x, batch_size=8),
        rtol=2e-5, atol=2e-6,
    )


def test_mixed_precision_policy_composes():
    """Quantized weights under compile(precision="mixed_bfloat16"): the
    dequantized kernels cast to bf16 compute, logits stay close to the
    f32-compute quantized model."""
    q32 = _lm()
    quant.quantize_model(q32)
    qbf = dtpu.Model(dtpu.models.transformer_lm(
        VOCAB, num_layers=LAYERS, d_model=D, num_heads=HEADS,
        max_len=MAXLEN))
    qbf.compile(optimizer="adam", loss="sparse_categorical_crossentropy",
                precision="mixed_bfloat16")
    qbf.build((16,), seed=0)
    quant.quantize_model(qbf)
    assert qbf.decode_dtype() == jnp.bfloat16
    x = _toks(b=2, t=8, seed=7)
    a = q32.predict(x, batch_size=2)
    b = qbf.predict(x, batch_size=2)
    assert float(np.max(np.abs(a - b))) < 0.5  # bf16 rounding, not garbage
    agree = float(np.mean(np.argmax(a, -1) == np.argmax(b, -1)))
    assert agree >= 0.9
