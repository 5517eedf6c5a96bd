"""Laguna's block (window-512 and full grouped-query attention in one stack
at unlike head counts, a head-wise output gate, YaRN on a part of a full
layer's dimensions, sigmoid-routed dropless experts with a shared one)
against its plain reference ``benchmarks/reference/laguna.py``, at a small
size on the CPU with seeded weights: YaRN's frequencies against
``transformers``' own, the attention layer (window, partial rotation, gate;
values and every gradient, on the dense path and on the kernels), what a
layer without the new arguments keeps, the shares of an expert-parallel
layer, and the whole model (logits, loss, every gradient leaf) through
``Model.fit``'s own step for several layer patterns."""

import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import nn
from distributed_tpu.nn import attention as attention_lib
from distributed_tpu.obs.registry import default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_laguna, harness  # noqa: E402

MANIFEST = {"paths": ["tests/bench_harness", "benchmarks"]}
ref = harness.load_module(MANIFEST, "reference", "laguna")
fam = harness.load_module(MANIFEST, "families", "laguna")

D, EXPERTS, HIDDEN, TOP_K = 64, 16, 32, 3
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}


def close(a, b, rel=1e-4):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a - b))) < rel * scale + 1e-7


def assert_trees_close(got, want, rel=1e-4):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert close(a, b, rel), jax.tree_util.keystr(path)


# ------------------------------------------------------------------- YaRN --
@pytest.mark.parametrize("head_dim,partial,theta,original,fast,slow,factor", [
    (128, 0.5, 500000.0, 4096, 64, 1, 64),   # Laguna-XS.2's full layers
    (128, 1.0, 10000.0, 4096, 32, 1, 16), (64, 0.5, 1e6, 8192, 32, 2, 4),
    (16, 0.5, 500000.0, 32, 4, 1, 64),       # the tiny configuration's
])
def test_yarn_frequencies_are_transformers_own(head_dim, partial, theta,
                                               original, fast, slow, factor):
    """The program's and the reference's frequencies, each written out on
    its own, against ``modeling_rope_utils._compute_yarn_parameters`` of the
    installed ``transformers``, and the factor on cos and sin."""
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    scaling = {"rope_type": "yarn", "factor": factor, "beta_fast": fast,
               "beta_slow": slow,
               "original_max_position_embeddings": original}
    config = types.SimpleNamespace(
        rope_theta=theta, partial_rotary_factor=partial, head_dim=head_dim,
        hidden_size=head_dim * 4, num_attention_heads=4,
        max_position_embeddings=original * factor, rope_scaling=scaling)
    want, want_factor = rope_utils._compute_yarn_parameters(config, "cpu")
    want = want.numpy()
    r = int(head_dim * partial)
    got = attention_lib.yarn_inv_freq(
        r, theta, factor=factor, original_max_position=original,
        beta_fast=fast, beta_slow=slow)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(
        ref.yarn_frequencies(r, theta, factor, original, fast, slow), want,
        rtol=2e-6)
    layer = nn.GroupedQueryAttention(
        4, 2, head_dim, rope_theta=theta, rotary_dim=r, rope_scaling=scaling)
    np.testing.assert_allclose(layer.rotation[0], want, rtol=2e-6)
    assert layer.rotation[1] == pytest.approx(want_factor)
    assert want_factor == pytest.approx(0.1 * math.log(factor) + 1.0)
    # the interpolated band is there: the slowest pair turns ``factor``
    # times slower than theta's own, the fastest as fast
    own = theta ** (-np.arange(0, r, 2) / r)
    assert got[0] == pytest.approx(own[0])
    assert got[-1] == pytest.approx(own[-1] / factor, rel=1e-5)


def test_the_published_attention_factor_is_the_formulas():
    assert YARN["attention_factor"] == pytest.approx(
        0.1 * math.log(YARN["factor"]) + 1.0, rel=1e-9)


# -------------------------------------------------------- attention layer --
KW = {"n_kv": 2, "head_dim": 16, "eps": 1e-6, "window": 8,
      "theta_sliding": 10000.0, "rotary_sliding": 16, "theta_full": 500000.0,
      "rotary_full": 8, "yarn_factor": 64.0, "yarn_original": 32,
      "yarn_beta_fast": 4.0, "yarn_beta_slow": 1.0,
      "yarn_attention_factor": 1.4158883083359672, "q_block": 8}


def attention_layer(heads, sliding, head_dim=16, window=8, **kw):
    if sliding:
        layer = nn.GroupedQueryAttention(
            heads, 2, head_dim, rope_theta=10000.0, window=window, gate=True,
            **kw)
    else:
        layer = nn.GroupedQueryAttention(
            heads, 2, head_dim, rope_theta=500000.0,
            rotary_dim=head_dim // 2, rope_scaling=dict(
                YARN, original_max_position_embeddings=32, beta_fast=4),
            gate=True, **kw)
    layer.name = layer.default_name()
    return layer


def as_reference(p):
    return {**{k: p[k] for k in ("wq", "wk", "wv", "wo", "wg")},
            "q_norm": p["q_norm"]["scale"], "k_norm": p["k_norm"]["scale"]}


@pytest.mark.parametrize("heads,sliding", [
    (8, True), (6, True), (6, False), (8, False)])
def test_layer_matches_the_reference_values_and_gradients(heads, sliding):
    """Window, partial YaRN rotation and head-wise gate, on the dense path:
    the layer's output and its gradient in every leaf and in its input."""
    t = 40
    layer = attention_layer(heads, sliding)
    assert layer.name == ("multi_head_attention_swa" if sliding
                          else "multi_head_attention_gqa")
    params, state, _ = layer.init(jax.random.PRNGKey(1), (t, D))
    assert params["wg"].shape == (D, heads)
    assert params["wq"].shape == (D, heads * 16)
    assert (set(state) == set(attention_lib._WINDOW_COUNTERS)) is sliding
    # scales that are not one, so that the norms' gradients are tested
    params = dict(params, q_norm={"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (16,))}, k_norm={"scale": 1.0 + 0.1 * (
            jax.random.normal(jax.random.PRNGKey(5), (16,)))})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def system(p, x):
        return jnp.sum(w * layer.apply(p, state, x, train=True)[0])

    def reference(p, x):
        return sum(jnp.sum(w[b] * ref.attention(
            as_reference(p), x[b], sliding, kw=KW)) for b in range(2))

    got = jax.value_and_grad(system, (0, 1))(params, x)
    want = jax.value_and_grad(reference, (0, 1))(params, x)
    assert_trees_close(got, want)
    # and each variant of the reference is another function of the same
    # leaves: the window, the gate and the rotation are really there
    for variant in ("full_causal", "no_gate", "plain_rope"):
        other = sum(jnp.sum(w[b] * ref.attention(
            as_reference(params), x[b], sliding, kw=KW, variant=variant))
            for b in range(2))
        moved = abs(float(other) - float(want[0])) > 1e-3 * abs(
            float(want[0]))
        assert moved is (variant == "no_gate" or sliding == (
            variant == "full_causal"))


@pytest.mark.parametrize("heads,window", [(16, 64), (12, 200), (16, 512)])
def test_layer_on_the_kernels_matches_its_dense_path(heads, window):
    """128-wide heads in groups of eight and six on the windowed kernels
    (``flash=True``: the interpreter) against the same layer's dense path,
    and the counters of a train step: pairs inside the window and the pairs
    of the sub-tiles the kernels walked."""
    t = 256
    mk = lambda flash: attention_layer(heads, True, head_dim=128,
                                       window=window, flash=flash)
    layer, dense = mk(True), mk(False)
    params, state, _ = layer.init(jax.random.PRNGKey(1), (t, D))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, t, D))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda lay: lambda p, x: jnp.sum(
        w * lay.apply(p, state, x, train=True)[0])
    assert_trees_close(jax.value_and_grad(loss(layer), (0, 1))(params, x),
                       jax.value_and_grad(loss(dense), (0, 1))(params, x),
                       rel=2e-4)
    _, counted = layer.apply(params, state, x, train=True)
    inside = sum(min(i + 1, window) for i in range(t))
    assert {k: float(v) for k, v in counted.items()} == {
        "steps": 1.0, "queries": float(t),
        "causal_pairs": t * (t + 1) / 2, "window_pairs": float(inside),
        # one (256, 256) block in sub-tiles of 128: the upper right one is
        # above the diagonal; row 128 of the lower left one still sees the
        # columns from 129 - window on
        "walked_pairs": 3 * 128.0 * 128.0}
    assert float(dense.apply(params, state, x, train=True)[1][
        "walked_pairs"]) == 0.0
    assert layer.apply(params, state, x, train=False)[1] == {}


@pytest.mark.parametrize("heads", [12, 16])
def test_full_layer_on_the_kernels_matches_its_dense_path(heads):
    """128-wide heads in groups of six and eight with YaRN over 64 of the
    128 dimensions: norm and rotation in ``dtpu_head_norm_rope`` and the
    plain grouped flash kernels (``flash=True``: the interpreter) against
    the same layer's dense path, which norms and rotates in plain lines."""
    t = 256
    mk = lambda flash: attention_layer(heads, False, head_dim=128,
                                       flash=flash)
    layer, dense = mk(True), mk(False)
    params, state, _ = layer.init(jax.random.PRNGKey(1), (t, D))
    params["q_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (128,))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda lay: lambda p, x: jnp.sum(
        w * lay.apply(p, state, x, train=True)[0])
    assert_trees_close(jax.value_and_grad(loss(layer), (0, 1))(params, x),
                       jax.value_and_grad(loss(dense), (0, 1))(params, x),
                       rel=2e-4)


@pytest.mark.parametrize("sliding", [True, False])
def test_a_128_wide_layer_norms_and_rotates_in_the_kernels(sliding):
    """On the flash path at 128-wide heads the gradient's jaxpr calls
    ``dtpu_head_norm_rope`` for q and for k and its backward for each, and
    nothing under ``q_norm`` or ``k_norm`` makes a float32 (B, T, H, 128)
    view (the plain lines' ``_rms`` does: XLA:TPU relayouts it, PERF.md
    section 6, PR 39); the dense path and the tiny models' 16-wide heads
    stay on the plain lines. The trace-time counters say which."""
    from qk_prep import float32_head_views, gradient_jaxpr, kernel_calls

    mk = lambda **kw: attention_layer(16, sliding, dtype="bfloat16", **kw)
    jaxpr, counted = gradient_jaxpr(mk(head_dim=128, flash=True), 256, D, 2)
    prep = [c for c in kernel_calls(jaxpr) if "head_norm" in c]
    assert prep == ["dtpu_head_norm_rope"] * 2 + [
        "dtpu_head_norm_rope_bwd"] * 2
    assert float32_head_views(jaxpr) == []
    assert counted == (2, 0)
    for plain in (mk(head_dim=128, flash=False), mk(flash=True), mk()):
        jaxpr, counted = gradient_jaxpr(plain, 256, D, 2)
        assert [c for c in kernel_calls(jaxpr) if "head_norm" in c] == []
        assert len(float32_head_views(jaxpr, plain.head_dim)) >= 2
        assert counted == (0, 2)


def test_a_layer_without_the_new_arguments_is_the_layer_it_was():
    """Keye's and LFM2's cells: the same leaves, the same scope, no state
    and ``rope_half`` itself; the new arguments are refused where they make
    no sense."""
    layer = nn.GroupedQueryAttention(4, 2, 16, rope_theta=1e6)
    params, state, _ = layer.init(jax.random.PRNGKey(0), (8, D))
    assert layer.default_name() == "multi_head_attention_gqa"
    assert sorted(params) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    assert state == {} and layer.rotation is None and layer.window is None
    assert layer.sharding_hints() == {
        "wq": "col", "wk": "col", "wv": "col", "wo": "row"}
    gated = nn.GroupedQueryAttention(4, 2, 16, rope_theta=1e6, gate=True)
    with_gate, _, _ = gated.init(jax.random.PRNGKey(0), (8, D))
    assert gated.sharding_hints()["wg"] == "col"
    # a "default" rotation over the whole head is no rotation of its own
    plain = nn.GroupedQueryAttention(
        4, 2, 16, rotary_dim=16, rope_scaling={"rope_type": "default"})
    assert plain.rotation is None
    with pytest.raises(ValueError, match="window or an indexer"):
        nn.GroupedQueryAttention(4, 2, 128, window=64, index_topk=32)
    with pytest.raises(ValueError, match="rotary_dim"):
        nn.GroupedQueryAttention(4, 2, 16, rotary_dim=24)
    with pytest.raises(ValueError, match="'yarn' and 'default'"):
        nn.GroupedQueryAttention(4, 2, 16, rope_scaling={
            "rope_type": "llama3", "factor": 8})


# ------------------------------------------------------------ expert layer --
def expert_layer(held=None, offset=0, shared=HIDDEN):
    return nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K, experts_held=held,
                          expert_offset=offset, shared_hidden_dim=shared,
                          routed_scaling=2.5, bias_update_rate=0.0)


def test_the_shares_routed_parts_and_the_shared_expert_once_make_the_layer():
    """What the guide's section 4 asks of a share: the routed parts that all
    the chips' shares give, with what every chip computes alike, the shared
    expert, counted once, add up to the uncut reference's layer (sigmoid
    scores over all experts, gates over the chosen's sum times 2.5, no
    selection bias)."""
    chips, held = 4, EXPERTS // 4
    whole = expert_layer()
    params, state, _ = whole.init(jax.random.PRNGKey(13), (24, D))
    assert "shared" in params
    assert not np.any(np.asarray(state["router_bias"]))
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 24, D))
    routed = jnp.zeros_like(x)
    for chip in range(chips):
        p = {k: (v[held * chip:held * (chip + 1)]
                 if k in ("w_gate", "w_up", "w_down") else v)
             for k, v in params.items() if k != "shared"}
        y, new = expert_layer(held, held * chip, shared=0).apply(
            p, state, x, train=True)
        routed = routed + y
        # nothing moves the selection bias
        assert not np.any(np.asarray(new["router_bias"]))
    shared, _ = whole.shared.apply(params["shared"], {}, x)
    gated = lambda p: {"gate": p["dense"]["kernel"],
                       "up": p["dense_1"]["kernel"],
                       "down": p["dense_2"]["kernel"]}
    b = {"router": params["router"], "router_bias": state["router_bias"],
         "experts": {"gate": params["w_gate"], "up": params["w_up"],
                     "down": params["w_down"]},
         "shared": gated(params["shared"])}
    want, _ = ref.ds.experts(b, x.reshape(-1, D), top_k=TOP_K, scaling=2.5,
                             expert_offset=0)
    assert close(routed + shared, want.reshape(x.shape))
    # a share with the shared expert computes it whole: the sum of the four
    # would count it four times
    one, _ = expert_layer(held, 0).apply(
        dict(params, **{k: params[k][:held]
                        for k in ("w_gate", "w_up", "w_down")}),
        state, x, train=True)
    first, _ = expert_layer(held, 0, shared=0).apply(
        {k: (v[:held] if v.ndim == 3 else v) for k, v in params.items()
         if k != "shared"}, state, x, train=True)
    assert close(one - first, shared)


# ------------------------------------------------------------- whole model --
def tiny_config(layer_types=None, mlp=None, heads=None):
    cfg = harness.load_json(os.path.join(
        ROOT, "tests", "bench_harness", "configs", "laguna-tiny.json"))
    if layer_types is not None:
        cfg = dict(cfg, layer_types=list(layer_types),
                   mlp_layer_types=list(mlp),
                   num_attention_heads_per_layer=list(heads),
                   num_hidden_layers=len(layer_types))
    return cfg


def built(cfg, t=48):
    model = dtpu.Model(fam.build_module(cfg))
    model.compile(optimizer=dtpu.optim.Adam(1e-3, b1=0.9),
                  loss="sparse_categorical_crossentropy", metrics=())
    model.build((t,), seed=5)
    tok = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, t + 1)).astype(np.int32)
    return model, tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's tiny configuration (float32): full, sliding, sliding
    at 6, 8, 8 heads over 2; one dense layer; experts 4-7 of 16 held and a
    shared one; 512 rows."""
    cfg = tiny_config()
    return (cfg,) + built(cfg)


FULL, SLIDING = "full_attention", "sliding_attention"


@pytest.mark.parametrize("layer_types,mlp,heads", [
    ((FULL, SLIDING, SLIDING), ("dense", "sparse", "sparse"), (6, 8, 8)),
    ((FULL, SLIDING, SLIDING, SLIDING, FULL),
     ("dense", "sparse", "sparse", "sparse", "sparse"), (6, 8, 8, 8, 6)),
    ((SLIDING, FULL), ("sparse", "dense"), (4, 2)),
])
def test_model_matches_the_reference_through_fits_own_step(layer_types, mlp,
                                                           heads):
    cfg = tiny_config(layer_types, mlp, heads)
    model, x, y = built(cfg, t=40)
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    assert "head_w" in p_ref and len(p_ref["blocks"]) == len(layer_types)
    assert [("swa" in b, "mlp" in b) for b in p_ref["blocks"]] == [
        (k == SLIDING, m == "dense") for k, m in zip(layer_types, mlp)]
    assert [b["swa" if "swa" in b else "attn"]["wg"].shape[1]
            for b in p_ref["blocks"]] == list(heads)
    logits, _ = model.module.apply(model.params, model.state, jnp.asarray(x),
                                   train=True, rng=None)
    assert close(logits[0], ref.forward(p_ref, x[0], kw=kw))
    # the forward and backward half of the train step, as fit jits it
    loss, state, grads, _ = jax.jit(model._grad_eval_body())(
        model.params, model.state, jnp.asarray(x), jnp.asarray(y), None)
    (ref_loss, own), ref_grads = jax.value_and_grad(
        lambda p: ref.sequence_loss(
            fam.reference_params(p, model.state, cfg), x[0], y[0], kw=kw),
        has_aux=True)(model.params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_trees_close(grads, ref_grads)
    forced = fam.choices(state, cfg)
    assert len(forced) == mlp.count("sparse")
    for choice, chosen in zip(forced, own["experts"]):
        np.testing.assert_array_equal(np.sort(choice, -1),
                                      np.sort(chosen, -1))
    # and the driver's comparison of it finds no flip and no difference
    compared = jax.device_get(ref.compare(
        p_ref, x, y, kw=kw, system_grads=fam.reference_params(
            grads, model.state, cfg), forced=forced))
    checks = fam.first_step_checks(
        float(loss), float(compared["grad_norm"]), compared,
        40 * cfg["num_experts_per_tok"])
    assert all(checks[k] for k in fam.FIRST_STEP_CHECKS)
    assert set(checks["grad_differences"]) == set(ref.GROUPS)
    assert max(checks["grad_differences"].values()) < 1e-4
    assert checks["flipped_pairs_share"] == [0.0] * len(forced)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["model.layers_sliding"] == layer_types.count(SLIDING)
    assert gauges["model.layers_attention"] == len(layer_types)
    assert gauges["model.layers_experts"] == mlp.count("sparse")


@pytest.mark.parametrize("variant,group", [
    ("int8", None), ("full_causal", "sliding_attention"),
    ("no_gate", "full_attention"), ("plain_rope", "full_attention")])
def test_the_comparison_tells_a_wrong_model_from_the_program(tiny, variant,
                                                             group):
    """The cell's four controls at the tiny size: the reference computed
    wrongly on purpose, held to its own choices and handed to the driver's
    comparison in the program's place, fails a limit, and where it is wrong
    by construction its group says so."""
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    run = lambda forced: ref.loss_and_grads(
        p_ref, jnp.asarray(x), jnp.asarray(y), kw=kw, variant=variant,
        forced=forced)
    held = run(None)[2]["experts"]
    wrong_loss, wrong, _ = run(held)
    compared = jax.device_get(ref.compare(
        p_ref, x, y, kw=kw, system_grads=wrong, forced=held))
    checks = fam.first_step_checks(
        float(wrong_loss), float(ref._norm(wrong)), compared,
        48 * cfg["num_experts_per_tok"])
    assert not all(checks[k] for k in fam.FIRST_STEP_CHECKS)
    if group is not None:
        assert checks["grad_differences"][group] > 0.1
        assert not checks["grad_differences_agree"]


def test_fit_counts_the_windowed_and_the_expert_layers_and_learns(tiny):
    cfg, model, x, y = tiny
    hist = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=1,
                     shuffle=False, verbose=0, seed=0)
    more = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=5,
                     shuffle=False, verbose=0, seed=0)
    assert more.history["loss"][-1] < hist.history["loss"][0]
    fit = model.last_fit_telemetry
    assert sorted(fit["moe"]) == ["residual_3/main/moe",
                                  "residual_5/main/moe"]
    assert sorted(fit["window"]) == [
        "residual_2/main/multi_head_attention_swa",
        "residual_4/main/multi_head_attention_swa"]
    inside = flops_laguna.window_pairs(48, cfg["sliding_window"])
    assert inside == sum(min(i + 1, 16) for i in range(48))
    for c in fit["window"].values():
        assert c == {"steps": 6.0, "queries": 6.0 * 48,
                     "causal_pairs": 6.0 * 48 * 49 / 2,
                     "window_pairs": 6.0 * inside, "walked_pairs": 0.0}
    assert "select" not in fit
    # no selection bias: the buffer stays at zeros, whatever the loads
    bias = model.state["residual_3"]["main"]["moe"]["router_bias"]
    assert not np.any(np.asarray(bias))
    assert attention_lib.window_counters(model.state).keys() == fit[
        "window"].keys()


def test_the_operation_count_follows_the_shapes():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "laguna-xs.2.json"))
    assert flops_laguna.attention_params(cfg, 48) == 29_458_432
    assert flops_laguna.attention_params(cfg, 64) == 37_879_808
    assert flops_laguna.expert_params(cfg, 512) == 3_145_728
    assert flops_laguna.causal_pairs(8192) == 33_558_528
    assert flops_laguna.window_pairs(8192, 512) == 4_063_488
    assert flops_laguna.window_pairs(300, 512) == 300 * 301 // 2
    # forward a step, by part, as ISSUE 38 counts it (TFLOP)
    fwd = 8192 * fam.train_flops_per_token(cfg, 8192) / 3.0
    assert fwd == pytest.approx(6.40e12, rel=0.005)
    t, hd = 8192, 128
    full = 2 * 4.0 * flops_laguna.causal_pairs(t) * 48 * hd
    windows = 3 * 4.0 * flops_laguna.window_pairs(t, 512) * 64 * hd
    assert full == pytest.approx(1.65e12, rel=0.005)
    assert windows == pytest.approx(0.40e12, rel=0.005)
    assert (full + windows + 2 * t * (
        2 * 29_458_432 + 3 * 37_879_808)) / fwd == pytest.approx(
            0.76, abs=0.005)
    # the kernels' costs: the same bytes under a window, fewer products
    ops, nbytes = flops_laguna.gqa_flash_cost(
        "fwd", 1, flops_laguna.window_pairs(t, 512), t, 64, 8, hd)
    assert ops == 2.0 * 4_063_488 * 64 * hd * 2
    assert nbytes == t * hd * 2 * (2 * 64 + 2 * 8)
    # the parameters the share holds: 389.6M
    module = fam.build_module(cfg)
    shapes, state, _ = jax.eval_shape(
        lambda k: module.init(k, (128,)), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == pytest.approx(389.6e6, rel=0.002)
    assert shapes["embedding"]["table"].shape == (12544, 2048)
    assert shapes["dense"]["kernel"].shape == (2048, 12544)
    mixers = [next(k for k in shapes[f"residual_{2 * i}" if i else "residual"
                                     ]["main"] if k.startswith("multi_head"))
              for i in range(5)]
    assert mixers == ["multi_head_attention_gqa"] + [
        "multi_head_attention_swa"] * 3 + ["multi_head_attention_gqa"]
    assert shapes["residual_3"]["main"]["moe"]["w_gate"].shape == (
        8, 2048, 512)
    assert shapes["residual_3"]["main"]["moe"]["router"].shape == (2048, 256)
