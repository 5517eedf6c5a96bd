"""LFM2-MoE's block (gated short convolutions and grouped-query attention in
one stack, a dense MLP then sigmoid-routed dropless experts, a tied head)
against its plain reference ``benchmarks/reference/lfm2_moe.py``, at a small
size on the CPU with seeded weights: the short convolution (values, every
gradient, a dense Toeplitz form of its taps, what each output reads), the
tied table's one leaf and its gradient, the shares of an expert-parallel
layer, and the whole model (logits, loss, every gradient leaf) through
``Model.fit``'s own step for several layer patterns."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import nn
from distributed_tpu.nn.layers import gated_taps
from distributed_tpu.obs.registry import default_registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_lfm2_moe, harness  # noqa: E402

MANIFEST = {"paths": ["tests/bench_harness", "benchmarks"]}
ref = harness.load_module(MANIFEST, "reference", "lfm2_moe")
fam = harness.load_module(MANIFEST, "families", "lfm2_moe")

D, EXPERTS, HIDDEN, TOP_K = 64, 16, 32, 3
KW = {"top_k": TOP_K, "scaling": 1.0, "expert_offset": 0}


def close(a, b, rel=1e-4):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a - b))) < rel * scale + 1e-7


def assert_trees_close(got, want, rel=1e-4):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert close(a, b, rel), jax.tree_util.keystr(path)


# -------------------------------------------------------- short convolution --
def conv_layer(k=3):
    layer = nn.ShortConv(k)
    layer.name = layer.default_name()
    return layer


@pytest.mark.parametrize("k", [3, 1, 4])
def test_short_conv_matches_the_reference_values_and_gradients(k):
    t = 24
    layer = conv_layer(k)
    params, state, shape = layer.init(jax.random.PRNGKey(1), (t, D))
    assert state == {} and shape == (t, D)
    assert {n: v.shape for n, v in params.items()} == {
        "w_in": (D, 3 * D), "taps": (k, D), "w_out": (D, D)}
    assert float(jnp.max(jnp.abs(params["taps"]))) <= k ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def system(p, x):
        y, new = layer.apply(p, state, x, train=True)
        assert new == {}
        return jnp.sum(y * w)

    def reference(p, x):
        return sum(jnp.sum(w[b] * ref.short_conv(p, x[b])) for b in range(2))

    got = jax.value_and_grad(system, (0, 1))(params, x)
    want = jax.value_and_grad(reference, (0, 1))(params, x)
    assert_trees_close(got, want)


def test_the_taps_are_a_banded_lower_triangular_matrix_a_channel():
    """Against the dense form: h = M_d g a channel d, M_d (T, T) Toeplitz
    with taps[K - 1 - i, d] on its i-th sub-diagonal and zeros elsewhere."""
    t, d, k = 12, 8, 3
    bcz = jax.random.normal(jax.random.PRNGKey(4), (2, t, 3 * d))
    taps = jax.random.normal(jax.random.PRNGKey(5), (k, d))
    w = jax.random.normal(jax.random.PRNGKey(6), (2, t, d))
    lag = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]     # t - s

    def dense(bcz, taps):
        band = jnp.where(
            ((lag >= 0) & (lag < k))[..., None],
            taps[jnp.clip(k - 1 - lag, 0, k - 1)], 0.0)       # (T, T, d)
        b, c, z = jnp.split(bcz, 3, axis=-1)
        return c * jnp.einsum("tsd,bsd->btd", band, b * z)

    loss = lambda f: lambda a, t_: jnp.sum(f(a, t_) * w)
    got = jax.value_and_grad(loss(gated_taps), (0, 1))(bcz, taps)
    want = jax.value_and_grad(loss(dense), (0, 1))(bcz, taps)
    assert_trees_close(got, want)
    assert close(gated_taps(bcz, taps), dense(bcz, taps))


def test_short_conv_reads_the_two_positions_before_and_nothing_else():
    t, d = 10, 8
    layer = conv_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(7), (t, d))
    x = jax.random.normal(jax.random.PRNGKey(8), (1, t, d))
    f = lambda x: layer.apply(params, state, x)[0]
    # an input changed at position 6 moves no output before it, bit for bit,
    # and none after position 8
    moved = f(x.at[0, 6].add(1.0)) - f(x)
    changed = np.asarray(jnp.any(moved != 0.0, axis=-1))[0]
    assert list(np.nonzero(changed)[0]) == [6, 7, 8]
    # and the output at t depends on inputs t - 2 .. t alone
    jac = jax.jacobian(lambda x: f(x)[0])(x)[:, :, 0]          # (T, d, T, d)
    reads = np.asarray(jnp.any(jac != 0.0, axis=(1, 3)))       # (T out, T in)
    want = np.array([[0 <= o - i < 3 for i in range(t)] for o in range(t)])
    np.testing.assert_array_equal(reads, want)


def test_the_backward_pass_keeps_the_layers_input_alone():
    """``gated_taps`` is a checkpoint: between the two passes lives what it
    was handed, not the float32 products autodiff multiplies by."""
    bcz = jnp.ones((1, 16, 3 * 8), jnp.bfloat16)
    taps = jnp.ones((3, 8))
    jaxpr = jax.make_jaxpr(
        lambda a, t: jax.vjp(gated_taps, a, t)[1])(bcz, taps)
    kept = [v.aval for v in jaxpr.jaxpr.outvars]
    assert sorted((a.shape, str(a.dtype)) for a in kept) == [
        ((1, 16, 24), "bfloat16"), ((3, 8), "float32")]


def test_short_conv_does_not_decode():
    layer = conv_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(0), (4, 8))
    with pytest.raises(NotImplementedError, match="ShortConv"):
        layer.decode(params, state, {}, jnp.zeros((1, 1, 8)), pos=0)
    with pytest.raises(ValueError, match="kernel_size"):
        nn.ShortConv(0)


# ------------------------------------- the attention layer's 64-wide heads --
# The gradient's jaxpr of a layer of 64-wide heads with the flash path on, as
# the commit before PR 39 traced it (PR 39 norms and rotates 128-wide heads
# in a kernel of their own; these take the plain lines, to the letter).
# Re-pinned where the flash backward's delta became a contraction on the
# (b, T, H x D) layout: against commit 7c33136's text only delta's equations
# differ (``test_flash_attention.py:PARENT_JAXPRS``).
PARENT_JAX = "0.9.0"
PARENT_JAXPR = "5e09f9e6dfa774f550012ddbe109a6e7bf812b362d6784b19efe6a8f23c03b3a"


@pytest.mark.parametrize("head_dim,flash", [(64, True), (64, False),
                                            (16, True)])
def test_heads_narrower_than_a_lane_tile_keep_the_plain_norm_and_rotation(
        head_dim, flash):
    from qk_prep import digest, gradient_jaxpr, kernel_calls

    layer = nn.GroupedQueryAttention(
        8, 2, head_dim, rope_theta=1e6, epsilon=1e-5, dtype="bfloat16",
        flash=flash)
    layer.name = layer.default_name()
    jaxpr, counted = gradient_jaxpr(layer, 256, D)
    assert counted == (0, 2)  # fused, plain: q and k each
    assert [c for c in kernel_calls(jaxpr) if "head_norm" in c] == []
    if (head_dim, flash) == (64, True):
        if jax.__version__ != PARENT_JAX:
            pytest.skip(
                f"the parent's digest was taken under JAX {PARENT_JAX}")
        assert digest(jaxpr) == PARENT_JAXPR


# ------------------------------------------------------------- tied head --
def lm(tie, layer_types=("conv", "full_attention"), dense=1, **kw):
    return dtpu.models.lfm2_moe_lm(
        96, layer_types=layer_types, num_dense_layers=dense, d_model=32,
        num_heads=4, num_kv_heads=2, head_dim=8, d_ff=48, num_experts=8,
        top_k=2, moe_hidden=16, experts_held=4, expert_offset=2,
        rope_theta=10000.0, tie_embeddings=tie, flash=False, **kw)


def test_the_tied_table_is_one_leaf_and_its_gradient_the_sum_of_both_uses():
    t = 12
    tied, untied = lm(True), lm(False)
    assert isinstance(tied, nn.TiedSequential)
    assert not isinstance(untied, nn.TiedSequential)
    p, state, shape = tied.init(jax.random.PRNGKey(9), (t,))
    assert shape == (t, 96) and "dense" not in p
    assert [l.name for l in tied.layers][-1] == "dense"
    q = dict(p, dense={"kernel": p["embedding"]["table"].T})
    assert jax.tree_util.tree_structure(q) == jax.tree_util.tree_structure(
        untied.init(jax.random.PRNGKey(9), (t,))[0])
    tok = jax.random.randint(jax.random.PRNGKey(10), (2, t), 0, 96)
    w = jax.random.normal(jax.random.PRNGKey(11), (2, t, 96))
    loss = lambda m: lambda p: jnp.sum(
        m.apply(p, state, tok, train=True)[0] * w)
    got, g_tied = jax.value_and_grad(loss(tied))(p)
    want, g_untied = jax.value_and_grad(loss(untied))(q)
    assert close(got, want)
    assert close(g_tied["embedding"]["table"],
                 g_untied["embedding"]["table"]
                 + g_untied["dense"]["kernel"].T)
    g_untied.pop("dense")
    g_tied["embedding"], g_untied["embedding"] = {}, {}
    assert_trees_close(g_tied, g_untied)
    # one leaf in the optimizer too
    model = dtpu.Model(tied)
    model.compile(optimizer=dtpu.optim.Adam(1e-3),
                  loss="sparse_categorical_crossentropy", metrics=())
    model.build((t,), seed=0)
    mu = jax.tree_util.tree_leaves_with_path(model.opt_state)
    tables = [path for path, leaf in mu if leaf.shape == (96, 32)]
    assert len(tables) == 2  # Adam's mu and nu of the one table
    assert model.num_params == sum(
        a.size for a in jax.tree_util.tree_leaves(p))


def test_a_tied_head_outside_its_container_says_so():
    head = nn.TiedHead(96)
    head.name = head.default_name()
    assert head.name == "dense"
    assert head.init(jax.random.PRNGKey(0), (12, 32)) == ({}, {}, (12, 96))
    with pytest.raises(ValueError, match="TiedSequential"):
        head.apply({}, {}, jnp.zeros((1, 12, 32)))
    with pytest.raises(ValueError, match="owns parameters"):
        nn.TiedSequential([nn.Embedding(96, 32), nn.Dense(96)]).init(
            jax.random.PRNGKey(0), (12,))
    model = dtpu.Model(lm(True))
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
                  metrics=())
    model.build((12,), seed=0)
    with pytest.raises(NotImplementedError, match="ShortConv"):
        model.module.decode(model.params, model.state, {},
                            jnp.zeros((1, 1), jnp.int32), pos=0)


def test_the_assembly_picks_each_layers_mixer_and_ffn_on_its_own():
    kinds = ("full_attention", "conv", "conv", "full_attention")
    module = lm(True, kinds, dense=3)
    p, _, _ = module.init(jax.random.PRNGKey(0), (8,))
    mixers = [next(k for k in p[f"residual_{2 * i}" if i else "residual"][
        "main"] if k != "rms_norm") for i in range(4)]
    assert mixers == ["multi_head_attention_gqa", "short_conv", "short_conv",
                      "multi_head_attention_gqa"]
    ffns = [next(k for k in p[f"residual_{2 * i + 1}"]["main"]
                 if k != "rms_norm") for i in range(4)]
    assert ffns == ["gated_mlp", "gated_mlp", "gated_mlp", "moe"]
    gauges = default_registry().snapshot()["gauges"]
    assert [gauges[f"model.layers_{k}"] for k in (
        "conv", "attention", "dense", "experts")] == [2, 2, 3, 1]
    with pytest.raises(ValueError, match="layer_types"):
        lm(True, ("conv", "sliding_attention"))


# ---------------------------------------------------------- expert layer --
def expert_layer(held=None, offset=0):
    return nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K, experts_held=held,
                          expert_offset=offset)


def test_the_four_shares_make_the_uncut_layer():
    """What the guide's section 4 asks of a share: the routed parts of all
    four chips add up to the uncut reference's layer (sigmoid scores, a
    selection bias that is not zero, gates over their sum + 1e-6); there is
    no shared expert to count once."""
    layer = expert_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(13), (24, D))
    assert "shared" not in params
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(15), (EXPERTS,))
    state = dict(state, router_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 24, D))
    total = jnp.zeros_like(x)
    for chip in range(4):
        p = dict(params, **{k: params[k][4 * chip:4 * chip + 4]
                            for k in ("w_gate", "w_up", "w_down")})
        y, _ = expert_layer(4, 4 * chip).apply(p, state, x, train=True)
        total = total + y
    b = {"router": params["router"], "router_bias": bias,
         "experts": {"gate": params["w_gate"], "up": params["w_up"],
                     "down": params["w_down"]}}
    want, own = ref.experts(b, x.reshape(-1, D), kw=KW)
    assert close(total, want.reshape(x.shape))
    # the bias chose: without it the reference picks other experts somewhere
    plain, _, _ = ref.route(dict(b, router_bias=jnp.zeros((EXPERTS,))),
                            x.reshape(-1, D), top_k=TOP_K, scaling=1.0)
    assert np.any(np.sort(plain, -1) != np.sort(own, -1))


def test_the_gates_agree_with_the_published_divisor_to_rounding():
    """The layer divides by the chosen scores' sum + 1e-20, the published
    code by that sum + 1e-6 (the reference's ``GATE_EPS``): over four
    sigmoid scores, a sum near 2, the two differ by about 5e-7 of a gate,
    float32's rounding, so the layer carries no option for it."""
    layer = expert_layer()
    params, _, _ = layer.init(jax.random.PRNGKey(16), (64, D))
    tokens = jax.random.normal(jax.random.PRNGKey(17), (64, D))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(18), (EXPERTS,))
    idx, gates = layer.route(tokens, params["router"], bias)
    want_idx, want, _ = ref.route(
        {"router": params["router"], "router_bias": bias}, tokens,
        top_k=TOP_K, scaling=1.0)
    assert ref.GATE_EPS == 1e-6
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(gates, want, rtol=2e-6)


# ------------------------------------------------------------- whole model --
def tiny_config(layer_types=None, dense=None):
    cfg = harness.load_json(os.path.join(
        ROOT, "tests", "bench_harness", "configs", "lfm2-tiny.json"))
    if layer_types is not None:
        cfg = dict(cfg, layer_types=list(layer_types),
                   num_hidden_layers=len(layer_types))
    if dense is not None:
        cfg = dict(cfg, num_dense_layers=dense)
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
    """The rehearsal's tiny configuration (float32): conv, attention, conv;
    one dense layer; experts 4-7 of 16 held; a tied table of 512 rows."""
    cfg = tiny_config()
    return (cfg,) + built(cfg)


@pytest.mark.parametrize("layer_types,dense", [
    (("conv", "full_attention", "conv"), 1),
    (("conv", "full_attention", "conv"), 2),
    (("full_attention", "conv", "conv", "conv"), 1),
    (("full_attention", "conv", "conv", "conv"), 2),
])
def test_model_matches_the_reference_through_fits_own_step(layer_types,
                                                           dense):
    cfg = tiny_config(layer_types, dense)
    model, x, y = built(cfg, t=40)
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    assert "head_w" not in p_ref and len(p_ref["blocks"]) == len(layer_types)
    assert sum("mlp" in b for b in p_ref["blocks"]) == dense
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
    assert len(forced) == len(layer_types) - dense
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
    assert checks["reference_logit_mean_square"] > 0.0


@pytest.mark.parametrize("variant,group", [
    ("int8", None), ("no_lookback", "short_conv"), ("untied_head", "table")])
def test_the_comparison_tells_a_wrong_model_from_the_program(tiny, variant,
                                                             group):
    """The cell's three controls at the tiny size: the reference computed
    wrongly on purpose, held to its own choices and handed to the driver's
    comparison in the program's place, fails a limit; taps that do not look
    back and a head that is not tied fail where they are wrong."""
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


def test_fit_counts_the_expert_layers_and_learns(tiny):
    cfg, model, x, y = tiny
    hist = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=1,
                     shuffle=False, verbose=0, seed=0)
    more = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=5,
                     shuffle=False, verbose=0, seed=0)
    assert more.history["loss"][-1] < hist.history["loss"][0]
    counted = model.last_fit_telemetry["moe"]
    assert sorted(counted) == ["residual_3/main/moe", "residual_5/main/moe"]
    for c in counted.values():
        assert c["steps"] == 6.0 and c["pairs"] == 6 * 48 * 3
        assert 0 < c["held_rows"] < c["pairs"]
    assert "select" not in model.last_fit_telemetry
    # the selection bias moved, by the rate, and carries no gradient
    bias = model.state["residual_3"]["main"]["moe"]["router_bias"]
    assert float(jnp.max(jnp.abs(bias))) == pytest.approx(0.006, rel=1e-4)


def test_the_operation_count_follows_the_shapes():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "lfm2-8b-a1b.json"))
    assert flops_lfm2_moe.conv_params(cfg) == 16_777_216
    assert flops_lfm2_moe.attention_params(cfg) == 10_485_760
    assert flops_lfm2_moe.expert_params(cfg) == 11_010_048
    per_token = fam.train_flops_per_token(cfg, 8192)
    assert 8192 * per_token == pytest.approx(10.63e12, rel=0.005)
    # forward a token, by layer, as ISSUE 34 counts it
    fwd = per_token / 3.0
    assert fwd == pytest.approx(
        121.6e6 + 76.7e6 + 3 * 55.7e6 + 67.1e6, rel=0.002)
    assert fam.train_flops_per_token(cfg, 4096) == pytest.approx(
        per_token - 3 * 2.0 * 4096 * 2048, rel=1e-9)
    # the parameters the share holds: 508M, of which one table
    module = fam.build_module(cfg)
    shapes, _, _ = jax.eval_shape(
        lambda k: module.init(k, (128,)), jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == pytest.approx(508e6, rel=0.005)
    assert shapes["embedding"]["table"].shape == (16384, 2048)
    assert "dense" not in shapes
