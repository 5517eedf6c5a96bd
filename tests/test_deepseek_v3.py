"""The DeepSeek-V3 block (kanana-2-30b-a3b's) against its plain reference
``benchmarks/reference/deepseek_v3.py``, at a small size on the CPU with
seeded weights: the attention layer, the expert layer and the whole model
(logits, loss, every gradient leaf) through ``Model.fit``'s own step; the
shares of an expert-parallel layer adding up to the uncut layer; no pair
dropped under any load; the counters."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import nn
from distributed_tpu.ops import flash_attention as fa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

MANIFEST = {"paths": ["tests/bench_harness", "benchmarks"]}
ref = harness.load_module(MANIFEST, "reference", "deepseek_v3")
fam = harness.load_module(MANIFEST, "families", "deepseek_v3")
keye_ref = harness.load_module(MANIFEST, "reference", "keye_vl2")

D, HEADS, KV_RANK, NOPE, ROPE, V = 64, 2, 32, 16, 8, 16
EXPERTS, HIDDEN, TOP_K, SCALING = 16, 32, 3, 2.448
EPS, THETA = 1e-6, 10000.0


def close(a, b, rel=1e-4):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a - b))) < rel * scale + 1e-7


def assert_trees_close(got, want, rel=1e-4):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert close(a, b, rel), jax.tree_util.keystr(path)


# -------------------------------------------------------------- attention --
def test_interleaved_rope_turns_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, ROPE))
    got = nn.attention.rope_interleaved(x, THETA)
    want = jnp.stack([ref.rope(x[b], THETA) for b in range(2)])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)  # position 0


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernels_take_values_narrower_than_keys(causal):
    """The folded kernels at latent attention's shape: q and k one width, v
    another; forward and all three gradients against the dense path."""
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(kq, (2, 80, 2, 24))
    k = jax.random.normal(kk, (2, 80, 2, 24))
    v = jax.random.normal(kv, (2, 80, 2, 16))
    w = jax.random.normal(kw, (2, 80, 2, 16))
    out = fa.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, fa.dense_attention(q, k, v, causal),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(fa.flash_attention(
        *a, causal=causal, block_q=32, block_k=32) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(
        fa.dense_attention(*a, causal) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def attention_params(p):
    return {"wq": p["wq"], "wkv_a": p["wkv_a"],
            "kv_norm": p["kv_norm"]["scale"], "wkv_b": p["wkv_b"],
            "wo": p["wo"]}


@pytest.mark.parametrize("flash", [False, True])
def test_latent_attention_matches_the_reference(flash):
    layer = nn.LatentAttention(
        HEADS, kv_rank=KV_RANK, nope_dim=NOPE, rope_dim=ROPE, v_dim=V,
        rope_theta=THETA, epsilon=EPS, flash=flash)
    params, _, _ = layer.init(jax.random.PRNGKey(2), (40, D))
    params["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), (KV_RANK,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 40, D))
    w = jax.random.normal(jax.random.PRNGKey(5), (2, 40, D))
    kw = dict(n_head=HEADS, nope=NOPE, rope_dim=ROPE, v_dim=V,
              kv_rank=KV_RANK, theta=THETA, eps=EPS)

    def system(p, x):
        return layer.apply(p, {}, x)[0]

    def plain(p, x):
        return jnp.stack([ref.attention(attention_params(p), x[b], **kw)
                          for b in range(x.shape[0])])

    assert close(system(params, x), plain(params, x))
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * w), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(plain(p, x) * w), (0, 1))(params, x)
    assert_trees_close(got, want)


# ---------------------------------------------------------- expert layer --
def expert_layer(held=None, offset=0, shared=2, scoring="sigmoid"):
    return nn.DroplessMoE(
        EXPERTS, HIDDEN, top_k=TOP_K, experts_held=held, expert_offset=offset,
        shared_hidden_dim=shared * HIDDEN, scoring=scoring,
        routed_scaling=SCALING if scoring == "sigmoid" else 1.0)


def reference_block(params, state, shared=True):
    gated = lambda p: {"gate": p["dense"]["kernel"],
                       "up": p["dense_1"]["kernel"],
                       "down": p["dense_2"]["kernel"]}
    b = {"router": params["router"], "router_bias": state["router_bias"],
         "experts": {"gate": params["w_gate"], "up": params["w_up"],
                     "down": params["w_down"]}}
    if shared and "shared" in params:
        b["shared"] = gated(params["shared"])
    return b


def reference_layer(params, state, x, offset, shared=True, scoring="sigmoid"):
    flat = x.reshape(-1, x.shape[-1])
    block = reference_block(params, state, shared)
    if scoring == "softmax":  # Qwen3-MoE's router: reference/keye_vl2.py's
        y, _ = keye_ref.experts(block, flat, kw={
            "top_k": TOP_K, "expert_offset": offset})
    else:
        y, _ = ref.experts(block, flat, top_k=TOP_K, scaling=SCALING,
                           expert_offset=offset)
    return y.reshape(x.shape)


@pytest.fixture(scope="module")
def whole_layer():
    """The uncut layer (all 16 experts), a selection bias that is not zero,
    and an input."""
    layer = expert_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(6), (24, D))
    state = dict(state, router_bias=0.2 * jax.random.normal(
        jax.random.PRNGKey(7), (EXPERTS,)))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, D))
    return layer, params, state, x


def share_of(params, lo, n):
    return dict(params, **{k: params[k][lo:lo + n]
                           for k in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("held,offset", [(16, 0), (4, 4), (2, 14)])
def test_expert_layer_matches_the_reference_on_its_share(whole_layer, held,
                                                         offset, scoring):
    """``softmax``: scores over all experts, no selection bias (the state's
    is not zero here, and is ignored), no shared expert, no scaling: the
    router of ``reference/keye_vl2.py``."""
    _, params, state, x = whole_layer
    softmax = scoring == "softmax"
    layer = expert_layer(held, offset, shared=0 if softmax else 2,
                         scoring=scoring)
    p = share_of(params, offset, held)
    if softmax:
        p = {k: v for k, v in p.items() if k != "shared"}
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def system(p, x):
        return layer.apply(p, state, x, train=True)[0]

    def reference(p, x):
        return reference_layer(p, state, x, offset, scoring=scoring)

    assert close(system(p, x), reference(p, x))
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * w), (0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(reference(p, x) * w), (0, 1))(p, x)
    assert_trees_close(got, want)


def test_the_eight_shares_and_the_shared_expert_once_make_the_uncut_layer(
        whole_layer):
    """What the guide's section 4 asks of a share: the routed parts of all
    eight chips, plus what every chip computes alike (the shared expert)
    counted once, are the uncut reference's layer."""
    _, params, state, x = whole_layer
    total = jnp.zeros_like(x)
    for chip in range(8):
        layer = expert_layer(2, 2 * chip, shared=0)
        p = {k: v for k, v in share_of(params, 2 * chip, 2).items()
             if k != "shared"}
        total = total + layer.apply(p, state, x, train=True)[0]
    shared = nn.GatedMLP(2 * HIDDEN).apply(params["shared"], {}, x)[0]
    assert close(total + shared, reference_layer(params, state, x, 0))


def test_no_pair_is_dropped_when_one_expert_takes_every_token(whole_layer):
    """A selection bias that sends every token to expert 5 first: its group
    is the whole batch, far over any capacity, and the result is still the
    reference's; the counters say so."""
    _, params, state, x = whole_layer
    state = dict(state, router_bias=state["router_bias"].at[5].set(100.0))
    layer = expert_layer(4, 4)
    p = share_of(params, 4, 4)
    y, new = layer.apply(p, state, x, train=True)
    assert close(y, reference_layer(p, state, x, 4))
    n = x.shape[0] * x.shape[1]
    assert float(new["pairs"]) == n * TOP_K
    assert float(new["load_max_sum"]) == n  # expert 5 holds every token
    assert n <= float(new["held_rows"]) <= n * TOP_K
    assert float(new["steps"]) == 1.0
    assert "choice" not in new  # an output only for who asks: record_choice
    # evaluation counts nothing and leaves the state alone
    assert layer.apply(p, state, x, train=False)[1] == {}


@pytest.mark.parametrize("held,offset", [(16, 0), (4, 4), (2, 14)])
def test_a_train_step_counts_the_tiles_it_went_over(whole_layer, held,
                                                    offset):
    """``tiles_used``: each held expert's pairs rounded up to tiles of 128
    rows, an expert nobody chose still one; ``buffer_tiles``: the tiles of
    the buffer's static worst case. The walks and the grouped matmuls go
    over the first and never over the rest."""
    from distributed_tpu.ops import grouped_matmul as gmm

    _, params, state, x = whole_layer
    layer = expert_layer(held, offset)
    p = share_of(params, offset, held)
    sizes = loads_of(layer, p, state, x)[offset:offset + held]
    new = layer.apply(p, state, x, train=True)[1]
    assert float(new["tiles_used"]) == sum(
        max(-(-int(s) // gmm.TILE_M), 1) for s in sizes)
    n = x.shape[0] * x.shape[1]
    assert float(new["buffer_tiles"]) == gmm.buffer_rows(
        n * TOP_K, held) // gmm.TILE_M
    again = layer.apply(p, new, x, train=True)[1]  # cumulative
    assert float(again["tiles_used"]) == 2 * float(new["tiles_used"])
    assert float(again["buffer_tiles"]) == 2 * float(new["buffer_tiles"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_tiny_presets_expert_layer_matches_the_reference(dtype):
    """The expert layer at the rehearsal's tiny configuration (experts 4-7
    of 16 held, top-3, two shared), output and every gradient, against
    ``reference/deepseek_v3.py``'s: in float32 to the equations, in bfloat16
    to its rounding."""
    cfg = harness.load_json(os.path.join(
        ROOT, "tests", "bench_harness", "configs", "kanana-tiny.json"))
    d, offset = cfg["hidden_size"], cfg["deployment"]["expert_offset"]
    layer = nn.DroplessMoE(
        fam.router_experts(cfg), cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"], expert_offset=offset,
        shared_hidden_dim=cfg["n_shared_experts"] * cfg[
            "moe_intermediate_size"],
        routed_scaling=cfg["routed_scaling_factor"], dtype=dtype)
    params, state, _ = layer.init(jax.random.PRNGKey(11), (48, d))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 48, d))
    w = jax.random.normal(jax.random.PRNGKey(13), x.shape)

    def system(p, x):
        return layer.apply(p, state, x, train=True)[0]

    def plain(p, x):
        y, _ = ref.experts(
            reference_block(p, state), x.reshape(-1, d),
            top_k=cfg["num_experts_per_tok"],
            scaling=cfg["routed_scaling_factor"], expert_offset=offset)
        return y.reshape(x.shape)

    rel = 1e-4 if dtype == "float32" else 3e-2
    assert close(system(params, x), plain(params, x), rel)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * w), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(plain(p, x) * w), (0, 1))(params, x)
    assert_trees_close(got, want, rel)


def loads_of(layer, params, state, x):
    idx, _ = layer.route(x.reshape(-1, D), params["router"],
                         state["router_bias"])
    return np.bincount(np.asarray(idx).reshape(-1), minlength=EXPERTS)


@pytest.mark.parametrize("rate", [0.0, 1e-3, 5e-2])
def test_a_train_step_moves_the_selection_bias_against_the_load(whole_layer,
                                                                rate):
    """noaux_tc: an expert over the batch's mean load goes ``rate`` down, one
    under it as much up, whatever the size of the excess; 0 freezes it."""
    _, params, state, x = whole_layer
    layer = nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K,
                           shared_hidden_dim=2 * HIDDEN,
                           routed_scaling=SCALING, bias_update_rate=rate)
    loads = loads_of(layer, params, state, x)
    mean = x.shape[0] * x.shape[1] * TOP_K / EXPERTS
    assert loads.max() > mean > loads.min()
    new = layer.apply(params, state, x, train=True)[1]
    np.testing.assert_allclose(
        new["router_bias"] - state["router_bias"],
        rate * np.sign(mean - loads), atol=1e-7)
    assert layer.apply(params, state, x, train=False)[1] == {}


def test_the_bias_update_evens_the_loads(whole_layer):
    """Held on one batch, the update walks a skewed selection to an even one:
    the busiest expert comes down to the mean load and stays there."""
    _, params, state, x = whole_layer
    layer = nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K,
                           routed_scaling=SCALING, bias_update_rate=5e-3)
    params = {k: v for k, v in params.items() if k != "shared"}
    mean = x.shape[0] * x.shape[1] * TOP_K / EXPERTS
    before = loads_of(layer, params, state, x).max()
    step = jax.jit(lambda s: layer.apply(params, s, x, train=True)[1])
    for _ in range(200):
        state = step(state)
    after = loads_of(layer, params, state, x).max()
    assert before > 1.5 * mean and after <= 1.25 * mean
    assert float(state["steps"]) == 200.0


def test_record_choice_keeps_the_first_examples_experts(whole_layer):
    _, params, state, x = whole_layer
    layer = nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K,
                           shared_hidden_dim=2 * HIDDEN,
                           routed_scaling=SCALING, record_choice=True)
    fresh = layer.init(jax.random.PRNGKey(6), x.shape[1:])[1]
    assert fresh["choice"].shape == (x.shape[1], TOP_K)
    new = layer.apply(params, dict(fresh, router_bias=state["router_bias"]),
                      x, train=True)[1]
    idx, _ = layer.route(x[0], params["router"], state["router_bias"])
    np.testing.assert_array_equal(new["choice"], idx)


def test_gates_are_the_unbiased_scores_normalised_and_scaled(whole_layer):
    layer, params, state, x = whole_layer
    flat = x.reshape(-1, D)
    idx, gates = layer.route(flat, params["router"], state["router_bias"])
    scores = jax.nn.sigmoid(flat @ params["router"])
    want_idx = jnp.argsort(-(scores + state["router_bias"]), axis=-1)[
        :, :TOP_K]
    np.testing.assert_array_equal(jnp.sort(idx), jnp.sort(want_idx))
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), SCALING, rtol=1e-5)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        gates, SCALING * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="not among"):
        nn.DroplessMoE(16, 8, top_k=2, experts_held=4, expert_offset=14)
    with pytest.raises(ValueError, match="top_k"):
        nn.DroplessMoE(4, 8, top_k=5)


# ------------------------------------------------------------ small layers --
def test_rms_norm_and_gated_mlp():
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 5, D))
    norm = nn.RMSNorm(EPS)
    p, _, _ = norm.init(jax.random.PRNGKey(0), (5, D))
    p = {"scale": p["scale"] * 1.5}
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * 1.5
    np.testing.assert_allclose(norm.apply(p, {}, x)[0], want, rtol=1e-5)
    mlp = nn.GatedMLP(HIDDEN)
    p, _, shape = mlp.init(jax.random.PRNGKey(1), (5, D))
    assert shape == (5, D) and sorted(p) == ["dense", "dense_1", "dense_2"]
    want = (jax.nn.silu(x @ p["dense"]["kernel"]) * (
        x @ p["dense_1"]["kernel"])) @ p["dense_2"]["kernel"]
    np.testing.assert_allclose(mlp.apply(p, {}, x)[0], want, atol=1e-5)
    seq = nn.Sequential([nn.RMSNorm(), nn.GatedMLP(8), nn.RMSNorm()])
    assert [l.name for l in seq.layers] == [
        "rms_norm", "gated_mlp", "rms_norm_1"]


# ------------------------------------------------------------- whole model --
@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's tiny configuration in float32 (the tolerances below
    pin the equations, not bfloat16 rounding): 3 layers, experts 4-7 of 16
    held, a vocabulary of 500 in 512 rows."""
    cfg = dict(harness.load_json(os.path.join(
        ROOT, "tests", "bench_harness", "configs", "kanana-tiny.json")),
        compute_dtype="float32")
    model = dtpu.Model(fam.build_module(cfg))
    model.compile(optimizer=dtpu.optim.Adam(1e-3, b1=0.9),
                  loss="sparse_categorical_crossentropy", metrics=())
    model.build((48,), seed=5)
    tok = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, 49)).astype(np.int32)
    return cfg, model, tok[:, :-1], tok[:, 1:]


def test_model_matches_the_reference_through_fits_own_step(tiny):
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    logits, _ = model.module.apply(model.params, model.state, jnp.asarray(x),
                                   train=True, rng=None)
    want = jnp.stack([ref.forward(p_ref, x[b], kw=kw) for b in range(2)])
    assert close(logits, want)
    # the forward and backward half of the train step, as fit jits it
    loss, _, grads, _ = jax.jit(model._grad_eval_body())(
        model.params, model.state, jnp.asarray(x), jnp.asarray(y), None)
    ref_loss, ref_grads = jax.value_and_grad(lambda p: ref.batch_loss(
        fam.reference_params(p, model.state, cfg), x, y, kw=kw))(model.params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_trees_close(grads, ref_grads)
    ref_loss2, gnorm = ref.loss_and_grad_norm(p_ref, x, y, kw=kw)
    want_norm = np.sqrt(sum(float(jnp.sum(g * g))
                            for g in jax.tree_util.tree_leaves(ref_grads)))
    assert float(gnorm) == pytest.approx(want_norm, rel=1e-4)
    assert float(ref_loss2) == pytest.approx(float(ref_loss), rel=1e-5)


def test_fit_steps_count_and_learn(tiny):
    """One ``fit``: Adam's first moment after a step is (1 - b1) x the
    reference's gradient (what the benchmark's driver reads), the expert
    layers' counters arrive in the fit's telemetry, and the loss falls."""
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    _, gnorm = ref.loss_and_grad_norm(
        fam.reference_params(model.params, model.state, cfg), x, y, kw=kw)
    hist = model.fit(x, y, batch_size=2, epochs=1, steps_per_epoch=1,
                     shuffle=False, verbose=0, seed=0)
    from benchmarks.drivers.train import find_field

    mu = find_field(model.opt_state, "mu")
    mu_norm = np.sqrt(sum(float(jnp.sum(m * m))
                          for m in jax.tree_util.tree_leaves(mu)))
    assert mu_norm / 0.1 == pytest.approx(float(gnorm), rel=1e-4)
    more = model.fit(x, y, batch_size=2, epochs=1, steps_per_epoch=5,
                     shuffle=False, verbose=0, seed=0)
    assert more.history["loss"][-1] < hist.history["loss"][0]
    counted = model.last_fit_telemetry["moe"]
    assert sorted(counted) == ["residual_3/main/moe", "residual_5/main/moe"]
    for c in counted.values():
        assert c["steps"] == 6.0 and c["pairs"] == 6 * 2 * 48 * 3
        assert 0 < c["held_rows"] < c["pairs"]
        assert c["load_max_sum"] >= c["pairs"] / 16
        # 2 x 48 x 3 pairs fit one tile a held expert: 4 of the buffer's 7
        assert c["tiles_used"] == 6 * 4 and c["buffer_tiles"] == 6 * 7
    reg = dtpu.obs.default_registry()
    assert reg.gauge_value("moe.pairs") == sum(
        c["pairs"] for c in counted.values())
    assert reg.gauge_value("moe.buffer_used_pct") == pytest.approx(
        100.0 * 4 / 7)


def test_no_row_movement_of_the_step_gathers_the_whole_buffer(tiny):
    """A count on XLA:CPU, never a speed: under ``moe*/route`` the lowered
    train step has no gather whose result is (pairs, d) or (buffer rows, d),
    the worst-case movements ``ops/moe_rows.py`` replaced (the interpreted
    kernels' own gathers are a tile's)."""
    import re

    from distributed_tpu.ops import grouped_matmul as gmm

    cfg, model, x, y = tiny
    d, k = cfg["hidden_size"], cfg["num_experts_per_tok"]
    pairs = x.size * k
    whole = {(pairs, d), (gmm.buffer_rows(pairs, cfg["n_routed_experts"]), d)}
    gather = re.compile(r"= \w+\[([\d,]*)\][^ ]* gather\(.*op_name=\"([^\"]*)\"")

    def results(text, under):
        # less the unit dimensions XLA leaves in a gather's result
        return [tuple(int(s) for s in m.group(1).split(",") if s not in "1")
                for m in map(gather.search, text.splitlines())
                if m and under in m.group(2)]

    # the expression finds such a gather where there is one
    take = jax.jit(lambda b, i: jnp.take(b, i, axis=0)).lower(
        jnp.zeros((max(whole)[0], d)), jnp.zeros((pairs,), jnp.int32))
    assert (pairs, d) in results(take.compile().as_text(), "")
    found = results(model.lower_train_step(x, y).compile().as_text(),
                    "/moe/route/")
    assert found  # the router's own small gathers
    assert not whole & set(found)


def test_no_pass_under_experts_goes_over_the_whole_buffer(tiny):
    """A count on XLA:CPU, never a speed: under ``moe*/experts`` the lowered
    train step has no elementwise instruction (the activation, its backward,
    the sum of d buf's two terms: all epilogues of the grouped matmuls
    since PR 35) whose result has the static buffer's row count. What the
    interpreted kernels do to a whole buffer is to slice it and to update
    slices of it."""
    import re

    from distributed_tpu.ops import grouped_matmul as gmm

    cfg, model, x, y = tiny
    pairs = x.size * cfg["num_experts_per_tok"]
    rows = gmm.buffer_rows(pairs, cfg["n_routed_experts"])
    hidden = cfg["moe_intermediate_size"]
    instruction = re.compile(
        r"= \w+\[(\d+),[\d,]*\][^ ]* ([\w-]+)\(.*op_name=\"([^\"]*)\"")
    moves = {"fusion", "dynamic-update-slice", "dynamic-slice", "slice",
             "broadcast", "get-tuple-element", "copy", "bitcast"}

    def passes(text, under):
        return {m.group(2) for m in map(instruction.search, text.splitlines())
                if m and int(m.group(1)) == rows and under in m.group(3)
                } - moves

    # the expression finds the replaced passes where there are some
    def parents(g, u, d1, d2):
        with jax.named_scope("moe"), jax.named_scope("experts"):
            return jax.nn.silu(g) * u, d1 + d2

    wide = jnp.ones((rows, hidden))
    assert {"multiply", "add"} <= passes(
        jax.jit(parents).lower(wide, wide, wide, wide).compile().as_text(),
        "moe/experts/")
    text = model.lower_train_step(x, y).compile().as_text()
    assert "/moe/experts/" in text
    assert not passes(text, "/moe/experts/")


def test_the_operation_count_knows_the_models_parameters(tiny):
    """``flops_deepseek_v3`` counts the matmul weights the program holds:
    all parameters but the norms' scales and the embedding, the routed
    experts at their mean share."""
    from benchmarks import flops_deepseek_v3 as flops

    cfg, model, _, _ = tiny
    scales = sum(int(np.prod(a.shape)) for path, a in
                 jax.tree_util.tree_flatten_with_path(model.params)[0]
                 if path[-1].key == "scale")
    rows, d = fam.vocab_rows(cfg), cfg["hidden_size"]
    matmul_weights = model.num_params - scales - rows * d
    held, routed = cfg["n_routed_experts"], fam.router_experts(cfg)
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    all_experts = moe_layers * held * flops.expert_params(cfg)
    share = cfg["num_experts_per_tok"] / routed
    want = 2.0 * (matmul_weights - all_experts + all_experts * share)
    t = 48
    attention = cfg["num_hidden_layers"] * t * cfg[
        "num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg[
            "qk_rope_head_dim"] + cfg["v_head_dim"])
    assert flops.forward_flops_per_token(cfg, rows, t, routed) == (
        pytest.approx(want + attention))
