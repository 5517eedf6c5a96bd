"""Keye-VL-2.0's language block (grouped-query attention over a learned
top-k selection of keys, softmax-routed dropless experts) against its plain
reference ``benchmarks/reference/keye_vl2.py``, at a small size on the CPU
with seeded weights: the row-wise selection, the flash kernels with a
selection operand and K/V heads shared by a group of query heads, the
attention layer with the selection off and on, the indexer's loss and where
its gradient goes, the shares of an expert-parallel layer, and the whole
model (logits, loss, every gradient leaf) through ``Model.fit``'s own step."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distributed_tpu as dtpu
from distributed_tpu import nn
from distributed_tpu.ops import flash_attention as fa
from distributed_tpu.ops.topk_select import topk_mask

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import flops_keye_vl2, harness  # noqa: E402

MANIFEST = {"paths": ["tests/bench_harness", "benchmarks"]}
ref = harness.load_module(MANIFEST, "reference", "keye_vl2")
fam = harness.load_module(MANIFEST, "families", "keye_vl2")

D, HEADS, KV_HEADS, HEAD_DIM = 64, 4, 2, 16
INDEX_HEADS, INDEX_DIM, TOPK = 3, 8, 12
EXPERTS, HIDDEN, TOP_K = 16, 32, 3
EPS, THETA = 1e-6, 10000.0
KW = {"n_head": HEADS, "n_kv": KV_HEADS, "head_dim": HEAD_DIM,
      "theta": THETA, "eps": EPS, "top_k": TOP_K, "expert_offset": 0,
      "index_heads": INDEX_HEADS, "index_dim": INDEX_DIM,
      "index_topk": TOPK, "q_block": 8}


def close(a, b, rel=1e-4):
    scale = float(jnp.max(jnp.abs(b))) + 1e-12
    return float(jnp.max(jnp.abs(a - b))) < rel * scale + 1e-7


def assert_trees_close(got, want, rel=1e-4):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert close(a, b, rel), jax.tree_util.keystr(path)


# -------------------------------------------------------------- selection --
def test_half_split_rope_turns_the_two_halves():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, HEAD_DIM))
    got = nn.attention.rope_half(x, THETA)
    want = jnp.stack([ref.rope(x[b], THETA) for b in range(2)])
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)  # position 0


@pytest.mark.parametrize("t,k", [(40, 7), (33, 1), (24, 64)])
def test_topk_mask_keeps_each_rows_k_largest_valid_scores(t, k):
    """Against the reference's selection: rows with fewer than k valid
    entries keep them all (every row where k passes the row's length),
    negative and tied scores keep their order, and a tie at the k-th value
    keeps every tied entry."""
    scores = jax.random.normal(jax.random.PRNGKey(1), (t, t))
    scores = scores.at[20, :15].set(0.25)       # a tie across the threshold
    scores = scores.at[21].set(-jnp.abs(scores[21]))  # all negative
    rows = jnp.arange(t)
    causal = rows[:, None] >= rows[None, :]
    got = topk_mask(scores, k, causal)
    np.testing.assert_array_equal(got, ref.own_selection(scores, rows, k))
    counts = np.asarray(got).sum(-1)
    assert list(counts[:k]) == list(range(1, min(k, t) + 1))
    assert (counts[k:] >= k).all() and counts[20] >= min(15, k)
    assert not np.asarray(got)[~np.asarray(causal)].any()
    # no mask handed in: over every entry
    np.testing.assert_array_equal(
        np.asarray(topk_mask(scores, k)).sum(-1) >= min(k, t), True)


# ---------------------------------------------------------- flash kernels --
def random_selection(key, b, t, density=0.3):
    """A causal selection that keeps the diagonal, spread over the keys, with
    one grid block of batch row 0 left empty."""
    causal = jnp.tril(jnp.ones((t, t), bool))
    sel = jax.random.uniform(key, (b, t, t)) < density
    sel = jnp.logical_and(jnp.logical_or(sel, jnp.eye(t, dtype=bool)), causal)
    return sel.at[0, t // 2:, :t // 4].set(False).astype(jnp.int8)


@pytest.mark.parametrize("heads,kv_heads,t,blocks", [
    (4, 2, 256, (128, 64)),     # 4 over 2, several grid blocks each way
    (4, 2, 200, (None, 1024)),  # a ragged sequence in one padded block
    (32, 4, 128, (64, 64)),     # the cell's 32 over 4
    (4, 4, 256, (64, 128)),     # a selection without grouped queries
])
def test_flash_kernels_with_a_selection_match_dense_attention(
        heads, kv_heads, t, blocks):
    """The lane-packed kernels at 128-wide heads with a selection operand
    and K/V heads shared by a group: forward and all three gradients
    against ``dense_attention`` under the same mask."""
    kq, kk, kv, ks, kg = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(kq, (2, t, heads, 128))
    k = jax.random.normal(kk, (2, t, kv_heads, 128))
    v = jax.random.normal(kv, (2, t, kv_heads, 128))
    w = jax.random.normal(kg, (2, t, heads, 128))
    sel = random_selection(ks, 2, t)
    flash = lambda q, k, v: jnp.sum(w * fa.flash_attention(
        q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
        selection=sel))
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(
        q, k, v, True, sel))
    got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert_trees_close(got, want, rel=2e-5)


@pytest.mark.parametrize("heads,kv_heads,d", [(4, 2, 128), (8, 2, 128),
                                              (4, 2, 64), (3, 1, 24)])
def test_flash_kernels_share_a_kv_head_among_a_group(heads, kv_heads, d):
    """Grouped queries with no selection: 128-wide heads read the shared
    head in place (dk/dv summed over the group inside the kernel), any other
    shape repeats K and V; both against the dense path."""
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(kq, (2, 192, heads, d))
    k = jax.random.normal(kk, (2, 192, kv_heads, d))
    v = jax.random.normal(kv, (2, 192, kv_heads, d))
    w = jax.random.normal(kg, q.shape)
    flash = lambda q, k, v: jnp.sum(w * fa.flash_attention(
        q, k, v, causal=True, block_q=64, block_k=64))
    dense = lambda q, k, v: jnp.sum(w * fa.dense_attention(q, k, v, True))
    got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert_trees_close(got, want, rel=2e-5)


def test_selection_blocks_flags_the_blocks_that_hold_a_pair():
    sel = random_selection(jax.random.PRNGKey(4), 2, 256)
    flags, total = fa.selection_blocks(sel, 64, 64)
    assert flags.shape == (2, 4, 4) and total == 10  # at or below the diagonal
    want = np.asarray(sel).reshape(2, 4, 64, 4, 64).any(axis=(2, 4))
    np.testing.assert_array_equal(np.asarray(flags), want)
    assert not flags[0, 2:, 0].any() and flags[1].sum() == 10


def test_a_selection_needs_the_packed_causal_kernels():
    x = jnp.zeros((1, 64, 2, 64))
    with pytest.raises(ValueError, match="128-wide"):
        fa.flash_attention(x, x, x, causal=True,
                           selection=jnp.ones((1, 64, 64), jnp.int8))


# -------------------------------------------------------- attention layer --
@pytest.fixture(autouse=True)
def blocks_of_eight_queries(monkeypatch):
    """Several blocks of queries at these tests' T: the layer scores, selects
    and computes L_I ``INDEX_BLOCK`` (512) queries at a time."""
    monkeypatch.setattr(nn.attention, "INDEX_BLOCK", 8)


def attention_layer(topk=TOPK, flash=False, head_dim=HEAD_DIM, **kw):
    layer = nn.GroupedQueryAttention(
        HEADS, KV_HEADS, head_dim, rope_theta=THETA, epsilon=EPS,
        index_topk=topk, index_heads=INDEX_HEADS, index_dim=INDEX_DIM,
        flash=flash, **kw)
    layer.name = layer.default_name()
    return layer


def reference_block(params):
    ix = params.get("indexer") or {
        "wq": jnp.zeros((D, INDEX_HEADS * INDEX_DIM)),
        "wk": jnp.zeros((D, INDEX_DIM)), "ww": jnp.zeros((D, INDEX_HEADS)),
        "k_norm": {"scale": jnp.ones((INDEX_DIM,)),
                   "bias": jnp.zeros((INDEX_DIM,))}}
    return {"wq": params["wq"], "wk": params["wk"], "wv": params["wv"],
            "wo": params["wo"], "q_norm": params["q_norm"]["scale"],
            "k_norm": params["k_norm"]["scale"],
            "indexer": {"wq": ix["wq"], "wk": ix["wk"], "ww": ix["ww"],
                        "k_norm_scale": ix["k_norm"]["scale"],
                        "k_norm_bias": ix["k_norm"]["bias"]}}


def test_a_selecting_128_wide_layer_norms_and_rotates_in_the_kernels():
    """On the flash path at 128-wide heads the selecting layer's gradient
    calls ``dtpu_head_norm_rope`` for q and for k and its backward for each
    (the indexer keeps ``rope_half`` on its own heads), and nothing under
    the layer's ``q_norm`` or ``k_norm`` makes a float32 (B, T, H, 128)
    view; the dense path and the tiny model's heads keep the plain lines."""
    from qk_prep import float32_head_views, gradient_jaxpr, kernel_calls

    mk = lambda **kw: attention_layer(dtype="bfloat16", **kw)
    jaxpr, counted = gradient_jaxpr(mk(flash=True, head_dim=128), 64, D, 2)
    prep = [c for c in kernel_calls(jaxpr) if "head_norm" in c]
    assert prep == ["dtpu_head_norm_rope"] * 2 + [
        "dtpu_head_norm_rope_bwd"] * 2
    assert float32_head_views(jaxpr) == []
    assert counted == (2, 0)
    for plain in (mk(head_dim=128), mk(flash=True), mk()):
        jaxpr, counted = gradient_jaxpr(plain, 64, D, 2)
        assert [c for c in kernel_calls(jaxpr) if "head_norm" in c] == []
        assert counted == (0, 2)


def test_grouped_query_attention_without_an_indexer_matches_the_reference():
    """The selection off: no indexer, no state, every key before a query
    seen: the reference told to ignore its own selection."""
    t = 40
    layer = attention_layer(topk=None)
    params, state, _ = layer.init(jax.random.PRNGKey(5), (t, D))
    assert state == {} and "indexer" not in params
    x = jax.random.normal(jax.random.PRNGKey(6), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    kw = dict(KW, head_dim=HEAD_DIM)

    def system(p, x):
        y, new = layer.apply(p, state, x, train=True)
        assert new == {}
        return jnp.sum(y * w)

    def reference(p, x):
        return sum(jnp.sum(w[b] * ref.attention(
            reference_block(p), x[b], kw=kw, variant="no_selection")[0])
            for b in range(2))

    got = jax.value_and_grad(system, (0, 1))(params, x)
    want = jax.value_and_grad(reference, (0, 1))(params, x)
    assert_trees_close(got, want)


@pytest.mark.parametrize("flash,head_dim", [(False, HEAD_DIM), (True, 128)])
def test_selecting_attention_matches_the_reference(flash, head_dim):
    """The selection on and biting (T = 40 over a top-12): the layer's
    output and L_I, and the gradient of their sum in every leaf and in the
    input, dense path and flash kernels (the interpreter, 128-wide heads)."""
    t = 40
    layer = attention_layer(flash=flash, head_dim=head_dim,
                            record_selection=True)
    params, state, _ = layer.init(jax.random.PRNGKey(8), (t, D))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(10), x.shape)
    kw = dict(KW, head_dim=head_dim)

    def system(p, x):
        y, new = layer.apply(p, state, x, train=True)
        return jnp.sum(y * w) + new["aux_loss"], new

    def reference(p, x):
        outs = [ref.attention(reference_block(p), x[b], kw=kw)
                for b in range(2)]
        return (sum(jnp.sum(w[b] * o[0]) for b, o in enumerate(outs))
                + sum(o[1] for o in outs) / 2, outs)

    (got, new), got_grads = jax.value_and_grad(system, (0, 1), has_aux=True)(
        params, x)
    (want, outs), want_grads = jax.value_and_grad(
        reference, (0, 1), has_aux=True)(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert_trees_close(got_grads, want_grads)
    assert float(new["aux_loss"]) == pytest.approx(
        float(sum(o[1] for o in outs) / 2), rel=1e-4)
    # the selection bites, and the one recorded is the first example's
    own = np.asarray(outs[0][2])
    # (three index heads: a score is exactly 0 where every head's ReLU is,
    # and the zeros that tie at a row's threshold are all kept)
    assert flops_keye_vl2.selected_pairs(t, TOPK) <= own.sum() < (
        t * (t + 1) / 2)
    np.testing.assert_array_equal(
        np.asarray(ref.unpack(new["selection"], t)), own)
    assert float(new["selected_pairs"]) == own.sum() + np.asarray(
        outs[1][2]).sum()
    assert float(new["causal_pairs"]) == 2 * t * (t + 1) // 2
    assert float(new["queries"]) == 2 * t and float(new["steps"]) == 1.0
    if flash:  # one padded grid block an example, and it holds pairs
        assert float(new["blocks_total"]) == float(new["blocks_computed"]) == 2
    else:
        assert float(new["blocks_total"]) == float(new["blocks_computed"]) == 0
    # evaluation selects the same keys, reports no loss, keeps no state
    y_eval, kept = layer.apply(params, state, x, train=False)
    assert kept == {} and close(y_eval, layer.apply(
        params, state, x, train=True)[0])


def test_the_index_loss_moves_the_indexer_and_nothing_else():
    """L_I's gradient is zero in every leaf outside ``indexer`` and in the
    layer's input, and non-zero in every leaf inside; the layer's output has
    no gradient in the indexer (the selection is a constant mask)."""
    t = 40
    layer = attention_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(11), (t, D))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, t, D))
    l_i = lambda p, x: layer.apply(p, state, x, train=True)[1]["aux_loss"]
    g_params, g_x = jax.grad(l_i, (0, 1))(params, x)
    assert float(l_i(params, x)) > 0
    assert float(jnp.max(jnp.abs(g_x))) == 0.0
    for path, g in jax.tree_util.tree_flatten_with_path(g_params)[0]:
        inside = path[0].key == "indexer"
        assert (float(jnp.max(jnp.abs(g))) > 0) == inside, path
    out = lambda p: jnp.sum(layer.apply(p, state, x, train=True)[0] ** 2)
    for g in jax.tree_util.tree_leaves(jax.grad(out)(params)["indexer"]):
        assert float(jnp.max(jnp.abs(g))) == 0.0


def test_heads_that_do_not_divide_are_refused():
    with pytest.raises(ValueError, match="multiple"):
        nn.GroupedQueryAttention(6, 4, 16)


# ---------------------------------------------------------- expert layer --
def expert_layer(held=None, offset=0):
    return nn.DroplessMoE(EXPERTS, HIDDEN, top_k=TOP_K, experts_held=held,
                          expert_offset=offset, scoring="softmax")


def reference_experts(params, x, offset):
    b = {"router": params["router"],
         "experts": {"gate": params["w_gate"], "up": params["w_up"],
                     "down": params["w_down"]}}
    y, own = ref.experts(b, x.reshape(-1, x.shape[-1]),
                         kw=dict(KW, expert_offset=offset))
    return y.reshape(x.shape), own


def test_the_eight_shares_make_the_uncut_layer():
    """What the guide's section 4 asks of a share: the routed parts of all
    eight chips add up to the uncut reference's layer; there is no shared
    expert to count once."""
    layer = expert_layer()
    params, state, _ = layer.init(jax.random.PRNGKey(13), (24, D))
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 24, D))
    total = jnp.zeros_like(x)
    for chip in range(8):
        p = dict(params, **{k: params[k][2 * chip:2 * chip + 2]
                            for k in ("w_gate", "w_up", "w_down")})
        y, new = expert_layer(2, 2 * chip).apply(p, state, x, train=True)
        total = total + y
        # softmax scoring has no selection bias: the buffer does not move
        assert not np.asarray(new["router_bias"]).any()
    assert "shared" not in params
    assert close(total, reference_experts(params, x, 0)[0])


# ------------------------------------------------------------- whole model --
@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's tiny configuration (float32): 2 layers, 4 query heads
    over 2 K/V heads, a top-16 selection, experts 4-7 of 16 held, a
    vocabulary of 500 in 512 rows; sequences of 48, so the selection bites."""
    cfg = harness.load_json(os.path.join(
        ROOT, "tests", "bench_harness", "configs", "keye-tiny.json"))
    model = dtpu.Model(fam.build_module(cfg))
    model.compile(optimizer=dtpu.optim.Adam(1e-3, b1=0.9),
                  loss="sparse_categorical_crossentropy", metrics=())
    model.build((48,), seed=5)
    tok = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (1, 49)).astype(np.int32)
    return cfg, model, tok[:, :-1], tok[:, 1:]


def test_model_matches_the_reference_through_fits_own_step(tiny):
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    logits, _ = model.module.apply(model.params, model.state, jnp.asarray(x),
                                   train=True, rng=None)
    assert close(logits[0], ref.forward(p_ref, x[0], kw=kw))
    # the forward and backward half of the train step, as fit jits it: the
    # cross-entropy plus both layers' L_I
    loss, state, grads, _ = jax.jit(model._grad_eval_body())(
        model.params, model.state, jnp.asarray(x), jnp.asarray(y), None)
    (ref_loss, (keys, chosen, aux)), ref_grads = jax.value_and_grad(
        lambda p: ref.sequence_loss(
            fam.reference_params(p, model.state, cfg), x[0], y[0], kw=kw),
        has_aux=True)(model.params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert 0.1 < float(aux) < float(ref_loss)
    assert_trees_close(grads, ref_grads)
    # what the step recorded is what the reference would choose itself
    forced = fam.choices(state, cfg)
    for packed, own in zip(forced["keys"], keys):
        np.testing.assert_array_equal(np.asarray(ref.unpack(packed, 48)), own)
    for choice, own in zip(forced["experts"], chosen):
        np.testing.assert_array_equal(np.sort(choice, -1), np.sort(own, -1))
    # and the driver's comparison of it finds no flip and no difference
    compared = jax.device_get(ref.compare(
        p_ref, x, y, kw=kw, system_grads=fam.reference_params(
            grads, model.state, cfg), forced=forced))
    checks = fam.first_step_checks(float(loss), float(compared["grad_norm"]),
                                   compared, 48 * cfg["num_experts_per_tok"])
    assert all(checks[k] for k in fam.FIRST_STEP_CHECKS)
    assert checks["flipped_keys_share"] == [0.0, 0.0]
    assert checks["missed_keys_share"] == [0.0, 0.0]
    assert checks["reference_index_loss"] == pytest.approx(float(aux))
    assert max(checks["grad_differences"].values()) < 1e-4


@pytest.mark.parametrize("variant", ["int8", "no_selection"])
def test_the_comparison_tells_a_wrong_model_from_the_program(tiny, variant):
    """The cell's two controls at the tiny size: the reference computed
    wrongly on purpose and handed to the driver's comparison in the
    program's place fails a limit."""
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    wrong_loss, wrong, _ = ref.loss_and_grads(p_ref, jnp.asarray(x),
                                              jnp.asarray(y), kw=kw,
                                              variant=variant)
    compared = jax.device_get(ref.compare(
        p_ref, x, y, kw=kw, system_grads=wrong, forced=fam.choices(
            jax.jit(model._grad_eval_body())(
                model.params, model.state, jnp.asarray(x), jnp.asarray(y),
                None)[1], cfg)))
    norm = np.sqrt(sum(float(jnp.sum(g * g))
                       for g in jax.tree_util.tree_leaves(wrong)))
    checks = fam.first_step_checks(float(wrong_loss), norm, compared,
                                   48 * cfg["num_experts_per_tok"])
    assert not all(checks[k] for k in fam.FIRST_STEP_CHECKS)


def test_a_selection_that_keeps_too_few_keys_fails_on_the_keys_missed(tiny):
    """The cell's third control: a model that selects half the keys, handed
    to the comparison with its own choices, as a program's are. The
    reference held to those keys agrees with it in loss and gradients and
    finds no key it would not select; the keys left out alone tell."""
    cfg, model, x, y = tiny
    kw = fam.reference_kwargs(cfg)
    p_ref = fam.reference_params(model.params, model.state, cfg)
    wrong_loss, wrong, (keys, chosen, _) = ref.loss_and_grads(
        p_ref, jnp.asarray(x), jnp.asarray(y), kw=kw,
        variant="half_selection")
    compared = jax.device_get(ref.compare(
        p_ref, x, y, kw=kw, system_grads=wrong, forced={
            "experts": chosen,
            "keys": [jnp.packbits(k, axis=-1) for k in keys]}))
    checks = fam.first_step_checks(
        float(wrong_loss), float(compared["grad_norm"]), compared,
        48 * cfg["num_experts_per_tok"])
    assert [k for k in fam.FIRST_STEP_CHECKS if not checks[k]] == [
        "selection_agrees"]
    assert checks["flipped_keys_share"] == [0.0, 0.0]
    assert max(checks["grad_differences"].values()) < 1e-4
    # 8 of up to 16 keys a query: 48 queries select 8 * 9 / 2 + 40 * 8 pairs
    # where the reference selects 16 * 17 / 2 + 32 * 16, and a tie more
    assert checks["missed_keys_share"] == pytest.approx([0.45, 0.45], abs=0.05)


def test_fit_counts_the_selection_and_learns(tiny):
    cfg, model, x, y = tiny
    hist = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=1,
                     shuffle=False, verbose=0, seed=0)
    more = model.fit(x, y, batch_size=1, epochs=1, steps_per_epoch=5,
                     shuffle=False, verbose=0, seed=0)
    assert more.history["loss"][-1] < hist.history["loss"][0]
    counted = model.last_fit_telemetry["select"]
    assert sorted(counted) == ["residual/main/multi_head_attention_gqa",
                               "residual_2/main/multi_head_attention_gqa"]
    for c in counted.values():
        assert c["steps"] == 6.0 and c["queries"] == 6 * 48
        assert c["causal_pairs"] == 6 * 48 * 49 / 2
        assert c["selected_pairs"] >= 6 * flops_keye_vl2.selected_pairs(48, 16)
        assert c["selected_pairs"] < c["causal_pairs"]
    assert sorted(model.last_fit_telemetry["moe"]) == [
        "residual_1/main/moe", "residual_3/main/moe"]


def test_the_operation_count_follows_the_selected_pairs():
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "keye-vl-2.0-30b-a3b.json"))
    assert flops_keye_vl2.selected_pairs(8192, 2048) == 14_681_088
    assert flops_keye_vl2.causal_pairs(8192) == 33_558_528
    assert flops_keye_vl2.selected_pairs(1024, 2048) == (
        flops_keye_vl2.causal_pairs(1024))
    assert flops_keye_vl2.attention_params(cfg) == 18_874_368
    assert flops_keye_vl2.indexer_params(cfg) == 2_260_992
    per_token = fam.train_flops_per_token(cfg, 8192)
    assert 8192 * per_token == pytest.approx(11.10e12, rel=0.005)
    # with the selection idle the attention's count is the causal half
    short = fam.train_flops_per_token(cfg, 1024)
    assert short < per_token
    ops, nbytes = flops_keye_vl2.dsa_flash_cost("dkv", 1, 8192, 32, 4, 128,
                                                2048)
    assert ops == 2.0 * 14_681_088 * 32 * 128 * 4
    assert nbytes == 8192 * 128 * 2 * (2 * 32 + 4 * 4)
