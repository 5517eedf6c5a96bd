"""Device scopes of the train step (docs/OBSERVABILITY.md, "Device scopes").

Every operation of a compiled train step carries, in its ``op_name``
metadata, the name of the layer and the phase it belongs to: a layer's scope
path is its parameter path (``nn.core.child_scope``), the phases outside the
layers are ``cast``, ``loss``, ``metrics`` and ``optimizer``
(``training/model.py``), and forward and backward are JAX's own ``jvp(...)``
and ``transpose(jvp(...))`` wrappers. The names are an interface (XProf, and
``benchmarks/scopes.py`` reads them from a device trace), so they are pinned
here as strings, from the tiny LM's step lowered and compiled on the CPU.
"""

import re

import numpy as np
import pytest

import jax
import distributed_tpu as dtpu

CASES = {
    "plain": ({}, {}),
    "chunked_head": ({}, {"head_chunks": 2}),
    "remat": ({"remat": True}, {}),
    "scan": ({"scan": True}, {}),
}
# What JAX puts between a transform wrapper and the program's scopes: loop
# and checkpoint bodies. Matched loosely: these are JAX's names, not ours.
JAX = r"(?:[\w()]+/)*?"


@pytest.fixture(scope="module")
def compiled():
    cache = {}

    def get(case):
        if case not in cache:
            lm_kw, compile_kw = CASES[case]
            m = dtpu.Model(dtpu.models.transformer_lm(
                64, num_layers=2, d_model=16, num_heads=2, max_len=16,
                **lm_kw))
            m.compile(optimizer=dtpu.optim.Adam(1e-3),
                      loss="sparse_categorical_crossentropy",
                      metrics=["accuracy"], precision="mixed_bfloat16",
                      **compile_kw)
            m.build((16,), seed=0)
            x = np.zeros((4, 16), np.int32)
            text = m.lower_train_step(x, x).compile().as_text()
            cache[case] = (m, sorted(set(
                re.findall(r'op_name="(jit\(step\)[^"]*)"', text))))
        return cache[case]

    return get


def has(names, pattern):
    rx = re.compile(pattern)
    return any(rx.match(n) for n in names)


def layer_paths(params):
    """The container path of every parameter leaf: ``residual_1/main/dense``."""
    return sorted({
        tuple(k.key for k in path[:-1])
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]})


@pytest.mark.parametrize("case", sorted(CASES))
def test_phases_carry_their_scopes(compiled, case):
    _, names = compiled(case)
    assert has(names, r"jit\(step\)/jvp\(cast\)/convert_element_type$")
    assert has(names, r"jit\(step\)/transpose\(jvp\(cast\)\)/")
    assert has(names, r"jit\(step\)/optimizer/")
    if case == "chunked_head":
        # head, loss and metrics run per chunk, in a checkpointed scan body
        assert has(names, rf"jit\(step\)/jvp\(\)/while/body/{JAX}loss/")
        assert has(names, rf"jit\(step\)/transpose\(jvp\(\)\)/while/body/"
                          rf"{JAX}loss/")
        assert has(names, rf"jit\(step\)/jvp\(\)/while/body/{JAX}metrics/")
    else:
        assert has(names, r"jit\(step\)/jvp\(loss\)/")
        assert has(names, r"jit\(step\)/transpose\(jvp\(loss\)\)/")
        assert has(names, r"jit\(step\)/metrics/")
    # nothing of the optimizer or the metrics is differentiated
    assert not has(names, r"jit\(step\)/(transpose\()?jvp\((optimizer|"
                          r"metrics)\)")


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_layers_scope_path_is_its_parameter_path(compiled, case):
    model, names = compiled(case)
    paths = layer_paths(model.params)
    assert ("residual_1", "main", "dense_1") in paths or case == "scan"
    for path in paths:
        top, rest = path[0], "/".join(path[1:])
        if case == "scan" and top == "scanned_blocks":
            # .../jvp(scanned_blocks)/while/body/.../blocks/residual/main/..
            fwd = (rf"jit\(step\)/jvp\({top}\)/while/body/{JAX}{rest}/")
            bwd = (rf"jit\(step\)/transpose\(jvp\({top}\)\)/while/body/"
                   rf"{JAX}{rest}/")
        elif case == "chunked_head" and path == ("dense",):
            fwd = rf"jit\(step\)/jvp\(\)/while/body/{JAX}dense/dot_general$"
            bwd = rf"jit\(step\)/transpose\(jvp\(\)\)/while/body/{JAX}dense/"
        else:
            tail = f"/{rest}/" if rest else "/"
            fwd = rf"jit\(step\)/jvp\({top}\){tail}"
            # under Remat the backward pass re-enters the name stack:
            # transpose(jvp(a))/jvp(a)/checkpoint/main/dense_1/...
            bwd = rf"jit\(step\)/transpose\(jvp\({top}\)\)/{JAX}{tail[1:]}"
        assert has(names, fwd), (path, fwd)
        assert has(names, bwd), (path, bwd)


def test_every_operation_of_the_plain_step_is_under_a_scope(compiled):
    model, names = compiled("plain")
    tops = {p[0] for p in layer_paths(model.params)} | {
        "cast", "loss", "metrics", "optimizer"}
    for name in names:
        if name == "jit(step)":
            continue
        m = re.match(r"jit\(step\)/(?:transpose\()?(?:jvp\()?(\w*)", name)
        assert m and m.group(1) in tops, name


# ------------------------------------------------- the DeepSeek-V3 block --
@pytest.fixture(scope="module")
def deepseek():
    m = dtpu.Model(dtpu.models.deepseek_v3_lm(
        64, num_layers=2, d_model=16, num_heads=2, kv_rank=8, nope_dim=8,
        rope_dim=4, v_dim=8, d_ff=24, num_experts=8, experts_held=4,
        expert_offset=2, top_k=2, moe_hidden=8, shared_experts=2))
    m.compile(optimizer=dtpu.optim.Adam(1e-3),
              loss="sparse_categorical_crossentropy", metrics=())
    m.build((16,), seed=0)
    x = np.zeros((4, 16), np.int32)
    text = m.lower_train_step(x, x).compile().as_text()
    return (m, sorted(set(re.findall(r'op_name="(jit\(step\)[^"]*)"', text))),
            text)


def test_deepseek_layers_scope_paths_are_their_parameter_paths(deepseek):
    model, names, _ = deepseek
    paths = layer_paths(model.params)
    for want in (("residual", "main", "multi_head_attention_latent"),
                 ("residual", "main", "multi_head_attention_latent",
                  "kv_norm"),
                 ("residual_1", "main", "gated_mlp", "dense_2"),
                 ("residual_3", "main", "moe"),
                 ("residual_3", "main", "moe", "shared", "dense_1")):
        assert want in paths
    for path in paths:
        top, rest = path[0], "/".join(path[1:])
        tail = f"/{rest}/" if rest else "/"
        assert has(names, rf"jit\(step\)/jvp\({top}\){tail}"), path
        assert has(names, rf"jit\(step\)/transpose\(jvp\({top}\)\)/{JAX}"
                          rf"{tail[1:]}"), path


@pytest.mark.parametrize("inner,primitive", [
    ("route", "top_k"), ("route", "gather"), ("route", "scatter"),
    ("route", "dtpu_moe_rows_gather"), ("route", "dtpu_moe_rows_sum"),
    ("experts", "dtpu_gmm"), ("shared/dense", "dot_general"),
])
def test_the_expert_layer_names_its_routing_its_experts_and_its_shared(
        deepseek, inner, primitive):
    """``moe*/route`` holds the router, the top-k, the sort and the gathers
    both ways; ``moe*/experts`` the grouped matmuls; ``moe*/shared`` the
    shared gated MLP: ``benchmarks/scopes_moe.py`` splits the expert layers'
    device time by them. The two row walks (``ops/moe_rows.py``) run under
    ``route`` in both passes: each is the other's transpose."""
    _, names, _ = deepseek
    assert has(names, rf"jit\(step\)/jvp\(residual_3\)/main/moe/{inner}/"
                      rf"{JAX}\w*{primitive}")
    if primitive not in ("top_k", "scatter"):  # indices have no gradient
        assert has(names, rf"jit\(step\)/transpose\(jvp\(residual_3\)\)/"
                          rf"{JAX}main/moe/{inner}/")
    if primitive.startswith("dtpu_moe_rows"):
        assert has(names, rf"jit\(step\)/transpose\(jvp\(residual_3\)\)/"
                          rf"{JAX}main/moe/route/{JAX}{primitive}")


@pytest.mark.parametrize("wrapper,kernel", [
    (r"jvp\(residual_3\)", "dtpu_gmm"),
    (r"transpose\(jvp\(residual_3\)\)", "dtpu_gmm_nt"),
    (r"transpose\(jvp\(residual_3\)\)", "dtpu_gmm_tn"),
])
def test_an_expert_layer_calls_each_grouped_matmul_kernel_three_times(
        deepseek, wrapper, kernel):
    """Nine ``dtpu_gmm*`` calls a layer-step under ``moe*/experts``, each
    one product (the activation, its backward and the sum of d buf's terms
    are epilogues): three forward, three a kind backward.
    ``benchmarks/layer_metrics/moe_experts_roofline.py`` prices a traced
    call at a ninth of a layer's work. An interpreted call is one loop over
    its grid."""
    _, _, text = deepseek
    call = re.compile(rf' while\(.*op_name="jit\(step\)/{wrapper}/{JAX}main/'
                      rf'moe/experts/{JAX}{kernel}/while"')
    assert sum(bool(call.search(line)) for line in text.splitlines()) == 3


def test_every_operation_of_the_deepseek_step_is_under_a_scope(deepseek):
    model, names, _ = deepseek
    tops = {p[0] for p in layer_paths(model.params)} | {
        "cast", "loss", "metrics", "optimizer"}
    for name in names:
        if name == "jit(step)":
            continue
        m = re.match(r"jit\(step\)/(?:transpose\()?(?:jvp\()?(\w*)", name)
        assert m and m.group(1) in tops, name


# ------------------------- grouped queries over a learned selection of keys --
@pytest.fixture(scope="module")
def selecting():
    m = dtpu.Model(dtpu.models.qwen3_moe_lm(
        64, num_layers=2, d_model=16, num_heads=4, num_kv_heads=2, head_dim=8,
        num_experts=8, experts_held=4, expert_offset=2, top_k=2, moe_hidden=8,
        index_topk=4, index_heads=2, index_dim=4,
        record_choice=True))
    m.compile(optimizer=dtpu.optim.Adam(1e-3),
              loss="sparse_categorical_crossentropy", metrics=())
    m.build((16,), seed=0)
    x = np.zeros((4, 16), np.int32)
    text = m.lower_train_step(x, x).compile().as_text()
    return m, sorted(set(re.findall(r'op_name="(jit\(step\)[^"]*)"', text)))


def test_selecting_layers_scope_paths_are_their_parameter_paths(selecting):
    model, names = selecting
    paths = layer_paths(model.params)
    gqa = ("residual_2", "main", "multi_head_attention_gqa")
    for want in (gqa, gqa + ("q_norm",), gqa + ("k_norm",),
                 gqa + ("indexer",), gqa + ("indexer", "k_norm"),
                 ("residual_3", "main", "moe")):
        assert want in paths
    for path in paths:
        top, rest = path[0], "/".join(path[1:])
        tail = f"/{rest}/" if rest else "/"
        assert has(names, rf"jit\(step\)/jvp\({top}\){tail}"), path
        assert has(names, rf"jit\(step\)/transpose\(jvp\({top}\)\)/{JAX}"
                          rf"{tail[1:]}"), path


@pytest.mark.parametrize("inner,primitive", [
    ("indexer", "dot_general"), ("indexer/k_norm", "rsqrt"),
    ("indexer/{JAX}select", "ge"), ("indexer/{JAX}select", "and"),
])
def test_the_selecting_layer_names_its_indexer_and_its_selection(
        selecting, inner, primitive):
    """``multi_head_attention_gqa/indexer`` holds the indexer's projections,
    the index scores, L_I and its gradient, and inside it ``select`` the
    row-wise top-k (the bisection's loop): ``benchmarks/scopes_dsa.py``
    splits the attention layers' device time by them. The component starts
    with ``multi_head_attention``, so ``attn_device_ms`` holds all of it.
    L_I's gradient is taken where the scores are, in the forward pass, and
    the selection has none: neither has a ``transpose(`` of its own."""
    _, names = selecting
    inner = inner.format(JAX=JAX)
    assert has(names, rf"jit\(step\)/jvp\(residual_2\)/main/"
                      rf"multi_head_attention_gqa/{inner}/{JAX}\w*{primitive}")
    assert not has(names, rf"jit\(step\)/transpose\(jvp\(residual_2\)\)/"
                          rf"{JAX}(?:jvp\()?select\)?/")


def test_every_operation_of_the_selecting_step_is_under_a_scope(selecting):
    model, names = selecting
    tops = {p[0] for p in layer_paths(model.params)} | {
        "cast", "loss", "metrics", "optimizer"}
    for name in names:
        if name == "jit(step)":
            continue
        m = re.match(r"jit\(step\)/(?:transpose\()?(?:jvp\()?(\w*)", name)
        assert m and m.group(1) in tops, name


def test_the_index_scores_kernel_runs_under_the_indexers_scope():
    """Where the selecting layer runs its flash kernels (128-wide heads, an
    indexer of 64), the index scores' gradient is ``dtpu_index_scores_bwd``
    (``ops/index_scores.py``): its operations carry
    ``multi_head_attention_gqa/indexer`` as the plain form's did, so
    ``dsa_index_device_ms`` reads the kernel with no scope renamed, and none
    lies under ``select``."""
    m = dtpu.Model(dtpu.models.qwen3_moe_lm(
        64, num_layers=1, d_model=16, num_heads=2, num_kv_heads=1,
        head_dim=128, num_experts=4, experts_held=2, expert_offset=0,
        top_k=2, moe_hidden=8, index_topk=32, index_heads=2, index_dim=64,
        flash=True))
    m.compile(optimizer=dtpu.optim.Adam(1e-3),
              loss="sparse_categorical_crossentropy", metrics=())
    m.build((128,), seed=0)
    x = np.zeros((1, 128), np.int32)
    text = m.lower_train_step(x, x).compile().as_text()
    names = [n for n in set(re.findall(r'op_name="(jit\(step\)[^"]*)"', text))
             if "dtpu_index_scores_bwd" in n]
    assert names
    for name in names:
        assert re.match(r"jit\(step\)/jvp\(residual\)/main/"
                        r"multi_head_attention_gqa/indexer/", name), name
        assert "select/" not in name.split("dtpu_index_scores_bwd")[0], name


# ------------------- short convolutions, attention and a tied head together --
@pytest.fixture(scope="module")
def hybrid():
    m = dtpu.Model(dtpu.models.lfm2_moe_lm(
        64, layer_types=("conv", "full_attention", "conv"),
        num_dense_layers=1, d_model=16, num_heads=4, num_kv_heads=2,
        head_dim=4, d_ff=24, num_experts=8, experts_held=4, expert_offset=2,
        top_k=2, moe_hidden=8, record_choice=True))
    m.compile(optimizer=dtpu.optim.Adam(1e-3),
              loss="sparse_categorical_crossentropy", metrics=())
    m.build((16,), seed=0)
    x = np.zeros((4, 16), np.int32)
    text = m.lower_train_step(x, x).compile().as_text()
    return m, sorted(set(re.findall(r'op_name="(jit\(step\)[^"]*)"', text)))


def test_hybrid_layers_scope_paths_are_their_parameter_paths(hybrid):
    model, names = hybrid
    paths = layer_paths(model.params)
    for want in (("residual", "main", "short_conv"),
                 ("residual_1", "main", "gated_mlp", "dense_2"),
                 ("residual_2", "main", "multi_head_attention_gqa", "q_norm"),
                 ("residual_3", "main", "moe"),
                 ("residual_4", "main", "short_conv"), ("embedding",)):
        assert want in paths
    assert ("dense",) not in paths  # the tied head owns no leaf
    for path in paths:
        top, rest = path[0], "/".join(path[1:])
        tail = f"/{rest}/" if rest else "/"
        assert has(names, rf"jit\(step\)/jvp\({top}\){tail}"), path
        assert has(names, rf"jit\(step\)/transpose\(jvp\({top}\)\)/{JAX}"
                          rf"{tail[1:]}"), path


@pytest.mark.parametrize("scope,primitive", [
    ("short_conv", "dot_general"), ("short_conv/mix", "mul"),
    ("short_conv/mix", "pad"),
])
def test_the_short_conv_names_its_products_and_its_mix(hybrid, scope,
                                                       primitive):
    """``short_conv`` holds the layer's two products and, inside it, ``mix``
    the gates and the taps: ``benchmarks/scopes_conv.py`` reads
    ``shortconv_device_ms`` and ``shortconv_mix_device_ms`` from them, both
    passes (the backward of ``mix`` is a checkpoint's: JAX enters the name
    stack again under it)."""
    _, names = hybrid
    for block in ("residual", "residual_4"):
        assert has(names, rf"jit\(step\)/jvp\({block}\)/main/{scope}/"
                          rf"{JAX}\w*{primitive}")
        assert has(names, rf"jit\(step\)/transpose\(jvp\({block}\)\)/"
                          rf"{JAX}main/{scope}/")
    # no product of the layer under ``mix``
    assert not has(names, r".*/short_conv/mix/(?:[\w()]+/)*dot_general")


def test_the_tied_heads_product_runs_under_the_heads_scope(hybrid):
    """The logits' product is ``dense`` at the top level, forward and
    backward, where an untied head's is (``benchmarks/scopes.py`` files a
    top-level ``dense*`` under ``head_loss``); the table's other use, the
    gather, stays under ``embedding``."""
    _, names = hybrid
    assert has(names, r"jit\(step\)/jvp\(dense\)/dot_general")
    assert has(names, r"jit\(step\)/transpose\(jvp\(dense\)\)/"
                      r"dot_general")
    assert has(names, rf"jit\(step\)/jvp\(embedding\)/{JAX}gather")
    assert not has(names, r"jit\(step\)/jvp\(embedding\)/dot_general")


def test_every_operation_of_the_hybrid_step_is_under_a_scope(hybrid):
    model, names = hybrid
    tops = {p[0] for p in layer_paths(model.params)} | {
        "cast", "loss", "metrics", "optimizer", "dense"}
    for name in names:
        if name == "jit(step)":
            continue
        m = re.match(r"jit\(step\)/(?:transpose\()?(?:jvp\()?(\w*)", name)
        assert m and m.group(1) in tops, name

