"""``ops/head_gate.py`` in the interpreter against the lines it stands in
for, ``nn/attention.py``'s ``round(ctx * sigmoid(z)[..., None])`` on the (B,
T, H, 128) view: values and gradients (in ``ctx``, in ``z`` and through
``z = x Wg`` in ``Wg`` and ``x``); a gated layer on the kernels against its
dense path; which path a layer takes and what the counters say; and the
ungated layers of the Keye and LFM2 cells, whose gradient jaxprs are the
parent's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu import nn
from distributed_tpu.ops import head_gate as hg

COUNTERS = ("attn.gate_fused", "attn.gate_xla")
D = 64
# Laguna's full layers: YaRN over half of a head's 128 dimensions.
YARN = {"rope_type": "yarn", "factor": 64.0,
        "original_max_position_embeddings": 4096, "beta_fast": 64.0,
        "beta_slow": 1.0, "attention_factor": 1.4158883083359672}


def plain(ctx, z):
    """The layer's lines, on the (B, T, H, 128) view."""
    b, t, width = ctx.shape
    g = jax.nn.sigmoid(z.astype(jnp.float32))
    c = ctx.reshape(b, t, width // 128, 128)
    return (c.astype(jnp.float32) * g[..., None]).astype(ctx.dtype).reshape(
        b, t, width)


def operands(b, t, heads, dtype, seed=0):
    kc, kz, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    ctx = jax.random.normal(kc, (b, t, heads * 128)).astype(dtype)
    z = (2.0 * jax.random.normal(kz, (b, t, heads))).astype(dtype)
    return ctx, z, jax.random.normal(kg, ctx.shape)


def ulp(a):
    """The spacing of bfloat16 at each value of ``a``."""
    return 2.0 ** (np.floor(np.log2(np.maximum(np.abs(a), 1e-30))) - 7)


def assert_equal_or_a_unit_apart(got, want):
    """Exact, or within one unit of bfloat16 on every entry and equal on at
    least 99.99% of them (float32's order of summing may tip a rounding)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.mean(got == want) >= 0.9999
    assert np.all(np.abs(got - want) <= ulp(want))


# (B, T, heads): the cell's 64 and 48 heads, 8 and 4; sequences shorter than
# a row block (512 rows, 256 in float32), T a multiple of one, and T not one
# (the last block part empty); batch 1 and 2.
SHAPES = [
    (1, 64, 64), (2, 600, 64), (1, 1024, 48), (2, 48, 48),
    (2, 40, 8), (1, 530, 8), (2, 50, 4), (1, 24, 4),
    # two blocks of 6 heads; 17 blocks of 8 over gates two lane tiles wide
    (2, 40, 12), (1, 24, 136),
]


@pytest.mark.parametrize("t,heads,dtype,grid", [
    (8192, 64, jnp.bfloat16, (1, 16, 8)),   # the cell's sliding layers
    (8192, 48, jnp.bfloat16, (1, 16, 6)),   # and its full ones
    (8192, 64, jnp.float32, (1, 32, 8)),
    (8192, 6, jnp.bfloat16, (1, 16, 1)),
    (40, 4, jnp.bfloat16, (1, 1, 1)),
])
def test_the_grid_walks_row_blocks_and_blocks_of_heads(t, heads, dtype, grid):
    """Both kernels walk ``head_norm_rope.blocks``' blocks: 512 rows (256 in
    float32) of up to 8 heads, so a body is unrolled over a block's heads
    and not over the layer's."""
    from qk_prep import kernel_grids

    ctx = jax.ShapeDtypeStruct((1, t, heads * 128), dtype)
    z = jax.ShapeDtypeStruct((1, t, heads), dtype)
    jaxpr = jax.make_jaxpr(lambda c, z, g: jax.vjp(hg.head_gate, c, z)[1](g))(
        ctx, z, ctx)
    assert kernel_grids(jaxpr) == [("dtpu_head_gate", grid),
                                   ("dtpu_head_gate_bwd", grid)]


def test_a_gate_of_another_shape_is_refused():
    ctx, z, _ = operands(1, 16, 4, jnp.float32)
    with pytest.raises(ValueError, match="gate"):
        hg.head_gate(ctx, z[..., :3])
    with pytest.raises(ValueError, match="gate"):
        hg.head_gate(ctx[..., :100], z)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,heads", SHAPES)
def test_values_are_the_plain_lines(b, t, heads, dtype):
    ctx, z, _ = operands(b, t, heads, jnp.dtype(dtype))
    got = jax.jit(hg.head_gate)(ctx, z)
    assert_equal_or_a_unit_apart(got, jax.jit(plain)(ctx, z))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,heads", SHAPES)
def test_gradients_are_autodiff_of_the_plain_lines(b, t, heads, dtype):
    """d ctx and d z, and through ``z = x Wg`` d Wg (a float32 leaf cast to
    the compute dtype, as the layer casts it) and d x, under ``jit``, in the
    dtypes autodiff of the plain lines gives."""
    dt = jnp.dtype(dtype)
    ctx, _, w = operands(b, t, heads, dt, seed=1)
    kx, kw = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (b, t, D)).astype(dt)
    wg = 0.3 * jax.random.normal(kw, (D, heads))

    def loss(gate):
        return lambda ctx, x, wg: jnp.sum(
            w * gate(ctx, jnp.dot(x, wg.astype(x.dtype))).astype(jnp.float32))

    got = jax.jit(jax.grad(loss(hg.head_gate), (0, 1, 2)))(ctx, x, wg)
    want = jax.jit(jax.grad(loss(plain), (0, 1, 2)))(ctx, x, wg)
    for a, e in zip(got, want):
        assert_equal_or_a_unit_apart(a, e)
    # and d z itself, the kernel's own output
    z = jnp.dot(x, wg.astype(dt))
    got = jax.vjp(hg.head_gate, ctx, z)[1](w.astype(dt))
    want = jax.vjp(plain, ctx, z)[1](w.astype(dt))
    for a, e in zip(got, want):
        assert_equal_or_a_unit_apart(a, e)


def test_each_pass_is_traced_once_a_shape(monkeypatch, request):
    """A model's layers call the passes at one shape each: the kernels'
    bodies are traced once a pass, however many layers call them."""
    from qk_prep import kernel_calls

    traced = []
    # Another test may have traced this shape already; and the counting
    # bodies must not stay in the caches after this one.
    jitted = (hg._forward, hg._backward)
    for f in jitted:
        f.clear_cache()
    request.addfinalizer(lambda: [f.clear_cache() for f in jitted])

    def counting(name, body):
        def kernel(*refs, **static):
            traced.append(name)
            return body(*refs, **static)
        return kernel

    monkeypatch.setattr(hg, "_fwd_kernel", counting("fwd", hg._fwd_kernel))
    monkeypatch.setattr(hg, "_bwd_kernel", counting("bwd", hg._bwd_kernel))
    ctx, z, w = operands(1, 40, 4, jnp.bfloat16, seed=3)
    loss = lambda c, z: sum(
        jnp.sum(w * hg.head_gate(c * s, z).astype(jnp.float32))
        for s in (1.0, 2.0, 3.0))
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(ctx, z)
    assert kernel_calls(jaxpr) == ["dtpu_head_gate"] * 3 + [
        "dtpu_head_gate_bwd"] * 3
    assert sorted(traced) == ["bwd", "fwd"]


# -------------------------------------------------------- attention layer --
def gated_layer(heads, sliding, **kw):
    if sliding:
        layer = nn.GroupedQueryAttention(heads, 2, 128, rope_theta=10000.0,
                                         window=64, gate=True, **kw)
    else:
        layer = nn.GroupedQueryAttention(
            heads, 2, 128, rope_theta=500000.0, rotary_dim=64,
            rope_scaling=YARN, gate=True, **kw)
    layer.name = layer.default_name()
    return layer


@pytest.mark.parametrize("heads,sliding", [(8, True), (6, False)])
def test_a_gated_layer_on_the_kernels_matches_its_dense_path(heads, sliding):
    """128-wide heads on the flash kernels (the interpreter), norm, rotation
    and gate in their kernels, against the same layer's dense path, which
    runs the plain lines throughout: the output and its gradient in every
    leaf, ``wg`` among them, and in the input, at batch 2."""
    t = 192
    layer, dense = (gated_layer(heads, sliding, flash=f) for f in (True,
                                                                    False))
    params, state, _ = layer.init(jax.random.PRNGKey(1), (t, D))
    params["wg"] = 4.0 * params["wg"]  # gates well away from one half
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    loss = lambda lay: lambda p, x: jnp.sum(
        w * lay.apply(p, state, x, train=True)[0])
    got = jax.value_and_grad(loss(layer), (0, 1))(params, x)
    want = jax.value_and_grad(loss(dense), (0, 1))(params, x)
    for (path, a), e in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(e))) + 1e-12
        assert float(jnp.max(jnp.abs(a - e))) < 2e-4 * scale + 1e-7, (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("sliding", [True, False])
def test_the_gate_kernels_in_a_bfloat16_layer_are_the_plain_gate(
        sliding, monkeypatch):
    """The same bfloat16 layer on the same flash kernels, its gate once in
    ``dtpu_head_gate`` and once in the plain lines (put in the kernels'
    place): the output and every gradient are the same, bit for bit."""
    t = 128
    layer = gated_layer(8, sliding, flash=True, dtype="bfloat16")
    params, state, _ = layer.init(jax.random.PRNGKey(4), (t, D))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, t, D))
    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def run():
        loss = lambda p, x: jnp.sum(w * layer.apply(
            p, state, x, train=True)[0].astype(jnp.float32))
        out = layer.apply(params, state, x, train=True)[0]
        return out, jax.grad(loss, (0, 1))(params, x)

    got = run()
    monkeypatch.setattr(hg, "head_gate", lambda c, z: plain(c, z))
    want = run()
    for (path, a), e in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == e.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(e, np.float32),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("sliding", [True, False])
def test_a_128_wide_gated_layer_gates_in_the_kernels(sliding):
    """On the flash path at 128-wide heads the gradient's jaxpr calls
    ``dtpu_head_gate`` and its backward once each, and nothing under
    ``gate`` makes a float32 (B, T, H, 128) view (the plain lines do: XLA:TPU
    relayouts it, PERF.md section 6); the dense path and narrower
    heads keep the plain lines, and a layer without a gate counts neither
    path. The trace-time counters say which."""
    from qk_prep import float32_head_views, gradient_jaxpr, kernel_calls

    def traced(layer):
        jaxpr, counted = gradient_jaxpr(layer, 256, D, 2, COUNTERS)
        gate = [c for c in kernel_calls(jaxpr) if "head_gate" in c]
        return jaxpr, gate, counted

    mk = lambda **kw: gated_layer(8, sliding, dtype="bfloat16", **kw)
    jaxpr, gate, counted = traced(mk(flash=True))
    assert gate == ["dtpu_head_gate", "dtpu_head_gate_bwd"]
    assert float32_head_views(jaxpr, scopes=("gate",)) == []
    assert counted == (1, 0)
    jaxpr, gate, counted = traced(mk(flash=False))
    assert gate == [] and counted == (0, 1)
    assert len(float32_head_views(jaxpr, scopes=("gate",))) >= 1
    narrow = nn.GroupedQueryAttention(8, 2, 64, window=64, gate=True,
                                      dtype="bfloat16", flash=True)
    narrow.name = narrow.default_name()
    assert traced(narrow)[1:] == ([], (0, 1))
    ungated = nn.GroupedQueryAttention(8, 2, 128, window=64 if sliding
                                       else None, dtype="bfloat16",
                                       flash=True)
    ungated.name = ungated.default_name()
    assert traced(ungated)[1:] == ([], (0, 0))


# The gradient's jaxprs of the two ungated cells' layers, with the flash path
# on, as the tree before the gate's kernels traced them: Keye's 128-wide
# selecting heads and LFM2's 64-wide ones. Re-pinned where the flash
# backward's delta became a contraction on the (b, T, H x D) layout: against
# commit 7c33136's text only delta's equations differ
# (``test_flash_attention.py:PARENT_JAXPRS``).
PARENT_JAX = "0.9.0"
PARENT_JAXPRS = {
    "keye": "cb0fea852f6084ea15aca0951861321c8161f04ba12513e7b5b36a492f0bcfd4",
    "lfm2": "57bdb0dc4bae6eabf6ad4ae58af864bd6b79e5e144674b6e029fc98fc48dab7c",
}


@pytest.mark.parametrize("cell", list(PARENT_JAXPRS))
def test_ungated_cells_layers_are_the_parents(cell):
    from qk_prep import digest, gradient_jaxpr, kernel_calls

    if cell == "keye":
        layer, t = nn.GroupedQueryAttention(
            8, 2, 128, rope_theta=1e7, epsilon=1e-6, index_topk=16,
            index_heads=4, index_dim=64, dtype="bfloat16", flash=True), 64
    else:
        layer, t = nn.GroupedQueryAttention(
            32, 8, 64, rope_theta=1e6, epsilon=1e-5, dtype="bfloat16",
            flash=True), 256
    layer.name = layer.default_name()
    jaxpr, counted = gradient_jaxpr(layer, t, D, counters=COUNTERS)
    assert counted == (0, 0)
    assert [c for c in kernel_calls(jaxpr) if "head_gate" in c] == []
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the parent's digests were taken under JAX {PARENT_JAX}")
    assert digest(jaxpr) == PARENT_JAXPRS[cell]
