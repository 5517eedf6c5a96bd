"""The index scores' hand-written gradient (``ops/index_scores.py``: one
Pallas kernel that recomputes the products a key tile at a time) against
autodiff of the plain ``index_scores``, in the interpreter: the three
gradients over shapes, blocks (first, middle, last), dtypes and a cotangent
that is zero off a random selection; what the kernel walks; and the
attention layer's ``L_I`` with the kernel and without it."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tpu import nn
from distributed_tpu.obs.registry import default_registry
from distributed_tpu.ops import index_scores as ix


def case(n, heads, d, t, row0, dtype):
    """A block of n queries from ``row0`` against t keys, and a cotangent
    that is zero off a random causal selection."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    qi = jax.random.normal(ks[0], (n, heads, d)).astype(dtype)
    ki = jax.random.normal(ks[1], (t, d)).astype(dtype)
    w = jax.random.normal(ks[2], (n, heads), jnp.float32)
    causal = jnp.arange(t)[None, :] <= row0 + jnp.arange(n)[:, None]
    picked = jnp.logical_and(jax.random.uniform(ks[3], (n, t)) < 0.4, causal)
    d_scores = jnp.where(picked, jax.random.normal(ks[4], (n, t)), 0.0)
    return qi, ki, w, d_scores


def grads(scores, qi, ki, w, d_scores):
    return jax.grad(lambda *a: jnp.sum(scores(*a) * d_scores), (0, 1, 2))(
        qi, ki, w)


def worst(got, want):
    """Largest difference of a gradient from ``want``, over want's largest
    entry."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


# (n, heads, d, t): one tile; four tiles a float32 sequence, two a bf16 one;
# the cell's 16 heads; a block of 8 rows.
SHAPES = [(64, 2, 64, 256), (128, 4, 64, 1024), (32, 16, 64, 1024),
          (8, 2, 64, 512)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 1e-2)])
@pytest.mark.parametrize("block", ["first", "middle", "last"])
@pytest.mark.parametrize("n,heads,d,t", SHAPES)
def test_the_kernels_gradients_match_autodiff_of_the_plain_scores(
        n, heads, d, t, block, dtype, tol):
    """d_qi, d_ki and d_w in the inputs' dtypes; key tiles after the block's
    last query are not walked: their rows of d_ki are exactly zero, as
    autodiff's are under a causal cotangent."""
    row0 = {"first": 0, "middle": (t // n // 2) * n, "last": t - n}[block]
    qi, ki, w, d_scores = case(n, heads, d, t, row0, dtype)
    assert ix.kernel_fits(n, heads, d, t, jnp.dtype(dtype).itemsize)
    got = grads(lambda a, b, c: ix.block_index_scores(
        a, b, c, jnp.int32(row0)), qi, ki, w, d_scores)
    want = grads(ix.index_scores, qi, ki, w, d_scores)
    for g, wnt, arg in zip(got, want, (qi, ki, w)):
        assert g.dtype == arg.dtype and g.shape == arg.shape
        assert worst(g, wnt) < tol
    tk = ix.key_tile(t, jnp.dtype(dtype).itemsize)
    after = -(-(row0 + n) // tk) * tk
    assert not np.asarray(got[1], np.float32)[after:].any()
    assert not np.asarray(want[1], np.float32)[row0 + n:].any()


def test_no_key_tile_after_the_blocks_last_query_is_walked():
    """The kernel reads no cotangent there: NaNs past the block's last tile
    reach no gradient, and those rows of d_ki stay zero. The forward holds
    zeros after the block's last query, so nothing flows back from there."""
    n, heads, d, t, row0 = 64, 2, 64, 1024, 192
    qi, ki, w, d_scores = case(n, heads, d, t, row0, jnp.float32)
    tk = ix.key_tile(t, 4)
    poisoned = d_scores.at[:, tk:].set(jnp.nan)
    got = ix.index_scores_bwd(qi, ki, w, poisoned, jnp.int32(row0))
    want = ix.index_scores_bwd(qi, ki, w, d_scores, jnp.int32(row0))
    for g, wnt in zip(got, want):
        np.testing.assert_array_equal(g, wnt)
    assert not np.asarray(got[1])[tk:].any()
    scores = ix.block_index_scores(qi, ki, w, jnp.int32(row0))
    np.testing.assert_array_equal(
        scores[:, :row0 + n], ix.index_scores(qi, ki, w)[:, :row0 + n])
    assert not np.asarray(scores)[:, row0 + n:].any()


@pytest.mark.parametrize("shape,fits", [
    ((512, 16, 64, 8192, 2), True),    # the cell's
    ((512, 16, 64, 8192, 4), True),
    ((512, 16, 128, 8192, 2), False),  # a head fills the lanes alone
    ((512, 15, 64, 8192, 2), False),   # heads in pairs
    ((12, 16, 64, 8192, 2), False),    # rows in sublanes of 8
    ((64, 2, 64, 8192 + 256, 2), False),  # whole key tiles
    ((8, 2, 4, 16, 4), False),         # the dense-path tests' sizes
])
def test_the_kernel_takes_the_shapes_that_tile(shape, fits):
    assert ix.kernel_fits(*shape) is fits


def test_tile_counts_by_shape():
    """T = 8192 in blocks of 512 queries and tiles of 512 keys: 16 x 16
    tiles, of which block b walks b + 1."""
    assert ix.tile_counts(8192, 512, 2) == (256, 136)
    assert ix.tile_counts(8192, 512, 4) == (512, 272)  # tiles of 256 keys
    assert ix.tile_counts(1024, 128, 2) == (16, 12)
    assert ix.tile_counts(256, 256, 2) == (1, 1)


# --------------------------------------------------------------- the layer --
def selecting_layer(flash):
    layer = nn.GroupedQueryAttention(
        2, 1, 128, index_topk=96, index_heads=2, index_dim=64, flash=flash)
    layer.name = layer.default_name()
    return layer


def index_loss_and_gradients(layer, params, state, x):
    def loss(p):
        _, new = layer.apply(p, state, x, train=True)
        return new["aux_loss"]

    value, grad = jax.value_and_grad(loss)(params)
    return value, grad["indexer"]


def test_the_layers_index_loss_is_the_same_with_the_kernel_and_without(
        monkeypatch):
    """``L_I`` and its gradient in the indexer's three matrices (and its key
    norm) on the flash path, where the scores' gradient is the kernel's,
    against the dense path's, where it is autodiff's: four blocks of 128
    queries, two key tiles. The gauges hold what the kernel path walks."""
    monkeypatch.setattr(nn.attention, "INDEX_BLOCK", 128)
    t, d_model = 512, 32
    params, state, _ = selecting_layer(True).init(
        jax.random.PRNGKey(0), (t, d_model))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, d_model))
    registry = default_registry()
    registry.gauge("index.tiles_computed", -1.0)
    want = index_loss_and_gradients(selecting_layer(False), params, state, x)
    assert registry.gauge_value("index.tiles_computed") == -1.0
    got = index_loss_and_gradients(selecting_layer(True), params, state, x)
    assert registry.gauge_value("index.tiles_square") == 8.0
    assert registry.gauge_value("index.tiles_computed") == 6.0
    assert float(want[0]) > 0.01
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    leaves = jax.tree_util.tree_leaves_with_path(want[1])
    assert len(leaves) == 5
    for (path, wnt), g in zip(leaves, jax.tree_util.tree_leaves(got[1])):
        assert float(jnp.abs(wnt).max()) > 0, jax.tree_util.keystr(path)
        assert worst(g, wnt) < 1e-4, jax.tree_util.keystr(path)


def test_a_layer_without_an_indexer_traces_as_before():
    """No ``index_topk``: no indexer, no kernel, and the jaxpr of the parent
    commit (its text's digest, taken there under the same JAX), forward and
    gradient."""
    layer = nn.GroupedQueryAttention(4, 2, 16, flash=False)
    layer.name = layer.default_name()
    params, state, _ = layer.init(jax.random.PRNGKey(0), (24, 32))
    x = jnp.zeros((2, 24, 32))

    def loss(p, x):
        return jnp.sum(layer.apply(p, state, x, train=True)[0])

    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert "pallas_call" not in text and "custom_vjp" not in text
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the parent's digest was taken under JAX {PARENT_JAX}")
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPR_SHA256


PARENT_JAX = "0.9.0"
PARENT_JAXPR_SHA256 = (
    "a9198bbb3925b1299b865e23f7ed5fa2b6821aabc3c774e24346cc2d7b125212")
