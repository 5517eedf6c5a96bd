"""The lightning indexer's scores, and a gradient of them that keeps nothing
(n, J, T) wide.

``index_scores`` is the plain form: ``I[t, s] = sum_j w[t, j] relu(qi[t, j] .
ki[s])``. Where no gradient is taken (the selection) XLA fuses product, ReLU,
weights and head sum into one pass. Under autodiff it keeps the (n, J, T)
float32 products for the ReLU's and the weights' gradients: at n = 512, J =
16, T = 8192 it writes 268 MB to HBM and reads them back three times, for
every block of queries (root PERF.md, PR 33).

``block_index_scores`` is the same scores for a block of queries whose first
is ``row0``, over the keys up to the block's last query (zero after them:
``L_I`` reads no score above the diagonal), with a hand-written gradient
whose residuals are the inputs. Its backward is one Pallas kernel,
``dtpu_index_scores_bwd``, which recomputes the products a key tile at a
time in VMEM and walks only the key tiles that hold a key of the block. With
``m[t, j, s] = dI[t, s] (qi[t, j] . ki[s] > 0)``, in the inputs' dtype as an
MXU operand (the flash kernels' rule; float32 accumulators):

    G[t, j]  = sum_s m[t, j, s] ki[s]          d_qi = w G
    d_w[t, j] = qi[t, j] . G[t, j]             (= sum_s dI relu(qi . ki))
    d_ki[s]  = sum_(t, j) m[t, j, s] w[t, j] qi[t, j]

so the kernel forms one masked copy of ``dI`` a head and key tile, and
neither ``w`` nor a row reduction enters its loop: ``G`` and ``d_ki`` leave
the kernel, the two small products with ``w`` and ``qi`` are XLA's. Layout:
two heads of 64 share a 128-lane block of qi as projected, and a head's
products contract that whole block against the key tile with the other
head's half zeroed (``ki^T``, (d, T), is handed in and stacked over or
under zeros in VMEM), so no lane is sliced or rotated; ``d_ki`` is
accumulated transposed, (d, T), its rows the product of ``(w qi)^T`` with
``m`` as it stands. The grid walks the key tiles; a tile after the block's
last query fetches nothing new (its index maps stay on the last tile walked)
and writes zeros to its rows of ``d_ki``.

Mosaic on TPU, the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANES, interpret as _interpret

# Keys a grid step: a (n, tile) float32 block of dI and of the products at a
# time (1 MB each at n = 512), under the 16 MB a kernel may use.
KEY_TILE = 512


def index_scores(qi, ki, w):
    """The lightning indexer's score of every key for a block of queries:
    ``I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])`` in float32, for qi
    (n, J, d), the one key head ki (T, d) and w (n, J)."""
    dots = jnp.einsum("qjd,sd->qjs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None].astype(jnp.float32),
                   axis=1)


def key_tile(t: int, itemsize: int) -> int:
    """Keys a grid step of the backward kernel: ``KEY_TILE`` under 2-byte
    inputs, half under float32 ones (the flash kernels' clamp), the whole
    sequence where that is shorter."""
    return min(t, KEY_TILE if itemsize <= 2 else KEY_TILE // 2)


def kernel_fits(n: int, heads: int, d: int, t: int, itemsize: int) -> bool:
    """Whether ``dtpu_index_scores_bwd`` takes a block of n queries of
    ``heads`` heads of d against T keys: heads of 64 in pairs (two fill the
    128 lanes), n a multiple of 8 sublanes, T whole key tiles of whole
    lanes."""
    tk = key_tile(t, itemsize)
    return (2 * d == LANES and heads % 2 == 0 and n % 8 == 0
            and tk % LANES == 0 and t % tk == 0)


def tile_counts(t: int, n: int, itemsize: int):
    """(key tiles the blocks of n queries of a sequence of T could walk,
    those they do walk: the tiles that hold a key of the block): the gauges
    ``index.tiles_square`` / ``index.tiles_computed``; 256 and 136 at T =
    8192, n = 512."""
    tk = key_tile(t, itemsize)
    blocks = t // n
    return blocks * (t // tk), sum(
        -(-(b + 1) * n // tk) for b in range(blocks))


def _bwd_kernel(row0_ref, q_ref, qwt_ref, kt_ref, ds_ref, g_ref, dkt_ref, *,
                n, tk, pairs):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    dkt_ref[...] = jnp.zeros_like(dkt_ref)

    @pl.when(step * tk < row0_ref[0] + n)
    def _():
        kt = kt_ref[...]
        none = jnp.zeros_like(kt)
        # The key tile for the pair's two heads: in the low lanes' rows of
        # the contraction, in the high lanes'; the other head's rows zero.
        halves = (jnp.concatenate([kt, none], axis=0),
                  jnp.concatenate([none, kt], axis=0))
        nt = (((1,), (1,)), ((), ()))

        def pair(p, carry):
            qp = q_ref[p]
            g = g_ref[p]
            for half, kh in enumerate(halves):
                dots = jnp.dot(qp, kh, preferred_element_type=jnp.float32)
                m = jnp.where(dots > 0.0, ds_ref[...], 0.0).astype(qp.dtype)
                g = g + jax.lax.dot_general(
                    m, kh, nt, preferred_element_type=jnp.float32)
                dkt_ref[...] += jnp.dot(qwt_ref[2 * p + half], m,
                                        preferred_element_type=jnp.float32)
            g_ref[p] = g
            return carry

        jax.lax.fori_loop(0, pairs, pair, 0)


def index_scores_bwd(qi, ki, w, d_scores, row0):
    """(d_qi (n, J, d), d_ki (T, d), d_w (n, J)), float32, of
    ``index_scores(qi, ki, w)`` under the cotangent ``d_scores`` (n, T)
    float32 of a block of queries whose first is ``row0``, over the keys up
    to the block's last query: the key tiles after it are not walked,
    whatever ``d_scores`` holds there, and their rows of d_ki are zero.
    Shapes as ``kernel_fits`` says."""
    n, j, d = qi.shape
    t = ki.shape[0]
    tk = key_tile(t, jnp.dtype(qi.dtype).itemsize)
    pairs = j // 2
    wf = w.astype(jnp.float32)
    # Heads in pairs, a pair a 128-lane block; (w qi)^T a head; ki^T.
    q2 = jnp.moveaxis(qi.reshape(n, pairs, 2 * d), 1, 0)
    qwt = jnp.transpose(
        (qi.astype(jnp.float32) * wf[:, :, None]).astype(qi.dtype), (1, 2, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda s, r: (0,) * len(shape))
    # A key tile of the step's own, or the last the block walks.
    walked = lambda s, r: (0, jnp.minimum(s, (r[0] + n - 1) // tk))
    g, dkt = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, tk=tk, pairs=pairs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(t // tk,),
            in_specs=[
                whole((pairs, n, 2 * d)),
                whole((j, d, n)),
                pl.BlockSpec((d, tk), walked),
                pl.BlockSpec((n, tk), walked),
            ],
            out_specs=[
                whole((pairs, n, 2 * d)),
                pl.BlockSpec((d, tk), lambda s, r: (0, s)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((pairs, n, 2 * d), jnp.float32),
                   jax.ShapeDtypeStruct((d, t), jnp.float32)],
        name="dtpu_index_scores_bwd", interpret=_interpret(),
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), q2, qwt, ki.T,
      d_scores.astype(jnp.float32))
    g = jnp.moveaxis(g, 0, 1).reshape(n, j, d)
    return (g * wf[:, :, None], dkt.T,
            jnp.sum(g * qi.astype(jnp.float32), axis=-1))


@jax.custom_vjp
def block_index_scores(qi, ki, w, row0):
    """``index_scores`` of a block of queries whose first is ``row0``, over
    the keys up to the block's last query; zero for the keys after it. Its
    gradient recomputes the products on the chip (``index_scores_bwd``) and
    keeps qi, ki and w alone."""
    seen = jnp.arange(ki.shape[0])[None, :] < row0 + qi.shape[0]
    return jnp.where(seen, index_scores(qi, ki, w), 0.0)


def _block_fwd(qi, ki, w, row0):
    return block_index_scores(qi, ki, w, row0), (qi, ki, w, row0)


def _block_bwd(res, d_scores):
    qi, ki, w, row0 = res
    d_qi, d_ki, d_w = index_scores_bwd(qi, ki, w, d_scores, row0)
    return (d_qi.astype(qi.dtype), d_ki.astype(ki.dtype),
            d_w.astype(w.dtype), None)


block_index_scores.defvjp(_block_fwd, _block_bwd)
