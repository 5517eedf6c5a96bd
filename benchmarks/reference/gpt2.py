"""Plain float32 reference of the GPT-2 block (Radford et al. 2019), as
straightforward ``jax.numpy``: no kernel, no cache, no batching tricks,
matrix multiplications at precision ``highest`` (a TPU would otherwise run
a float32 matmul in bf16 passes).

It follows the published equations (written for one sequence; the layers
are a loop over stacked weights, see ``forward``): token + learned position embeddings;
per block ``x += Attn(LN(x))`` and ``x += W2 gelu_new(W1 LN(x))`` with
biased projections and causal softmax(QK^T / sqrt(head)) V; a final layer
norm; logits. Three departures mirror the program under test and are
listed in each configuration file under ``assumed``:

(a) the embedding and head hold the vocabulary padded to a multiple of 128;
(b) the head is an untied, biased matrix (GPT-2 ties it to ``wte``);
(c) layer norms run with the epsilon the program uses (1e-6, not 1e-5).

Parameters arrive under the names of ``families/gpt2.reference_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps)) * p["scale"].astype(
        jnp.float32) + p["bias"].astype(jnp.float32)


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(b, x, n_head):
    t, d = x.shape
    hd = d // n_head
    q = (_mm(x, b["wq"]) + b["bq"]).reshape(t, n_head, hd)
    k = (_mm(x, b["wk"]) + b["bk"]).reshape(t, n_head, hd)
    v = (_mm(x, b["wv"]) + b["bv"]).reshape(t, n_head, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(
        hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    return _mm(ctx.reshape(t, d), b["wo"]) + b["bo"]


def block(b, x, n_head: int, eps: float):
    x = x + attention(b, layer_norm(b["ln1"], x, eps), n_head)
    h = gelu_new(_mm(layer_norm(b["ln2"], x, eps), b["w1"]) + b["b1"])
    return x + _mm(h, b["w2"]) + b["b2"]


def forward(p, tokens, *, n_head: int, eps: float):
    """Logits (T, vocab rows) of one sequence ``tokens`` (T,).

    The blocks run as a loop (``lax.scan``) over their stacked weights, so
    the program holds one block's code whatever the depth, and each
    iteration is a ``jax.checkpoint``: under ``jax.grad`` only a block's
    input is kept and the block is computed again. Neither changes a value;
    together they let a gradient at full width compile in seconds and fit
    beside the system's own state. The stack is a transient copy of the
    block weights inside this program."""
    t = tokens.shape[0]
    x = p["wte"].astype(jnp.float32)[tokens] + p["wpe"].astype(
        jnp.float32)[:t]
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                     *p["blocks"])
    step = jax.checkpoint(functools.partial(block, n_head=n_head, eps=eps))
    x, _ = jax.lax.scan(lambda x, b: (step(b, x), None), x, stacked)
    x = layer_norm(p["lnf"], x, eps)
    return _mm(x, p["head_w"]) + p["head_b"].astype(jnp.float32)


def sequence_loss(p, tokens, labels, *, n_head: int, eps: float):
    """Mean next-token cross-entropy of one sequence."""
    logp = jax.nn.log_softmax(forward(p, tokens, n_head=n_head, eps=eps))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def batch_loss(p, x, y, *, n_head: int, eps: float):
    """Mean loss over the sequences of ``x`` (B, T), one at a time (a
    sequential map, so one sequence's activations are live at once)."""
    fn = functools.partial(sequence_loss, n_head=n_head, eps=eps)
    return jnp.mean(jax.lax.map(lambda xy: fn(p, xy[0], xy[1]), (x, y)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def loss_and_grad_norm(p, x, y, *, n_head: int, eps: float):
    """(mean loss, global L2 norm of its gradient) over ``x`` (B, T): one
    sequence's loss and gradient at a time, summed in a loop, so only one
    sequence's activations and two gradient trees are ever live."""
    fn = jax.value_and_grad(
        functools.partial(sequence_loss, n_head=n_head, eps=eps))

    def one(carry, xy):
        loss, grads = fn(p, xy[0], xy[1])
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    zero = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), p)
    (loss, grads), _ = jax.lax.scan(one, (jnp.float32(0.0), zero), (x, y))
    n = x.shape[0]
    sq = sum(jnp.sum(jnp.square(g / n))
             for g in jax.tree_util.tree_leaves(grads))
    return loss / n, jnp.sqrt(sq)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def log_probs(p, tokens, *, n_head: int, eps: float):
    """log-softmax of the logits at every position of ``tokens`` (T,)."""
    return jax.nn.log_softmax(forward(p, tokens, n_head=n_head, eps=eps))
