"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language block
(``model_type: KeyeVL2``; Qwen3-MoE's block with DeepSeek-V3.2-Exp's
lightning indexer, ``sa_config``): straightforward ``jax.numpy``, no kernel,
no buffer, matrix multiplications at precision ``highest``. Text only: the
three position ids of ``mrope_section`` are equal and the rotation is 1-D.

For one sequence x (T, d), every block pre-norm with residual adds:

- main attention: h = RMSNorm(x); q = rope(RMSNorm_hd((h Wq)_head)), (T, H,
  hd); k likewise over Hkv heads; v = h Wv; query head i reads K/V head
  i // (H / Hkv); rope is the half-split rotation at ``theta`` from position
  0; scores q k^T / sqrt(hd), softmax over the keys the query selected,
  times v, times Wo;
- indexer, on h as a constant: qI = rope(h WqI), (T, J, dI); kI =
  rope(LayerNorm(h WkI)), one key head; w = h Ww / sqrt(J dI); the index
  score I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]);
- selection: query t keeps the ``topk`` keys s <= t with the largest I[t, s]
  (all of them while t < topk), found with ``jax.lax.top_k``;
- L_I = mean_t KL(pbar_t || softmax of I[t, .] over the selection), pbar the
  main attention's probabilities over the selection averaged over the heads,
  a constant; the selection is a constant too. The block's loss moves the
  indexer's leaves and nothing else;
- experts: p = softmax(h Wr) over all experts, the ``top_k`` largest, gates
  p / (sum of the chosen p), as a dense loop over the experts held; no
  shared expert, no bias;
- a final RMSNorm and a bias-free head; the loss is the mean next-token
  cross-entropy plus the sum of the layers' L_I.

Everything (T, T) is computed a block of ``q_block`` queries at a time, each
block a ``jax.checkpoint``, so that T = 8192 fits: 32 heads' scores of 512
queries are 0.5 GB.

Departures, mirroring the program and listed in the configuration file: (a)
the chip's share: the blocks' ``experts`` hold the experts ``[expert_offset,
expert_offset + held)`` only, routing is over all of the router's columns,
and what the other experts would add is left out; (b) embedding and head
hold the sliced vocabulary padded to a multiple of 128; (c) no auxiliary
router loss. What ``config.json`` cannot say (the norms on q and k, the
indexer's LayerNorm and RoPE, the scale of w, L_I and its stop-gradients) is
under ``assumed`` there, with its sources.

``variant`` computes a wrong model on purpose, to show that the cell's
limits catch it (``scripts/moe_wrong_models.py``; never used by the
benchmark): ``int8`` (every weight matmul on operands rounded to int8, the
precision below the configuration's bfloat16; the attention's and the
indexer's own products and the router stay float32), ``no_selection``
(the selection ignored: every query sees every key before it, in the main
attention and in L_I) and ``half_selection`` (a selection that keeps too
few keys: the ``topk / 2`` with the largest index score, used and
reported).

Parameters arrive under the names of ``families/keye_vl2.reference_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.deepseek_v3 import (
    HIGHEST, _frozen, _mm, _mm_int8, _norm, _worst, gated_mlp, rms_norm)

NEG = -1e30


def _mm_of(variant):
    return _mm_int8 if variant == "int8" else _mm


def layer_norm(scale, bias, x, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32) + bias.astype(
        jnp.float32)


def rope(x, theta):
    """Half-split RoPE on the last axis of ``x`` (T, ..., d): the pair
    (x[i], x[i + d/2]) at position t turns by t * theta^(-2i/d)."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def own_selection(scores, rows, topk):
    """(n, T) bool: the keys each of the queries at positions ``rows``
    selects by its index ``scores`` (n, T)."""
    causal = rows[:, None] >= jnp.arange(scores.shape[1])[None, :]
    low = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(low, min(topk, scores.shape[1]))[0][:, -1:]
    return jnp.logical_and(causal, low >= kth)


def attention(b, x, forced=None, *, kw, variant=None):
    """``(the layer's output (T, d), L_I, the reference's own selection (T,
    T) bool)`` for the normed input ``x`` (T, d). ``forced`` (T, T) bool
    names the keys to use instead of the reference's own (the program's:
    a selection is discrete, and a comparison of rounding has to start from
    the same keys); the index scores are the reference's own either way."""
    mm = _mm_of(variant)
    t = x.shape[0]
    h, g, hd = kw["n_head"], kw["n_kv"], kw["head_dim"]
    j, di, eps, theta = kw["index_heads"], kw["index_dim"], kw["eps"], kw[
        "theta"]
    q = rope(rms_norm(b["q_norm"], mm(x, b["wq"]).reshape(t, h, hd), eps),
             theta)
    k = rope(rms_norm(b["k_norm"], mm(x, b["wk"]).reshape(t, g, hd), eps),
             theta)
    v = mm(x, b["wv"]).reshape(t, g, hd)
    ix, xc = b["indexer"], jax.lax.stop_gradient(x)
    qi = rope(mm(xc, ix["wq"]).reshape(t, j, di), theta)
    ki = rope(layer_norm(ix["k_norm_scale"], ix["k_norm_bias"],
                         mm(xc, ix["wk"]), eps), theta)
    w = mm(xc, ix["ww"]) / math.sqrt(j * di)
    block = math.gcd(t, kw["q_block"])
    cut = lambda a: a.reshape((t // block, block) + a.shape[1:])

    @jax.checkpoint
    def rows_of(blk):
        qb, qib, wb, rows, forced_b = blk
        dots = jnp.einsum("qjd,sd->qjs", qib, ki, precision=HIGHEST)
        scores = jnp.sum(jax.nn.relu(dots) * wb[:, :, None], axis=1)
        own = own_selection(
            jax.lax.stop_gradient(scores), rows, kw["index_topk"] // (
                2 if variant == "half_selection" else 1))
        used = own if forced_b is None else forced_b
        if variant == "no_selection":
            used = rows[:, None] >= jnp.arange(t)[None, :]
        s = jnp.einsum("qgmd,sgd->gmqs", qb.reshape(block, g, h // g, hd), k,
                       precision=HIGHEST) / math.sqrt(hd)
        # exp(NEG - max) is exactly 0 off the selection: no second mask (a
        # select after the divide makes XLA:TPU lower the row sums as
        # reduce-windows 16,383 wide, 0.9 s a block on the v5e).
        p = jax.nn.softmax(jnp.where(used, s, NEG), axis=-1)
        out = jnp.einsum("gmqs,sgd->qgmd", p, v, precision=HIGHEST)
        target = jax.lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_index = jax.nn.log_softmax(jnp.where(used, scores, NEG), axis=-1)
        kl = jnp.sum(jnp.where(
            used, jax.scipy.special.xlogy(target, target)
            - target * log_index, 0.0))
        return out.reshape(block, h * hd), kl, own

    out, kl, own = jax.lax.map(rows_of, (
        cut(q), cut(qi), cut(w), cut(jnp.arange(t)),
        None if forced is None else cut(forced)))
    return (mm(out.reshape(t, h * hd), b["wo"]), jnp.sum(kl) / t,
            own.reshape(t, t))


def route(b, x, *, top_k, forced=None):
    """``(idx, gates, own)`` (T, top_k) over all of the router's experts:
    the experts used, their gates (the reference's own scores of them,
    normalised over the chosen) and the reference's own choice."""
    scores = jax.nn.softmax(_mm(x, b["router"]), axis=-1)
    _, own = jax.lax.top_k(scores, top_k)
    idx = own if forced is None else forced
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20
                          ), own


def experts(b, x, forced=None, *, kw, variant=None):
    """``(y, the reference's own choice of experts)``: the held experts'
    part of the layer, every held expert on every token, weighted by the
    gate the token gave it or zero."""
    idx, gates, own = route(b, x, top_k=kw["top_k"], forced=forced)
    mm, offset = _mm_of(variant), kw["expert_offset"]

    @jax.checkpoint
    def one(y, ew):
        e, w = ew
        weight = jnp.sum(jnp.where(idx == e + offset, gates, 0.0), axis=-1)
        return y + weight[:, None] * gated_mlp(w, x, mm), None

    held = b["experts"]["gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), b["experts"]))
    return y, own


def block(b, x, forced_keys=None, forced_experts=None, *, kw, variant=None):
    """``(x after the block, L_I, own selection, own experts)``."""
    eps = kw["eps"]
    y, l_i, keys = attention(b, rms_norm(b["norm1"], x, eps), forced_keys,
                             kw=kw, variant=variant)
    x = x + y
    y, own = experts(b, rms_norm(b["norm2"], x, eps), forced_experts, kw=kw,
                     variant=variant)
    return x + y, l_i, keys, own


def unpack(packed, t):
    """A program's recorded selection, (T, T / 8) uint8 bit-packed along the
    keys, as (T, T) bool."""
    return jnp.unpackbits(packed, axis=-1, count=t).astype(bool)


def sequence_loss(p, tokens, labels, *, kw, variant=None, forced=None):
    """``(cross-entropy + sum of L_I, (own selections, own experts, sum of
    L_I))`` of one sequence. ``forced`` is ``{"keys": [(T, T) bool a layer],
    "experts": [(T, top_k) a layer]}``. Each block is a ``jax.checkpoint``
    and the blocks a Python loop (``reference/deepseek_v3.py`` says why)."""
    step = jax.checkpoint(functools.partial(block, kw=kw, variant=variant))
    x = p["wte"].astype(jnp.float32)[tokens]
    keys, chosen, aux = [], [], 0.0
    for i, b in enumerate(p["blocks"]):
        x, l_i, own_keys, own = step(
            b, x, None if forced is None else forced["keys"][i],
            None if forced is None else forced["experts"][i])
        keys.append(own_keys)
        chosen.append(own)
        aux = aux + l_i
    logits = _mm_of(variant)(rms_norm(p["norm_f"], x, kw["eps"]), p["head_w"])
    logp = jax.nn.log_softmax(logits)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return ce + aux, (keys, chosen, aux)


def forward(p, tokens, *, kw, variant=None, forced=None):
    """Logits (T, vocabulary rows held) of one sequence."""
    x = p["wte"].astype(jnp.float32)[tokens]
    for i, b in enumerate(p["blocks"]):
        x = block(b, x, None if forced is None else forced["keys"][i],
                  None if forced is None else forced["experts"][i], kw=kw,
                  variant=variant)[0]
    return _mm_of(variant)(rms_norm(p["norm_f"], x, kw["eps"]), p["head_w"])


def loss_and_grads(p, x, y, *, kw, variant=None, forced=None):
    """``(loss, its gradient in ``p``'s names, (own selections, own experts,
    sum of L_I))`` of the one sequence of ``x`` (1, T)."""
    if x.shape[0] != 1:
        raise ValueError("the reference takes one sequence a batch")
    fn = functools.partial(sequence_loss, kw=kw, variant=variant,
                           forced=forced)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: fn(p, x[0], y[0]), has_aux=True)(p)
    return loss, grads, aux


# Groups of the parameters, by the reference's names, in which a gradient is
# compared with another (``grad_differences``).
ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
GROUPS = ("attention", "indexer", "router", "experts", "other")


def grad_groups(grads):
    blocks = grads["blocks"]
    return {
        "attention": [[b[k] for k in ATTENTION] for b in blocks],
        "indexer": [b["indexer"] for b in blocks],
        "router": [b["router"] for b in blocks],
        "experts": [b["experts"] for b in blocks],
        "other": [grads["wte"], grads["norm_f"], grads["head_w"],
                  [(b["norm1"], b["norm2"]) for b in blocks]],
    }


def grad_differences(grads, other, scale=1.0):
    """``{group: the largest |g - scale * o| / |g| over the group's leaves}``
    over ``GROUPS``, a leaf at a time and the held experts' stacked matrices
    an expert at a time."""
    mine, theirs = grad_groups(grads), grad_groups(other)
    leaves = jax.tree_util.tree_leaves
    return {name: jnp.max(jnp.stack([
        _worst(a, b, scale, per_row=name == "experts")
        for a, b in zip(leaves(mine[name]), leaves(theirs[name]))]))
        for name in GROUPS}


@functools.partial(jax.jit, static_argnames=("kw", "variant"))
def _compare(p, x, y, forced, system_grads, scale, *, kw, variant=None):
    t = x.shape[1]
    if forced is not None:
        forced = dict(forced, keys=[unpack(k, t) for k in forced["keys"]])
    loss, grads, (keys, chosen, aux) = loss_and_grads(
        p, x, y, kw=dict(kw), variant=variant, forced=forced)
    flipped = None if forced is None else {
        "experts": [jnp.sum(jnp.all(f[:, :, None] != own[:, None, :],
                                    axis=-1))
                    for f, own in zip(forced["experts"], chosen)],
        "keys": [jnp.sum(jnp.logical_and(f, jnp.logical_not(own)))
                 for f, own in zip(forced["keys"], keys)],
        "missed_keys": [jnp.sum(jnp.logical_and(own, jnp.logical_not(f)))
                        for f, own in zip(forced["keys"], keys)],
        "selected": [jnp.sum(f) for f in forced["keys"]],
        "own_selected": [jnp.sum(own) for own in keys],
    }
    return {"loss": loss, "grad_norm": _norm(grads), "flipped": flipped,
            "index_loss": aux,
            "grad_differences": grad_differences(grads, system_grads, scale)}


def compare(p, x, y, *, kw, system_grads, scale=1.0, forced=None,
            variant=None):
    """The reference on ``x`` (1, T) against the program's first step:
    ``loss`` (cross-entropy plus the layers' L_I, ``index_loss``) and
    ``grad_norm`` of the reference; ``grad_differences``, the worst leaf of
    each group, of the reference's gradient and ``scale * system_grads``
    (the program's, in ``p``'s names); and, where ``forced`` gives the
    program's own choices (``{"experts": [(T, top_k) a layer], "keys": [(T,
    T / 8) uint8 bit-packed a layer]}``), ``flipped``: in each layer, how
    many (token, choice) pairs name an expert, and how many of the
    ``selected`` (query, key) pairs a key, that the reference, held to the
    program's choices in the layers before, would not choose (``experts``,
    ``keys``), and how many of the reference's ``own_selected`` pairs the
    program left out (``missed_keys``: the reference computes its loss and
    gradients over the program's keys, so a selection that keeps too few
    shows here and nowhere else). One program: no gradient tree leaves the
    device."""
    return _compare(p, x, y, forced, system_grads, scale, kw=_frozen(kw),
                    variant=variant)
