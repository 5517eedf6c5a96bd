"""Plain float32 reference of LFM2-MoE's block (Liquid AI, ``model_type:
lfm2_moe``; LFM2-8B-A1B; Hugging Face ``transformers``,
``models/lfm2_moe/modeling_lfm2_moe.py``): straightforward ``jax.numpy``, no
kernel, no buffer, matrix multiplications at precision ``highest``.

For one sequence x (T, d), every block pre-norm with residual adds,
``x <- x + mixer(RMSNorm(x))`` then ``x <- x + ffn(RMSNorm(x))``:

- mixer ``conv`` (``Lfm2MoeShortConv``): (b, c, z) = split3(h W_in), in this
  order; g = b * z; u[t] = sum_j taps[j] * g[t - (K - 1) + j] with g[< 0] =
  0, a tap a channel and offset (the published ``Conv1d`` with ``groups =
  d``, ``padding = K - 1``, cut to the first T outputs, no bias); out = (c *
  u) W_out. Written as K copies of g, each moved down by its tap's offset
  with zeros moved in;
- mixer ``full_attention``: q = rope(RMSNorm_hd((h Wq)_head)), (T, H, hd); k
  likewise over Hkv heads; v = h Wv; query head i reads K/V head i // (H /
  Hkv); rope is the half-split rotation at ``theta`` from position 0; scores
  q k^T / sqrt(hd), causal softmax, times v, times Wo; computed a block of
  ``q_block`` queries at a time, each a ``jax.checkpoint``, so that T = 8192
  fits;
- ffn of the leading dense layers: down(silu(gate(h)) * up(h));
- ffn of the others: s = sigmoid(h Wr); the ``top_k`` experts of a token are
  the largest of s + b (``use_expert_bias``); gates s / (sum of the chosen s
  + 1e-6) (``norm_topk_prob``) times ``routed_scaling_factor``; y = sum over
  the chosen experts of gate * expert(h), as a dense loop over the experts
  held; no shared expert;
- logits = RMSNorm(x) E^T with E the embedding's own table (tied); mean
  next-token cross-entropy.

Departures, mirroring the program and listed in the configuration file: (a)
the chip's share: a block's ``experts`` hold the experts ``[expert_offset,
expert_offset + held)`` only, routing is over all of the router's columns,
and what the other experts would add is left out; (b) the table holds the
sliced vocabulary; (c) the selection bias b is given (the program's buffer
as the step found it) and has no gradient; no auxiliary loss.

``variant`` computes a wrong model on purpose, to show that the cell's
limits catch it (``scripts/moe_wrong_models.py``; never used by the
benchmark): ``int8`` (every weight matmul on operands rounded to int8, the
precision below the configuration's bfloat16; the attention's own two
products, the taps and the router stay float32), ``no_lookback`` (the taps
replaced by their last one: u[t] = taps[K - 1] * g[t]) and ``untied_head``
(the head reads a copy of the table that takes no gradient, so the table's
gradient is the gather's alone, as an optimizer holding two leaves would
see it).

Parameters arrive under the names of ``families/lfm2_moe.reference_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference.deepseek_v3 import (
    HIGHEST, _frozen, _mm, _mm_int8, _norm, _worst, gated_mlp, rms_norm)
from benchmarks.reference.keye_vl2 import rope

# As published. The program's layer adds 1e-20 (DeepSeek-V3's): over four
# sigmoid scores the two agree to float32 rounding, about 5e-7 of a gate.
GATE_EPS = 1e-6


def _mm_of(variant):
    return _mm_int8 if variant == "int8" else _mm


def short_conv(c, x, *, variant=None):
    """The gated short convolution of the normed input ``x`` (T, d) with
    ``c = {"w_in", "taps", "w_out"}``."""
    mm = _mm_of(variant)
    t = x.shape[0]
    b, gate, z = jnp.split(mm(x, c["w_in"]), 3, axis=-1)
    g = b * z
    taps = c["taps"].astype(jnp.float32)
    k = taps.shape[0]
    if variant == "no_lookback":
        u = taps[k - 1] * g
    else:
        u = jnp.zeros_like(g)
        for j in range(k):
            back = k - 1 - j  # tap j reads g[t - back]
            u = u + taps[j] * jnp.concatenate(
                [jnp.zeros((back, g.shape[1]), g.dtype), g[:t - back]])
    return mm(gate * u, c["w_out"])


def attention(a, x, *, kw, variant=None):
    """Causal grouped-query attention of the normed input ``x`` (T, d)."""
    mm = _mm_of(variant)
    t = x.shape[0]
    h, g, hd = kw["n_head"], kw["n_kv"], kw["head_dim"]
    eps, theta = kw["eps"], kw["theta"]
    q = rope(rms_norm(a["q_norm"], mm(x, a["wq"]).reshape(t, h, hd), eps),
             theta)
    k = rope(rms_norm(a["k_norm"], mm(x, a["wk"]).reshape(t, g, hd), eps),
             theta)
    v = mm(x, a["wv"]).reshape(t, g, hd)
    block = math.gcd(t, kw["q_block"])
    cut = lambda arr: arr.reshape((t // block, block) + arr.shape[1:])

    @jax.checkpoint
    def rows_of(blk):
        qb, rows = blk
        s = jnp.einsum("qgmd,sgd->gmqs", qb.reshape(block, g, h // g, hd), k,
                       precision=HIGHEST) / math.sqrt(hd)
        seen = rows[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("gmqs,sgd->qgmd", p, v,
                          precision=HIGHEST).reshape(block, h * hd)

    out = jax.lax.map(rows_of, (cut(q), cut(jnp.arange(t))))
    return mm(out.reshape(t, h * hd), a["wo"])


def route(b, x, *, top_k, scaling, forced=None):
    """``(idx, gates, own)`` (T, top_k) over all of the router's experts:
    the experts used (``forced``, the program's own, where given), their
    gates by the reference's own scores, and the reference's own choice."""
    scores = jax.nn.sigmoid(_mm(x, b["router"]))
    _, own = jax.lax.top_k(scores + b["router_bias"].astype(jnp.float32),
                           top_k)
    idx = own if forced is None else forced
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + GATE_EPS)
    return idx, gates * scaling, own


def experts(b, x, forced=None, *, kw, variant=None):
    """``(y, the reference's own choice of experts)``: the held experts'
    part of the layer, every held expert on every token, weighted by the
    gate the token gave it or zero."""
    idx, gates, own = route(b, x, top_k=kw["top_k"], scaling=kw["scaling"],
                            forced=forced)
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


def block(b, x, forced=None, *, kw, variant=None):
    """``(x after the block, the experts it would choose or None)``."""
    eps = kw["eps"]
    h = rms_norm(b["norm1"], x, eps)
    if "conv" in b:
        x = x + short_conv(b["conv"], h, variant=variant)
    else:
        x = x + attention(b["attn"], h, kw=kw, variant=variant)
    h = rms_norm(b["norm2"], x, eps)
    if "mlp" in b:
        return x + gated_mlp(b["mlp"], h, _mm_of(variant)), None
    y, own = experts(b, h, forced, kw=kw, variant=variant)
    return x + y, own


def hidden_and_routing(p, tokens, *, kw, variant=None, forced=None):
    """``(x (T, d) before the final norm, [the reference's own choice of
    experts (T, top_k) in each expert layer])`` of one sequence; ``forced``
    names, expert layer by expert layer, the experts to use instead. Each
    block is a ``jax.checkpoint`` and the blocks a Python loop
    (``reference/deepseek_v3.py`` says why)."""
    step = jax.checkpoint(functools.partial(block, kw=kw, variant=variant))
    x = p["wte"].astype(jnp.float32)[tokens]
    chosen = []
    for b in p["blocks"]:
        if "mlp" in b:
            x, _ = step(b, x)
            continue
        x, own = step(b, x, None if forced is None else forced[len(chosen)])
        chosen.append(own)
    return x, chosen


def _logits(p, x, kw, variant=None):
    table = p["wte"]
    if variant == "untied_head":
        table = jax.lax.stop_gradient(table)
    return _mm_of(variant)(rms_norm(p["norm_f"], x, kw["eps"]),
                           table.astype(jnp.float32).T)


def forward(p, tokens, *, kw, variant=None, forced=None):
    """Logits (T, vocabulary rows held) of one sequence."""
    return _logits(p, hidden_and_routing(
        p, tokens, kw=kw, variant=variant, forced=forced)[0], kw, variant)


def sequence_loss(p, tokens, labels, *, kw, variant=None, forced=None):
    """``(mean next-token cross-entropy of one sequence, {"experts": the
    reference's own choices [(T, top_k) an expert layer],
    "logit_mean_square": that of its logits})``."""
    x, chosen = hidden_and_routing(p, tokens, kw=kw, variant=variant,
                                   forced=forced)
    logits = _logits(p, x, kw, variant)
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, {"experts": chosen, "logit_mean_square":
                  jax.lax.stop_gradient(jnp.mean(jnp.square(logits)))}


def loss_and_grads(p, x, y, *, kw, variant=None, forced=None):
    """``(loss, its gradient in ``p``'s names, ``sequence_loss``'s second
    value)`` of the one sequence of ``x`` (1, T). The selection bias only
    picks indices: its gradient is zero."""
    if x.shape[0] != 1:
        raise ValueError("the reference takes one sequence a batch")
    fn = functools.partial(sequence_loss, kw=kw, variant=variant,
                           forced=forced)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: fn(p, x[0], y[0]), has_aux=True)(p)
    return loss, grads, aux


# Groups of the parameters, by the reference's names, in which a gradient is
# compared with another (``grad_differences``).
GROUPS = ("short_conv", "attention", "dense_mlp", "router", "experts",
          "table", "norms")


def grad_groups(grads):
    blocks = grads["blocks"]
    pick = lambda key: [b[key] for b in blocks if key in b]
    return {
        "short_conv": pick("conv"), "attention": pick("attn"),
        "dense_mlp": pick("mlp"), "router": pick("router"),
        "experts": pick("experts"), "table": [grads["wte"]],
        "norms": [grads["norm_f"],
                  [(b["norm1"], b["norm2"]) for b in blocks]],
    }


def grad_differences(grads, other, scale=1.0):
    """``{group: the largest |g - scale * o| / |g| over the group's leaves}``
    over the groups that hold a leaf, a leaf at a time and the held experts'
    stacked matrices an expert at a time."""
    mine, theirs = grad_groups(grads), grad_groups(other)
    leaves = jax.tree_util.tree_leaves
    return {name: jnp.max(jnp.stack([
        _worst(a, b, scale, per_row=name == "experts")
        for a, b in zip(leaves(mine[name]), leaves(theirs[name]))]))
        for name in GROUPS if leaves(mine[name])}


@functools.partial(jax.jit, static_argnames=("kw",))
def _compare(p, x, y, forced, system_grads, scale, *, kw):
    loss, grads, own = loss_and_grads(p, x, y, kw=dict(kw), forced=forced)
    flipped = None if forced is None else [
        jnp.sum(jnp.all(f[:, :, None] != o[:, None, :], axis=-1))
        for f, o in zip(forced, own["experts"])]
    return {"loss": loss, "grad_norm": _norm(grads), "flipped": flipped,
            "logit_mean_square": own["logit_mean_square"],
            "grad_differences": grad_differences(grads, system_grads, scale)}


def compare(p, x, y, *, kw, system_grads, scale=1.0, forced=None):
    """The reference on ``x`` (1, T) against the program's first step:
    ``loss`` and ``grad_norm`` of the reference; ``logit_mean_square``, the
    mean square of its first logits (a tied table's logits are not near
    zero at the start, and the first loss stands that much above ln(rows):
    ``drivers/train_family_tied.py``); ``grad_differences``, the worst leaf
    of each group, of the reference's gradient and ``scale * system_grads``
    (the program's, in ``p``'s names); and, where ``forced`` gives the
    program's own choices of experts ([(T, top_k) an expert layer]),
    ``flipped``: in each expert layer, how many (token, choice) pairs name an
    expert the reference, held to those choices in the layers before, would
    not choose for that token. One program: no gradient tree leaves the
    device."""
    return _compare(p, x, y, forced, system_grads, scale, kw=_frozen(kw))
