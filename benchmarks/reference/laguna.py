"""Plain float32 reference of Laguna's block (poolside, ``model_type:
laguna``; Laguna-XS.2's ``config.json``): straightforward ``jax.numpy``, no
kernel, no buffer, matrix multiplications at precision ``highest``.

For one sequence x (T, d), every block pre-norm with residual adds,
``x <- x + attention(RMSNorm(x))`` then ``x <- x + ffn(RMSNorm(x))``:

- attention of layer l, h the normed input: q = rope_l(RMSNorm_hd((h
  Wq)_head)), (T, H_l, hd), with H_l the layer's own head count (the width
  of its Wq over hd); k likewise over Hkv heads; v = h Wv; query head i
  reads K/V head i // (H_l / Hkv); scores q k^T / sqrt(hd); query t sees the
  keys s <= t in a ``full_attention`` layer and t - window < s <= t in a
  ``sliding_attention`` one, as a mask on the (T, T) scores, computed
  ``q_block`` queries at a time, each a ``jax.checkpoint``, so that 64 heads
  at T = 8192 fit; softmax, times v: ctx (T, H_l, hd); the gate, one a head
  and token, from the same normed input: out = (ctx * sigmoid(h Wg)[...,
  None]) Wo, Wg (d, H_l);
- rope_l, half-split (``rotate_half``) from position 0 on the first r of a
  head's hd dimensions, the other hd - r passed through: a sliding layer
  turns pair i of all hd = r dimensions by t * theta_s^(-2i/r); a full layer
  turns the first r = hd / 2 by YaRN's frequencies (Peng et al. 2023,
  arXiv:2309.00071, as ``transformers``' ``_compute_yarn_parameters`` sets
  them): with e_i = theta_f^(-2i/r) and the pair index at which a dimension
  makes n turns over the original length L, c(n) = r ln(L / (2 pi n)) / (2
  ln theta_f), the ramp g_i = clip((i - floor(c(beta_fast))) /
  (ceil(c(beta_slow)) - floor(c(beta_fast))), 0, 1) (both ends clipped to
  [0, r - 1]) blends f_i = (1 - g_i) e_i + g_i e_i / factor, and cos and
  sin are multiplied by ``attention_factor``, which so scales the rotated
  dimensions of q and k and not the ones passed through;
- ffn of a ``dense`` layer: down(silu(gate(h)) * up(h));
- ffn of a ``sparse`` layer: s = sigmoid(h Wr) over all the router's
  experts; the ``top_k`` largest of s + b; gates s / (sum of the chosen s +
  1e-20) times ``moe_routed_scaling_factor``; y = sum over the chosen
  experts of gate * expert(h), as a dense loop over the experts held, plus
  the shared expert on every token (``reference/deepseek_v3.py:experts``:
  the same layer);
- logits = RMSNorm(x) W_head, untied; mean next-token cross-entropy.

Departures, mirroring the program and listed in the configuration file: (a)
the chip's share: a block's ``experts`` hold the experts ``[expert_offset,
expert_offset + held)`` only, routing is over all of the router's columns,
and what the other experts would add is left out; the shared expert is
whole; (b) the embedding and the head hold the sliced vocabulary; (c) the
selection bias b is given (the program's buffer, zeros: nothing moves it)
and has no gradient; no auxiliary loss; (d) per-head RMSNorm on q and k and
the gate's form are assumptions of the configuration file, not published.

``variant`` computes a wrong model on purpose, to show that the cell's
limits catch it (``scripts/moe_wrong_models.py``; never used by the
benchmark): ``int8`` (every weight matmul on operands rounded to int8, the
precision below the configuration's bfloat16; the attention's own two
products and the router stay float32), ``full_causal`` (the sliding layers
see every key s <= t), ``no_gate`` (the heads' outputs go to Wo ungated) and
``plain_rope`` (the full layers rotate all hd dimensions at theta_f,
unscaled, as the sliding layers do at theirs).

Parameters arrive under the names of ``families/laguna.reference_params``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3 as ds
from benchmarks.reference.deepseek_v3 import (
    HIGHEST, _frozen, _mm_of, _norm, _worst, gated_mlp, rms_norm)


def yarn_frequencies(r, theta, factor, original, beta_fast, beta_slow):
    """f_i of the module docstring, (r / 2,) float32."""
    def pair_of(turns):
        return r * math.log(original / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), r - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(r // 2, dtype=jnp.float32)
    e = theta ** (-2.0 * i / r)
    g = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - g) * e + g * e / factor


def rope(x, freqs, scale=1.0):
    """Half-split rotation of the first ``2 * len(freqs)`` dimensions of the
    last axis of ``x`` (T, heads, hd): the pair (x[i], x[i + r/2]) at
    position t turns by t * freqs[i], cos and sin times ``scale``; the
    dimensions from r on pass through."""
    t, r = x.shape[0], 2 * freqs.shape[0]
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None]
             )[:, None, :]
    cos, sin = scale * jnp.cos(angle), scale * jnp.sin(angle)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


def rotation(sliding, *, kw, variant=None):
    """``(freqs, scale)`` of a layer's kind."""
    hd = kw["head_dim"]
    if sliding or variant == "plain_rope":
        theta = kw["theta_sliding"] if sliding else kw["theta_full"]
        r = kw["rotary_sliding"] if sliding else hd
        return theta ** (-2.0 * jnp.arange(r // 2, dtype=jnp.float32) / r), 1.0
    return yarn_frequencies(
        kw["rotary_full"], kw["theta_full"], kw["yarn_factor"],
        kw["yarn_original"], kw["yarn_beta_fast"], kw["yarn_beta_slow"]
    ), kw["yarn_attention_factor"]


def attention(a, x, sliding, *, kw, variant=None):
    """Gated grouped-query attention of the normed input ``x`` (T, d),
    windowed where ``sliding``."""
    mm = _mm_of(variant)
    t = x.shape[0]
    g, hd, eps = kw["n_kv"], kw["head_dim"], kw["eps"]
    h = a["wq"].shape[1] // hd
    freqs, scale = rotation(sliding, kw=kw, variant=variant)
    q = rope(rms_norm(a["q_norm"], mm(x, a["wq"]).reshape(t, h, hd), eps),
             freqs, scale)
    k = rope(rms_norm(a["k_norm"], mm(x, a["wk"]).reshape(t, g, hd), eps),
             freqs, scale)
    v = mm(x, a["wv"]).reshape(t, g, hd)
    window = kw["window"] if sliding and variant != "full_causal" else t
    block = math.gcd(t, kw["q_block"])
    cut = lambda arr: arr.reshape((t // block, block) + arr.shape[1:])

    @jax.checkpoint
    def rows_of(blk):
        qb, rows = blk
        s = jnp.einsum("qgmd,sgd->gmqs", qb.reshape(block, g, h // g, hd), k,
                       precision=HIGHEST) / math.sqrt(hd)
        keys = jnp.arange(t)[None, :]
        seen = jnp.logical_and(keys <= rows[:, None],
                               keys > rows[:, None] - window)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("gmqs,sgd->qgmd", p, v,
                          precision=HIGHEST).reshape(block, h, hd)

    ctx = jax.lax.map(rows_of, (cut(q), cut(jnp.arange(t)))).reshape(t, h, hd)
    if variant != "no_gate":
        ctx = ctx * jax.nn.sigmoid(mm(x, a["wg"]))[..., None]
    return mm(ctx.reshape(t, h * hd), a["wo"])


def block(b, x, forced=None, *, kw, variant=None):
    """``(x after the block, the experts it would choose or None)``. A
    sliding layer's attention arrives under ``swa``, a full one's under
    ``attn``."""
    eps = kw["eps"]
    sliding = "swa" in b
    x = x + attention(b["swa" if sliding else "attn"],
                      rms_norm(b["norm1"], x, eps), sliding, kw=kw,
                      variant=variant)
    h = rms_norm(b["norm2"], x, eps)
    if "mlp" in b:
        return x + gated_mlp(b["mlp"], h, _mm_of(variant)), None
    y, own = ds.experts(b, h, top_k=kw["top_k"], scaling=kw["scaling"],
                        expert_offset=kw["expert_offset"], variant=variant,
                        forced=forced)
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
    return _mm_of(variant)(rms_norm(p["norm_f"], x, kw["eps"]), p["head_w"])


def forward(p, tokens, *, kw, variant=None, forced=None):
    """Logits (T, vocabulary rows held) of one sequence."""
    return _logits(p, hidden_and_routing(
        p, tokens, kw=kw, variant=variant, forced=forced)[0], kw, variant)


def sequence_loss(p, tokens, labels, *, kw, variant=None, forced=None):
    """``(mean next-token cross-entropy of one sequence, {"experts": the
    reference's own choices [(T, top_k) an expert layer]})``."""
    x, chosen = hidden_and_routing(p, tokens, kw=kw, variant=variant,
                                   forced=forced)
    logp = jax.nn.log_softmax(_logits(p, x, kw, variant))
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, {"experts": chosen}


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
GROUPS = ("full_attention", "sliding_attention", "dense_mlp", "router",
          "shared", "experts", "other")


def grad_groups(grads):
    blocks = grads["blocks"]
    pick = lambda key: [b[key] for b in blocks if key in b]
    return {
        "full_attention": pick("attn"), "sliding_attention": pick("swa"),
        "dense_mlp": pick("mlp"), "router": pick("router"),
        "shared": pick("shared"), "experts": pick("experts"),
        "other": [grads["wte"], grads["norm_f"], grads["head_w"],
                  [(b["norm1"], b["norm2"]) for b in blocks]],
    }


def grad_differences(grads, other, scale=1.0):
    """``{group: the largest |g - scale * o| / |g| over the group's leaves}``
    over the groups that hold a leaf, a leaf at a time (the gate's matrix
    and the q and k norms are leaves of their attention layer's group) and
    the held experts' stacked matrices an expert at a time."""
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
            "grad_differences": grad_differences(grads, system_grads, scale)}


def compare(p, x, y, *, kw, system_grads, scale=1.0, forced=None):
    """The reference on ``x`` (1, T) against the program's first step:
    ``loss`` and ``grad_norm`` of the reference; ``grad_differences``, the
    worst leaf of each group, of the reference's gradient and ``scale *
    system_grads`` (the program's, in ``p``'s names); and, where ``forced``
    gives the program's own choices of experts ([(T, top_k) an expert
    layer]), ``flipped``: in each expert layer, how many (token, choice)
    pairs name an expert the reference, held to those choices in the layers
    before, would not choose for that token. One program: no gradient tree
    leaves the device."""
    return _compare(p, x, y, forced, system_grads, scale, kw=_frozen(kw))
