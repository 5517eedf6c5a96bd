"""Plain float32 reference of the DeepSeek-V3 block (DeepSeek-AI 2024,
arXiv:2412.19437; Hugging Face ``modeling_deepseek_v3.py``) without query
compression, as kanana-2-30b-a3b publishes it: straightforward
``jax.numpy``, no kernel, no sort, no buffer, matrix multiplications at
precision ``highest``.

For one sequence x (T, d), every block pre-norm with residual adds:

- attention: h = RMSNorm(x); q = h Wq, (T, H, nope + rope); [c | k_rope] =
  h Wkv_a; [k_nope | v] = RMSNorm(c) Wkv_b, (T, H, nope + v); RoPE on
  adjacent pairs (``rope_interleave``), theta ``rope_theta``, from position
  0, on q's rope part and on the one k_rope that all heads share; scores
  q k^T / sqrt(nope + rope), causal softmax, times v, times Wo. Computed a
  head at a time so that a (T, T) matrix of one head is all that is live;
- the first ``first_dense`` layers' MLP: down(silu(gate(h)) * up(h));
- expert layers: s = sigmoid(h Wr); the ``top_k`` experts of a token are the
  largest of s + b; gates s / (sum of the chosen s + 1e-20) *
  ``routed_scaling``; y = sum over the chosen experts of gate * expert(h),
  as a dense loop over the experts: every expert sees every token, weighted
  by the gate the token gave it or zero; plus the shared expert;
- a final RMSNorm and a bias-free head; mean next-token cross-entropy.

Departures, mirroring the program and listed in the configuration file:

(a) the chip's share: ``p["blocks"][i]["experts"]`` holds the experts
    ``[expert_offset, expert_offset + held)`` only, and the loop runs over
    those; routing is over all of the router's columns; what the other
    experts would add is left out, as in the program;
(b) the embedding and head hold the sliced vocabulary padded to a multiple
    of 128, and the loss is over those rows;
(c) the selection bias b is given (the program's buffer as the step
    found it; its update after a step changes no loss and no gradient of
    that step) and no auxiliary loss exists.

``variant`` computes a wrong model on purpose, to show that the cell's
tolerances catch it (``scripts/moe_wrong_models.py``, PERF.md; never used
by the benchmark): ``no_shared``,
``unnormalised_gates``, ``capacity`` (pairs beyond 1.25 x the mean load of
an expert dropped), ``int8_experts`` (the routed experts' matmuls on
operands rounded to int8 with one scale a row and a column) and ``int8``
(every weight matmul so: the precision below the bfloat16 the configuration
states; the attention's own two products and the router stay float32).

Parameters arrive under the names of ``families/deepseek_v3.reference_params``.
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


def _int8(a, axis):
    """``a`` rounded to 255 levels with one scale along ``axis``; the
    rounding passes gradients straight through, as int8 training does."""
    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return a + jax.lax.stop_gradient(jnp.round(a / scale) * scale - a)


def _mm_int8(a, b):
    return _mm(_int8(a, -1), _int8(b, 0))


def _mm_of(variant):
    return _mm_int8 if variant == "int8" else _mm


def rms_norm(scale, x, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * scale.astype(jnp.float32)


def rope(x, theta):
    """Interleaved RoPE on the last axis of ``x`` (T, ..., d): the pair
    (x[2i], x[2i+1]) at position t is the complex number x[2i] + i x[2i+1]
    times exp(i t theta^(-2i/d))."""
    t, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    z = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * jnp.exp(
        1j * angle.astype(jnp.complex64))
    return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)


def attention(b, x, *, n_head, nope, rope_dim, v_dim, kv_rank, theta, eps,
              mm=_mm):
    t = x.shape[0]
    q = mm(x, b["wq"]).reshape(t, n_head, nope + rope_dim)
    latent = mm(x, b["wkv_a"])
    c = rms_norm(b["kv_norm"], latent[:, :kv_rank], eps)
    k_rope = rope(latent[:, kv_rank:], theta)                 # (T, rope)
    kv = mm(c, b["wkv_b"]).reshape(t, n_head, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):
        qh, kh_nope, vh = qkv                                 # (T, .)
        kh = jnp.concatenate([kh_nope, k_rope], axis=-1)
        scores = jnp.matmul(qh, kh.T, precision=HIGHEST) / math.sqrt(
            nope + rope_dim)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.matmul(probs, vh, precision=HIGHEST)

    by_head = lambda a: jnp.moveaxis(a, 1, 0)
    ctx = jax.lax.map(head, (by_head(q), by_head(kv[..., :nope]),
                             by_head(kv[..., nope:])))        # (H, T, v)
    return mm(jnp.moveaxis(ctx, 0, 1).reshape(t, n_head * v_dim), b["wo"])


def gated_mlp(w, x, mm=_mm):
    return mm(jax.nn.silu(mm(x, w["gate"])) * mm(x, w["up"]), w["down"])


def route(b, x, *, top_k, scaling, variant=None, forced=None):
    """``(idx, gates, own)`` (T, top_k) over all of the router's experts:
    the experts used, their gates, and the reference's own choice. They
    differ only where ``forced`` names the experts to use (the program's own
    choices: routing is discrete, and a comparison of rounding has to start
    from the same experts); the gates are the reference's own scores of
    them."""
    scores = jax.nn.sigmoid(_mm(x, b["router"]))
    _, own = jax.lax.top_k(scores + b["router_bias"].astype(jnp.float32),
                           top_k)
    idx = own if forced is None else forced
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if variant != "unnormalised_gates":
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return idx, chosen * scaling, own


def experts(b, x, *, top_k, scaling, expert_offset, variant=None,
            forced=None):
    """``(y, the reference's own choice of experts)``."""
    idx, gates, own = route(b, x, top_k=top_k, scaling=scaling,
                            variant=variant, forced=forced)
    if variant == "capacity":
        # Switch-style dropping: of an expert's pairs, in token order, those
        # beyond 1.25 x the mean load lose their gate.
        n_experts = b["router"].shape[1]
        cap = int(1.25 * idx.size / n_experts)
        onehot = jax.nn.one_hot(idx.reshape(-1), n_experts, dtype=jnp.int32)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
        gates = jnp.where(rank.reshape(idx.shape) < cap, gates, 0.0)
    mm = _mm_int8 if variant in ("int8_experts", "int8") else _mm
    held = b["experts"]["gate"].shape[0]

    @jax.checkpoint
    def one(y, ew):
        e, w = ew
        weight = jnp.sum(jnp.where(idx == e + expert_offset, gates, 0.0),
                         axis=-1)
        return y + weight[:, None] * gated_mlp(w, x, mm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (jnp.arange(held), b["experts"]))
    if "shared" in b and variant != "no_shared":
        y = y + gated_mlp(b["shared"], x, _mm_of(variant))
    return y, own


def block(b, x, forced=None, *, kw, variant=None):
    """``(x after the block, the experts it would choose or None)``."""
    eps = kw["eps"]
    x = x + attention(
        b, rms_norm(b["norm1"], x, eps), n_head=kw["n_head"],
        nope=kw["nope"], rope_dim=kw["rope_dim"], v_dim=kw["v_dim"],
        kv_rank=kw["kv_rank"], theta=kw["theta"], eps=eps,
        mm=_mm_of(variant))
    h = rms_norm(b["norm2"], x, eps)
    if "mlp" in b:
        return x + gated_mlp(b["mlp"], h, _mm_of(variant)), None
    y, own = experts(b, h, top_k=kw["top_k"], scaling=kw["scaling"],
                     expert_offset=kw["expert_offset"], variant=variant,
                     forced=forced)
    return x + y, own


def hidden_and_routing(p, tokens, *, kw, variant=None, forced=None):
    """``(x (T, d) before the final norm, [the reference's own choice of
    experts (T, top_k) in each expert layer])`` of one sequence ``tokens``
    (T,); ``forced`` names, layer by layer, the experts to use instead
    (``route``). Each block is a ``jax.checkpoint``: under ``jax.grad`` only
    its input is kept and the block is computed again, which changes no
    value. The blocks are a Python loop, not a scan over stacked weights:
    the stack would be a second copy of the expert blocks (1.8 GB at the
    benchmark's size, and as much again for its gradient) beside the
    system's own state; the price is a program five blocks long (v5e
    compile, PR 28: 154 s and 1.4 GB of temporaries, against 99 s and 6.8 GB
    as a scan). ``kw`` is ``families/deepseek_v3.reference_kwargs``."""
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


def forward(p, tokens, *, kw, variant=None, forced=None):
    """Logits (T, vocabulary rows held) of one sequence."""
    return _logits(p, hidden_and_routing(
        p, tokens, kw=kw, variant=variant, forced=forced)[0], kw, variant)


def _logits(p, x, kw, variant=None):
    return _mm_of(variant)(rms_norm(p["norm_f"], x, kw["eps"]), p["head_w"])


def sequence_loss(p, tokens, labels, *, kw, variant=None, forced=None):
    """``(mean next-token cross-entropy of one sequence, the reference's own
    choices of experts)``."""
    x, chosen = hidden_and_routing(p, tokens, kw=kw, variant=variant,
                                   forced=forced)
    logp = jax.nn.log_softmax(_logits(p, x, kw, variant))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1)
                     ), chosen


def batch_loss(p, x, y, *, kw, variant=None):
    """Mean loss over the sequences of ``x`` (B, T), one at a time."""
    fn = functools.partial(sequence_loss, kw=kw, variant=variant)
    return jnp.mean(jax.lax.map(lambda xy: fn(p, xy[0], xy[1])[0], (x, y)))


def loss_and_grads(p, x, y, *, kw, variant=None, forced=None):
    """``(mean loss, its gradient in ``p``'s names, the reference's own
    choices [(B, T, top_k) a layer])`` over ``x`` (B, T), a sequence at a
    time. ``forced`` ([(T, top_k) a layer]) is for a batch of one sequence.
    A sequence's loss is a checkpoint: the loop's backward pass keeps the
    sequence and computes it again, so one sequence's activations and one
    gradient tree are live. The selection bias only picks indices: its
    gradient is exactly zero."""
    if forced is not None and x.shape[0] != 1:
        raise ValueError("choices can be forced for one sequence at a time")
    fn = jax.checkpoint(functools.partial(
        sequence_loss, kw=kw, variant=variant, forced=forced))

    def mean_loss(p):
        losses, chosen = jax.lax.map(lambda xy: fn(p, xy[0], xy[1]), (x, y))
        return jnp.mean(losses), chosen

    (loss, chosen), grads = jax.value_and_grad(mean_loss, has_aux=True)(p)
    return loss, grads, chosen


def _norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(tree)))


# Groups of the parameters, by the reference's names, in which a gradient is
# compared with another (``grad_differences``).
ATTENTION = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
GROUPS = ("attention", "dense_mlp", "router", "shared", "experts", "other")


def grad_groups(grads):
    """``{group: subtree}`` of a gradient in ``p``'s names: the attention
    layers' matrices, layer 0's MLP, the routers, the shared experts, the
    held experts, and the rest (embedding, head, norms)."""
    blocks = grads["blocks"]
    pick = lambda key: [b[key] for b in blocks if key in b]
    return {
        "attention": [[b[k] for k in ATTENTION] for b in blocks],
        "dense_mlp": pick("mlp"), "router": pick("router"),
        "shared": pick("shared"), "experts": pick("experts"),
        "other": [grads["wte"], grads["norm_f"], grads["head_w"],
                  [(b["norm1"], b["norm2"]) for b in blocks]],
    }


def _worst(a, b, scale, per_row):
    """``|a - scale * b| / |a|``, the largest over the slices of ``a`` along
    its first axis if ``per_row``; 0 where both are zero."""
    a = a.astype(jnp.float32)
    axes = tuple(range(1, a.ndim)) if per_row else None
    num = jnp.sqrt(jnp.sum(jnp.square(a - scale * b.astype(jnp.float32)),
                           axis=axes))
    den = jnp.sqrt(jnp.sum(jnp.square(a), axis=axes))
    return jnp.max(jnp.where(den > 0, num / jnp.where(den > 0, den, 1.0),
                             jnp.where(num > 0, jnp.inf, 0.0)))


def grad_differences(grads, other, scale=1.0):
    """``{group: the largest |g - scale * o| / |g| over the group's leaves}``
    over ``GROUPS``, a leaf at a time and the held experts' stacked matrices
    an expert at a time: one wrong matrix among many reads as itself and is
    not averaged away."""
    mine, theirs = grad_groups(grads), grad_groups(other)
    leaves = jax.tree_util.tree_leaves
    return {name: jnp.max(jnp.stack([
        _worst(a, b, scale, per_row=name == "experts")
        for a, b in zip(leaves(mine[name]), leaves(theirs[name]))]))
        for name in GROUPS}


def _frozen(kw):
    return tuple(sorted(kw.items()))


@functools.partial(jax.jit, static_argnames=("kw",))
def _compare(p, x, y, forced, system_grads, scale, *, kw):
    loss, grads, chosen = loss_and_grads(p, x, y, kw=dict(kw), forced=forced)
    flipped = None if forced is None else [
        jnp.sum(jnp.all(f[:, :, None] != own[0][:, None, :], axis=-1))
        for f, own in zip(forced, chosen)]
    return {"loss": loss, "grad_norm": _norm(grads), "flipped": flipped,
            "grad_differences": grad_differences(grads, system_grads, scale)}


def compare(p, x, y, *, kw, system_grads, scale=1.0, forced=None):
    """The reference on ``x`` (B, T) against the program's first step:
    ``loss`` and ``grad_norm`` of the reference; ``grad_differences``, the
    worst leaf of each group, of the reference's gradient and ``scale *
    system_grads`` (the program's, in ``p``'s names); and, where ``forced``
    gives the program's own choices of experts (one sequence), ``flipped``: in each expert
    layer, how many (token, choice) pairs name an expert the reference,
    held to those choices in the layers before, would not choose for that
    token. One program: no gradient tree leaves the device."""
    return _compare(p, x, y, forced, system_grads, scale, kw=_frozen(kw))


@functools.partial(jax.jit, static_argnames=("kw", "variant"))
def _loss_and_grad_norm(p, x, y, *, kw, variant):
    loss, grads, _ = loss_and_grads(p, x, y, kw=dict(kw), variant=variant)
    return loss, _norm(grads)


def loss_and_grad_norm(p, x, y, *, kw, variant=None):
    """(mean loss, global L2 norm of its gradient over the parameters) over
    ``x`` (B, T), a sequence at a time."""
    return _loss_and_grad_norm(p, x, y, kw=_frozen(kw), variant=variant)
