"""Int8 weight-only quantization for serving and collectives.

The serving decode path is memory-bound: every decode step streams the full
parameter tree through HBM to produce one token per slot, so parameter
bytes ARE the roofline (Pope et al., 2022 — "Efficiently Scaling
Transformer Inference"). Weight-only int8 cuts those bytes 4x vs f32 (2x
vs bf16) without touching the activation math: weights are stored as
per-channel symmetric int8 with float32 scales and dequantized IN-TRACE
right before each matmul, so the compute (and its dtype, under a
``precision.Policy``) is unchanged — the AQT-style weight-only recipe,
applied at the layer seams this framework already has.

Representation — plain dicts, not a custom leaf type. A quantized kernel
``w`` of shape (..., C) becomes::

    {"q": int8 (..., C), "scale": float32 (C,)}   # w ~= q * scale

with ``scale = amax(|w|, all axes but -1) / 127``. Keeping the container a
dict means EVERY existing tree seam works unchanged: ``Checkpointer`` /
``ShardedCheckpointer`` walk dicts (the q + scale trees round-trip
leaf-for-leaf), ``FSDP.params_sharding`` shards ``q`` on its largest
divisible dim (the per-layer all-gathers move int8 — 4x fewer bytes than
f32, 2x fewer than bf16, visible in ``Strategy.comm_bytes_estimate``
because ``_leaf_comm_bytes`` prices int8 leaves at their own 1-byte
dtype), and ``Policy.cast_to_compute`` walks through without touching the
int8 payload. Only leaves with ndim >= 2 quantize (kernels, embedding and
positional tables, attention projections); biases and norm scales stay
f32 — they are a rounding error of the byte count.

Usage — quantize-on-load for serving::

    model = dtpu.Model(...); model.compile(...); model.build(...)
    ckpt.restore_into(model)          # any f32/mixed checkpoint
    dtpu.quant.quantize_model(model)  # int8 weights, placed per strategy
    engine = dtpu.serving.Engine(model, ...)   # or model.generate(...)

Quantized models SERVE (generate / predict / evaluate / serving.Engine);
``fit`` raises — int8 weights carry no gradients, and training belongs to
the f32 masters the checkpoint still holds. The KV cache defaults to the
``Model.decode_dtype()`` policy dtype (f32/bf16); KV values are
data-dependent per step, so the int8 KV cache uses per-row DYNAMIC
scales — ``serving.Engine(kv_dtype="int8")`` stores the pools as the
same ``{"q", "scale"}`` plain-dict leaves used here, quantizing on
scatter and dequantizing in-trace on gather (``nn/attention.py``
``_kv_scatter`` / ``_paged_view``; docs/SERVING.md "int8 KV
cache").

Accuracy contract: dequantized weights differ from the originals by at
most ``scale/2`` per element (symmetric round-to-nearest), and
tests/test_quant.py pins the end effect — bounded logit error and top-1
agreement against the f32 model on the serving LM shapes.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

QKEY = "q"
SKEY = "scale"
_QMAX = 127.0


def is_quantized_leaf(x) -> bool:
    """True for a ``{"q": int8, "scale": f32}`` quantized-weight dict."""
    return (
        isinstance(x, dict)
        and set(x) == {QKEY, SKEY}
        and getattr(x[QKEY], "dtype", None) == jnp.dtype(jnp.int8)
    )


def is_quantized(tree) -> bool:
    """True when any quantized-weight dict appears in ``tree``."""
    found = [False]

    def walk(t):
        if found[0]:
            return
        if is_quantized_leaf(t):
            found[0] = True
        elif isinstance(t, dict):
            for v in t.values():
                walk(v)
        elif isinstance(t, (list, tuple)):
            for v in t:
                walk(v)

    walk(tree)
    return found[0]


def shape_of(w):
    """Logical weight shape, whether ``w`` is a plain array or a quantized
    dict (layers use this where they read ``params["wq"].shape``)."""
    return w[QKEY].shape if is_quantized_leaf(w) else w.shape


def quantize_leaf(w) -> Dict[str, Any]:
    """Per-channel symmetric int8 quantization of one weight: the channel
    axis is the LAST dim (this codebase's universal output-features
    convention — Dense (din, units), conv (kh, kw, cin, filters),
    attention (d, inner), embedding (vocab, d)). All-zero channels get
    scale 1 so the dequant stays finite."""
    w = jnp.asarray(w)
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)),
                   axis=tuple(range(w.ndim - 1)))
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -_QMAX, _QMAX)
    return {QKEY: q.astype(jnp.int8), SKEY: scale}


def dequantize(w, dtype=None):
    """``q * scale`` in f32, cast to ``dtype`` when given (the layer's
    resolved compute dtype under a precision policy). The multiply runs in
    f32 so a bf16 target rounds once, not twice."""
    out = w[QKEY].astype(jnp.float32) * w[SKEY].astype(jnp.float32)
    return out if dtype is None else out.astype(dtype)


def maybe_dequantize(w, dtype=None):
    """Dequantize-in-trace seam for layers: quantized dicts dequantize,
    plain arrays pass through untouched (the caller's own dtype handling
    applies)."""
    return dequantize(w, dtype) if is_quantized_leaf(w) else w


def quantize_tree(tree, *, min_ndim: int = 2):
    """Quantize every floating leaf with ndim >= ``min_ndim`` (default:
    matrices and up — kernels, tables, projections), leaving smaller
    leaves (biases, norm scales) and non-floating leaves untouched.
    Raises on an already-quantized tree: double quantization would
    silently re-round the already-rounded values."""

    def walk(t):
        if is_quantized_leaf(t):
            raise ValueError(
                "tree is already int8-quantized; quantize_tree must run "
                "on full-precision weights (restore the f32 checkpoint "
                "first)"
            )
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            return type(t)(walk(v) for v in t)
        if (
            getattr(t, "ndim", 0) >= min_ndim
            and jnp.issubdtype(jnp.result_type(t), jnp.floating)
        ):
            return quantize_leaf(t)
        return t

    return walk(tree)


def quantize_model(model, *, min_ndim: int = 2):
    """Quantize a built model's parameters in place (weight-only int8) and
    re-place them under its strategy — the quantize-on-load step between
    checkpoint restore and serving. The module's tensor-parallel hints
    still apply (a 'col'-hinted kernel's q + scale subtree shards over the
    model axis; FSDP shards ``q`` by shape as usual, so gathers move int8
    bytes). Cached compiled functions are invalidated; ``fit`` on the
    quantized model raises. Returns the model."""
    if not model.built:
        raise RuntimeError("Build the model (or restore a checkpoint) "
                           "before quantizing")
    if is_quantized(model.params):
        raise ValueError("model is already int8-quantized")
    host = jax.tree_util.tree_map(lambda a: np.asarray(jax.device_get(a)),
                                  model.params)
    qtree = quantize_tree(host, min_ndim=min_ndim)
    model.params = model.strategy.put_params(
        qtree, hints=model.module.sharding_hints()
    )
    # Placements, dtypes and the tree structure changed: every cached
    # compiled step is stale (same invalidation set as load_weights).
    model._train_step = model._eval_step = model._predict_step = None
    model._multi_train_steps = {}
    model._accum_train_steps = {}
    model._decode_dtype = None
    model._generate_fns = {}
    model.opt_state = None  # training state is meaningless for int8 weights
    return model


def abstract_quantize_tree(tree, *, min_ndim: int = 2):
    """Abstract (``jax.ShapeDtypeStruct``) twin of :func:`quantize_tree`:
    the SHAPE of the int8+scales tree a quantize-on-load would produce,
    without any weights. The auto-shard planner's pricing hook for
    quantized-serving footprints — feed the result to
    ``profiler.tree_bytes_per_device`` / :func:`tree_param_bytes` /
    ``Strategy.comm_bytes_estimate`` to cost an int8 deployment from
    shapes alone (int8 leaves price at 1 byte everywhere)."""

    def walk(t):
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            return type(t)(walk(v) for v in t)
        shape = tuple(getattr(t, "shape", ()))
        if (
            len(shape) >= min_ndim
            and jnp.issubdtype(jnp.result_type(t), jnp.floating)
        ):
            return {
                QKEY: jax.ShapeDtypeStruct(shape, jnp.int8),
                SKEY: jax.ShapeDtypeStruct(shape[-1:], jnp.float32),
            }
        return t

    return walk(tree)


def tree_param_bytes(tree) -> int:
    """Global logical byte count of a (possibly quantized) param tree —
    the serving-HBM number to compare across weight formats
    (per-DEVICE resident bytes come from profiler.tree_bytes_per_device).
    Works on live arrays AND abstract ``ShapeDtypeStruct`` leaves (the
    planner's dry-run path)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = int(np.prod(leaf.shape)) if getattr(leaf, "shape", ()) else 1
        total += size * jnp.dtype(jnp.result_type(leaf)).itemsize
    return total


__all__ = [
    "is_quantized",
    "is_quantized_leaf",
    "shape_of",
    "abstract_quantize_tree",
    "quantize_leaf",
    "quantize_tree",
    "quantize_model",
    "dequantize",
    "maybe_dequantize",
    "tree_param_bytes",
]
