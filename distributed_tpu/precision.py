"""Mixed-precision dtype policies (TPU-first: bf16 compute, f32 masters).

The reference trains everything in float32; on TPU the MXU runs bf16
matmuls at ~2x the f32 rate and half the HBM/ICI bytes, so reduced
precision is a first-class *training mode* here, not a per-layer knob.
The recipe is the standard one (Micikevicius et al., 2018 — "Mixed
Precision Training"): parameters and optimizer state stay in float32
("master weights"), each step casts the params once to the compute dtype
for the forward/backward pass, the gradients flow back to f32 through the
cast's VJP, and the optimizer update applies to the f32 masters. Loss and
metric accumulation keep their existing f32 paths. bf16 shares float32's
exponent range so it needs no loss scaling (Kalamkar et al., 2019 —
"A Study of BFLOAT16 for Deep Learning Training"), which makes
``mixed_bfloat16`` the TPU-native default; ``mixed_float16`` (for
f16-only backends) adds dynamic loss scaling (optim.dynamic_loss_scaling).

A :class:`Policy` is three dtypes:

- ``param_dtype``   — storage dtype of params/optimizer state (f32 masters)
- ``compute_dtype`` — dtype of the forward/backward math (the MXU dtype)
- ``output_dtype``  — dtype of model outputs handed to losses/predict

Selected per model via ``model.compile(precision="mixed_bfloat16")`` (or a
``Policy`` instance). Inside a jitted step the model enters the policy's
``scope()`` at trace time, so layers resolve their effective compute dtype
with :func:`resolve_dtype` — an explicit per-layer ``dtype=`` still wins,
and :meth:`Policy.cast_to_compute` skips those layers' param subtrees
(tracked by ``Layer.dtype_hints``) so an f32-pinned layer under a bf16
policy computes from full-precision masters, not round-tripped bf16.

Under ``FSDP``/ZeRO strategies the compute cast is also the comms lever:
casting the param tree to bf16 *before* the sharding-constraint-driven
per-layer all-gathers halves the dominant collective traffic
(``Strategy.constrain_compute_params`` pins the cast copy to the shard
layout so GSPMD gathers compute-dtype bytes; see docs/API.md "Mixed
precision").

Checkpoints always persist the f32 masters, so saving under one policy and
restoring under another round-trips cleanly (mixed<->f32).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

_local = threading.local()


def current_policy() -> Optional["Policy"]:
    """The ambient Policy set by ``Policy.scope()`` (None outside one).
    Model step functions enter the scope at trace time, exactly like
    ``Strategy.scope()``."""
    return getattr(_local, "policy", None)


def resolve_dtype(explicit=None):
    """Effective compute dtype for a layer: an explicit per-layer
    ``dtype=`` always wins; otherwise the ambient policy's compute dtype;
    None when neither is set (the layer computes in its input dtype)."""
    if explicit is not None:
        return explicit
    pol = current_policy()
    return None if pol is None else pol.compute_dtype


class Policy:
    """A mixed-precision dtype policy.

    ``Policy("mixed_bfloat16")`` / ``Policy("float32")`` /
    ``Policy("mixed_float16")`` build the named presets; the explicit form
    ``Policy(param_dtype=..., compute_dtype=..., output_dtype=...)`` builds
    a custom one. ``loss_scaling`` defaults to True only for float16
    compute (bf16 keeps f32's exponent range and needs none); the
    ``initial_loss_scale`` / ``loss_scale_growth_interval`` /
    ``loss_scale_factor`` knobs configure ``optim.dynamic_loss_scaling``.
    """

    def __init__(
        self,
        param_dtype=jnp.float32,
        compute_dtype=jnp.float32,
        output_dtype=jnp.float32,
        *,
        name: Optional[str] = None,
        loss_scaling: Optional[bool] = None,
        initial_loss_scale: float = 2.0 ** 15,
        loss_scale_growth_interval: int = 2000,
        loss_scale_factor: float = 2.0,
    ):
        if isinstance(param_dtype, str) and param_dtype in _PRESETS:
            preset = _PRESETS[param_dtype]
            param_dtype = preset["param"]
            compute_dtype = preset["compute"]
            output_dtype = preset["output"]
            name = name or preset["name"]
        self.param_dtype = jnp.dtype(param_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype)
        self.output_dtype = jnp.dtype(output_dtype)
        self.name = name or (
            f"policy({self.param_dtype.name}/{self.compute_dtype.name}"
            f"/{self.output_dtype.name})"
        )
        if loss_scaling is None:
            loss_scaling = self.compute_dtype == jnp.dtype(jnp.float16)
        self.loss_scaling = bool(loss_scaling)
        self.initial_loss_scale = float(initial_loss_scale)
        self.loss_scale_growth_interval = int(loss_scale_growth_interval)
        self.loss_scale_factor = float(loss_scale_factor)

    # ------------------------------------------------------------- ambient
    @contextlib.contextmanager
    def scope(self):
        prev = current_policy()
        _local.policy = self
        try:
            yield self
        finally:
            _local.policy = prev

    # --------------------------------------------------------------- casts
    @property
    def needs_compute_cast(self) -> bool:
        return self.compute_dtype != self.param_dtype

    @property
    def compute_itemsize(self) -> int:
        """Bytes per element at the compute dtype — the pricing hook the
        auto-shard planner (and Strategy.comm_bytes_estimate callers) use
        to cost activations and compute-dtype collectives without
        materializing anything."""
        return int(self.compute_dtype.itemsize)

    def cast_to_compute(self, tree, dtype_hints: Optional[Dict] = None):
        """The master->compute cast: floating leaves cast to
        ``compute_dtype``, everything else (ints, rng keys) untouched.
        ``dtype_hints`` (``Layer.dtype_hints()``, mirroring the params
        nesting) marks subtrees whose layer carries an explicit ``dtype=``
        — those are left at master precision so the layer's own cast runs
        from the f32 values, keeping per-layer overrides exact."""

        cd = self.compute_dtype

        def walk(t, h):
            if h is not None and not isinstance(h, dict):
                return t  # explicitly-dtyped layer casts its own params
            if isinstance(t, dict):
                hh = h or {}
                return {k: walk(v, hh.get(k)) for k, v in t.items()}
            return _cast_floating(t, cd)

        return walk(tree, dtype_hints)

    def cast_output(self, x):
        """Model-boundary cast of logits/outputs to ``output_dtype``
        (floating outputs only)."""
        if jnp.issubdtype(jnp.result_type(x), jnp.floating):
            return x.astype(self.output_dtype)
        return x

    def cast_params_to_storage(self, tree):
        """Cast floating leaves to ``param_dtype`` (build-time; a no-op for
        the standard f32-master presets)."""
        if self.param_dtype == jnp.dtype(jnp.float32):
            return tree
        return jax.tree_util.tree_map(
            lambda a: _cast_floating(a, self.param_dtype), tree
        )

    def __repr__(self):
        return (
            f"Policy(name={self.name!r}, param={self.param_dtype.name}, "
            f"compute={self.compute_dtype.name}, "
            f"output={self.output_dtype.name}, "
            f"loss_scaling={self.loss_scaling})"
        )


def _cast_floating(a, dtype):
    if jnp.issubdtype(jnp.result_type(a), jnp.floating):
        return a.astype(dtype)
    return a


_PRESETS = {
    "float32": {
        "name": "float32",
        "param": jnp.float32, "compute": jnp.float32, "output": jnp.float32,
    },
    "mixed_bfloat16": {
        "name": "mixed_bfloat16",
        "param": jnp.float32, "compute": jnp.bfloat16, "output": jnp.float32,
    },
    "mixed_float16": {
        "name": "mixed_float16",
        "param": jnp.float32, "compute": jnp.float16, "output": jnp.float32,
    },
}


def get(policy) -> Optional[Policy]:
    """Resolve ``compile(precision=...)``: None passes through (no policy —
    the pre-policy f32 behavior, byte-for-byte), a Policy passes through,
    a preset name ('float32' / 'mixed_bfloat16' / 'mixed_float16')
    builds one."""
    if policy is None or isinstance(policy, Policy):
        return policy
    if isinstance(policy, str):
        if policy in _PRESETS:
            return Policy(policy)
        raise ValueError(
            f"Unknown precision policy {policy!r}; choose from "
            f"{sorted(_PRESETS)} or pass a precision.Policy"
        )
    raise TypeError(
        f"precision must be None, a preset name, or a Policy; got "
        f"{type(policy).__name__}"
    )


# ------------------------------------------------- gradient accumulation --
def grad_accum_init(params):
    """Zero accumulator tree for gradient accumulation: floating leaves get
    FLOAT32 zeros regardless of the param/grad compute dtype (bf16 partial
    sums over M microbatches would lose the low bits the equivalent big
    batch keeps — master-precision accumulation is part of the mixed-
    precision contract), everything else ``zeros_like``. The single
    implementation behind ``Model._accum_train_step_body``."""

    def zeros(p):
        if jnp.issubdtype(jnp.result_type(p), jnp.floating):
            return jnp.zeros(p.shape, jnp.float32)
        return jnp.zeros_like(p)

    return jax.tree_util.tree_map(zeros, params)


def assert_f32_accumulator(acc) -> None:
    """Trace-time guard: every floating leaf of a gradient accumulator must
    be f32 (see grad_accum_init). A non-f32 leaf means a refactor broke
    master-precision accumulation under a reduced-precision policy."""
    for leaf in jax.tree_util.tree_leaves(acc):
        dt = jnp.result_type(leaf)
        if jnp.issubdtype(dt, jnp.floating) and dt != jnp.dtype(jnp.float32):
            raise AssertionError(
                f"gradient accumulator leaf has dtype {dt}, expected "
                "float32 — accumulation must stay at master precision "
                "even when grads arrive in a reduced compute dtype"
            )


def cast_like(tree, ref):
    """Cast each leaf of ``tree`` to the dtype of the matching leaf of
    ``ref`` (e.g. accumulated f32 mean gradients back to the params'
    master dtype before the optimizer update)."""
    return jax.tree_util.tree_map(
        lambda a, r: a.astype(jnp.result_type(r)), tree, ref
    )


__all__ = [
    "Policy",
    "current_policy",
    "resolve_dtype",
    "get",
    "grad_accum_init",
    "assert_f32_accumulator",
    "cast_like",
]
