"""Optimizers: Keras-shaped constructors over optax transforms.

Parity target: ``optimizer_sgd(lr = 0.001)`` / ``tf.keras.optimizers.SGD``
(/root/reference/README.md:71, 301). Optimizer state is an ordinary pytree, so
it replicates/shards with the same ``NamedSharding`` rules as the parameters.

Named constructors build through ``optax.inject_hyperparams``, which lifts
the numeric hyperparameters (learning rate, momentum, ...) into the
optimizer STATE instead of baking them into the jitted update — so
``Model.set_learning_rate`` (and the ``LearningRateScheduler`` /
``ReduceLROnPlateau`` callbacks) can change them between steps without a
recompile, and a checkpointed run resumes with the learning rate it was
actually using. Schedules still work: a callable learning_rate is
re-evaluated against the step count inside the update, as before.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import optax


def SGD(learning_rate: float = 0.001, momentum: float = 0.0, nesterov: bool = False):
    if momentum:
        return optax.inject_hyperparams(optax.sgd)(
            learning_rate, momentum=momentum, nesterov=nesterov
        )
    return optax.inject_hyperparams(optax.sgd)(learning_rate)


def Adam(learning_rate: float = 0.001, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    return optax.inject_hyperparams(optax.adam)(
        learning_rate, b1=b1, b2=b2, eps=eps
    )


def AdamW(learning_rate: float = 0.001, weight_decay: float = 0.01, b1=0.9, b2=0.999):
    return optax.inject_hyperparams(optax.adamw)(
        learning_rate, b1=b1, b2=b2, weight_decay=weight_decay
    )


def fused_adam(learning_rate: float = 0.001, b1: float = 0.9,
               b2: float = 0.999, eps: float = 1e-8):
    """Adam whose whole update — moment EMAs, bias correction, step — runs
    as ONE Pallas kernel pass per same-dtype flat segment of the master
    tree, instead of the stock per-leaf tree walk (ops.fused_update; the
    one-pass form of the update; root PERF.md, PR 24). Numerically
    operation-for-operation identical to ``Adam``; drops into the same
    ``Strategy.init_opt_state``/``constrain_step`` seams (the moment trees
    shard exactly like stock Adam state under ZeRO-1/FSDP), and the
    ``inject_hyperparams`` wrapper keeps the learning rate runtime-mutable
    and checkpointable. CPU backends run the kernel in interpret mode
    (same semantics, no speedup: docs/API.md, Design notes)."""
    from ..ops import fused_update  # lazy: pulls in pallas

    return optax.inject_hyperparams(fused_update.fused_adam)(
        learning_rate, b1=b1, b2=b2, eps=eps
    )


def fused_adamw(learning_rate: float = 0.001, weight_decay: float = 0.01,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8):
    """AdamW spelling of :func:`fused_adam` — the decoupled weight decay
    folds into the same single kernel pass."""
    from ..ops import fused_update  # lazy: pulls in pallas

    return optax.inject_hyperparams(fused_update.fused_adam)(
        learning_rate, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay
    )


def RMSprop(learning_rate: float = 0.001, decay: float = 0.9,
            momentum: float = 0.0, eps: float = 1e-7):
    return optax.inject_hyperparams(optax.rmsprop)(
        learning_rate, decay=decay, momentum=momentum, eps=eps
    )


def Adagrad(learning_rate: float = 0.001, eps: float = 1e-7):
    return optax.inject_hyperparams(optax.adagrad)(learning_rate, eps=eps)


def Lamb(learning_rate: float = 0.001, weight_decay: float = 0.0,
         b1: float = 0.9, b2: float = 0.999):
    """Layer-wise adaptive large-batch optimizer — the standard choice for
    the data-parallel global-batch scaling this framework's mesh enables."""
    return optax.inject_hyperparams(optax.lamb)(
        learning_rate, b1=b1, b2=b2, weight_decay=weight_decay
    )


def _tree_get(opt_state, name: str):
    """optax.tree_utils.tree_get with this module's failure semantics:
    a missing hyperparameter (raw optax transform) and a schedule-driven
    one (tree_get's 'multiple values' — the schedule's wrapped state also
    carries the name, and re-evaluates over whatever we write) both raise
    a KeyError that says what to do instead."""
    import optax.tree_utils as otu

    try:
        value = otu.tree_get(opt_state, name)
    except KeyError as e:
        raise KeyError(
            f"hyperparameter {name!r} is schedule-driven in this optimizer "
            "state — a per-step schedule recomputes it inside the update, "
            "so runtime mutation would be silently overwritten. Mutate the "
            "schedule (recompile) or use a constant hyperparameter."
        ) from e
    if value is None:
        raise KeyError(
            f"optimizer state carries no injectable hyperparameter "
            f"{name!r} — build the optimizer via dtpu.optim names/"
            "constructors (optax.inject_hyperparams) to make it mutable"
        )
    return value


def set_hyperparam(opt_state, name: str, value):
    """Return ``opt_state`` with injected hyperparameter ``name`` replaced
    (e.g. 'learning_rate'), searching through chained/nested states.
    Raises KeyError for raw optax transforms (nothing injected) and for
    schedule-driven hyperparameters (mutation would be a silent no-op)."""
    import jax.numpy as jnp
    import optax.tree_utils as otu

    current = _tree_get(opt_state, name)
    return otu.tree_set(
        opt_state,
        **{name: jnp.asarray(value, getattr(current, "dtype", None))},
    )


def get_hyperparam(opt_state, name: str):
    """Read an injected hyperparameter from ``opt_state`` (see
    ``set_hyperparam``)."""
    return _tree_get(opt_state, name)


class LossScaleState(NamedTuple):
    """State of ``dynamic_loss_scaling``: the live scale (f32 scalar), the
    count of consecutive finite steps since the last scale change, and the
    wrapped transform's state. A NamedTuple pytree, so it shards/replicates
    with the usual NamedSharding rules, checkpoints leaf-for-leaf (the live
    scale survives save/restore), and stays transparent to
    ``optax.tree_utils`` — ``set_hyperparam('learning_rate', ...)`` reaches
    through it into the wrapped optimizer."""

    scale: Any
    growth_count: Any
    inner_state: Any


def dynamic_loss_scaling(
    inner,
    *,
    init_scale: float = 2.0 ** 15,
    growth_interval: int = 2000,
    factor: float = 2.0,
    min_scale: float = 1.0,
):
    """Dynamic-loss-scale wrapper for float16 training (the optax-style
    half of the Micikevicius et al. 2018 recipe; bf16 does not need it).

    The model's step multiplies the loss by ``state.scale`` before
    autodiff, so the incoming gradients here are SCALED. ``update``:

    1. unscales the gradients (divide by the live scale, in f32),
    2. checks every leaf for finiteness,
    3. finite   -> applies the wrapped transform to the unscaled grads and,
       after ``growth_interval`` consecutive finite steps, doubles the
       scale (``factor``),
    4. non-finite -> SKIPS the step: zero updates, the wrapped state is
       kept (not advanced), and the scale is halved (floored at
       ``min_scale``).

    The skip keeps params and optimizer statistics untouched while the
    scale searches back down to the representable range — overflow costs
    one step of progress, never a poisoned Adam moment."""
    inner = get(inner)

    def init_fn(params):
        return LossScaleState(
            jnp.float32(init_scale), jnp.int32(0), inner.init(params)
        )

    def update_fn(grads, state, params=None):
        inv = jnp.float32(1.0) / state.scale
        unscaled = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype)
            if jnp.issubdtype(jnp.result_type(g), jnp.floating) else g,
            grads,
        )
        leaves = jax.tree_util.tree_leaves(unscaled)
        finite = jnp.array(True)
        for leaf in leaves:
            if jnp.issubdtype(jnp.result_type(leaf), jnp.floating):
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(leaf)))
        new_updates, new_inner = inner.update(
            unscaled, state.inner_state, params
        )
        # Elementwise select: on a skipped step the zero update and the
        # retained old inner state win; any NaN/inf in the not-taken
        # branch is discarded by the select, never propagated.
        updates = jax.tree_util.tree_map(
            lambda u: jnp.where(finite, u, jnp.zeros_like(u)), new_updates
        )
        inner_state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o), new_inner,
            state.inner_state,
        )
        grown = state.growth_count + 1
        should_grow = jnp.logical_and(finite, grown >= growth_interval)
        new_scale = jnp.where(
            finite,
            jnp.where(should_grow, state.scale * factor, state.scale),
            jnp.maximum(state.scale / factor, jnp.float32(min_scale)),
        )
        new_count = jnp.where(
            jnp.logical_and(finite, jnp.logical_not(should_grow)),
            grown, jnp.int32(0),
        )
        return updates, LossScaleState(new_scale, new_count, inner_state)

    return optax.GradientTransformation(init_fn, update_fn)


def loss_scale_value(opt_state):
    """The live loss scale of an optimizer state built through
    ``dynamic_loss_scaling`` (the wrapper is always outermost), or None
    when no loss scaling is active. Model step bodies read this to
    multiply the loss before autodiff."""
    if isinstance(opt_state, LossScaleState):
        return opt_state.scale
    return None


class EmaBaseline:
    """Exponential-moving-average reward baseline for policy-gradient
    advantages (``rl.PostTrainer``): ``advantage = reward - baseline``.
    Host-side scalar state, like the learning-rate hyperparams — small
    enough to live outside the jitted step, and it must NOT shard (every
    rollout subtracts the same baseline or the gradient gains a spurious
    per-shard offset). ``state_dict``/``load_state`` round-trip it through
    checkpoint metadata."""

    def __init__(self, decay: float = 0.9):
        if not 0.0 <= decay < 1.0:
            raise ValueError(f"decay must be in [0, 1); got {decay}")
        self.decay = float(decay)
        self.value = None  # None until the first update (cold start)

    def update(self, reward_mean: float) -> float:
        """Fold one iteration's mean reward in; returns the new baseline.
        The first update adopts the observed mean outright (a 0-init
        baseline would hand the whole first batch a large spurious
        advantage)."""
        r = float(reward_mean)
        if self.value is None:
            self.value = r
        else:
            self.value = self.decay * self.value + (1.0 - self.decay) * r
        return self.value

    def state_dict(self):
        return {"decay": self.decay, "value": self.value}

    def load_state(self, state):
        self.decay = float(state["decay"])
        self.value = None if state["value"] is None else float(state["value"])


class AdaptiveKLCoef:
    """PPO-style adaptive KL-penalty coefficient (Schulman et al., 2017):
    after each policy update, grow the coefficient when the observed
    policy-vs-reference KL overshoots ``target`` and shrink it when the
    policy is moving too timidly. ``rl.PostTrainer`` accepts an instance
    anywhere a fixed ``kl_coef`` float goes and calls ``update`` with the
    measured post-update KL each iteration."""

    def __init__(self, init_coef: float = 0.1, target: float = 0.01,
                 factor: float = 1.5, tolerance: float = 1.5):
        if init_coef < 0 or target <= 0 or factor <= 1 or tolerance < 1:
            raise ValueError(
                "need init_coef >= 0, target > 0, factor > 1, "
                f"tolerance >= 1; got {init_coef}, {target}, {factor}, "
                f"{tolerance}"
            )
        self.coef = float(init_coef)
        self.target = float(target)
        self.factor = float(factor)
        self.tolerance = float(tolerance)

    def update(self, observed_kl: float) -> float:
        """Adapt to one iteration's measured KL; returns the new coef."""
        kl = float(observed_kl)
        if kl > self.target * self.tolerance:
            self.coef *= self.factor
        elif kl < self.target / self.tolerance:
            self.coef /= self.factor
        return self.coef

    def state_dict(self):
        return {"coef": self.coef, "target": self.target,
                "factor": self.factor, "tolerance": self.tolerance}

    def load_state(self, state):
        self.coef = float(state["coef"])
        self.target = float(state["target"])
        self.factor = float(state["factor"])
        self.tolerance = float(state["tolerance"])


def sgd_with_cosine(learning_rate: float, steps: int, warmup: int = 0, momentum: float = 0.9):
    return optax.sgd(cosine_schedule(learning_rate, steps, warmup),
                     momentum=momentum)


def cosine_schedule(learning_rate: float, steps: int, warmup: int = 0):
    """Warmup-then-cosine decay schedule; pass as any optimizer's
    learning_rate (optax schedules are plain callables)."""
    return optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, max(warmup, 1), max(steps, warmup + 1)
    )


def exponential_schedule(learning_rate: float, decay_rate: float,
                         decay_steps: int, warmup: int = 0):
    if warmup:
        return optax.warmup_exponential_decay_schedule(
            0.0, learning_rate, warmup, decay_steps, decay_rate
        )
    return optax.exponential_decay(learning_rate, decay_steps, decay_rate)


_REGISTRY = {
    "sgd": SGD,
    "adam": Adam,
    "adamw": AdamW,
    "fused_adam": fused_adam,
    "fused_adamw": fused_adamw,
    "rmsprop": RMSprop,
    "adagrad": Adagrad,
    "lamb": Lamb,
}


def get(name_or_tx, **kwargs):
    """Resolve 'sgd'/'adam'/'adamw' by name, or pass an optax transform through.

    A (init_fn, update_fn) sequence is rebuilt into a GradientTransformation:
    ``GradientTransformation`` is a NamedTuple, and language bridges flatten
    NamedTuples to plain lists (reticulate converts Python tuples to R lists,
    so an optimizer built in R via ``dtpu()$optim$get(...)`` comes back as a
    list of its two functions — caught by tests/test_reticulate_semantics.py).
    """
    if isinstance(name_or_tx, str):
        try:
            return _REGISTRY[name_or_tx.lower()](**kwargs)
        except KeyError:
            raise ValueError(f"Unknown optimizer {name_or_tx!r}") from None
    if (
        isinstance(name_or_tx, (list, tuple))
        and not isinstance(name_or_tx, optax.GradientTransformation)
        and len(name_or_tx) == 2
        and all(callable(f) for f in name_or_tx)
    ):
        return optax.GradientTransformation(*name_or_tx)
    return name_or_tx
