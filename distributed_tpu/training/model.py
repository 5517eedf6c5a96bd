"""Keras-shaped ``Model``: compile / fit / evaluate / predict.

Parity targets (what migrating users keep):
- ``model.compile(loss=..., optimizer=..., metrics=['accuracy'])``
  (/root/reference/README.md:300-302, 70-73).
- ``model.fit(x, y, batch_size, epochs, steps_per_epoch)`` returning a
  History (/root/reference/README.md:304, 392, 153); ``batch_size`` is the
  *global* batch, exactly like the reference's ``64 * num_workers``
  (/root/reference/README.md:124-125, 366-367).
- Built under ``strategy.scope()`` -> distributed; built bare -> local
  (scope-wraps-construction, /root/reference/README.md:134, 375).

TPU-first internals (what changed under the hood):
- One jitted train step: forward + backward + optimizer update + metrics in a
  single XLA program; buffers donated so params update in place in HBM.
- Under DataParallel the batch arrives sharded on the mesh's 'data' axis and
  params replicated; XLA emits one fused gradient all-reduce per step over
  ICI — the compiled equivalent of the reference's observed "Collective
  batch_all_reduce: 6 all-reduces" (/root/reference/README.md:403).
- Per-epoch metric aggregation happens on device as (sum, count) pairs; only
  epoch boundaries synchronize to host (no per-step device->host stalls).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import optim
from .. import precision as precision_lib
from ..nn.core import (
    Layer,
    child_scope as _child_scope,
    apply_layers as _apply_layers,
    eval_sample_weights as _eval_sample_weights,
)
from ..nn import moe as moe_lib
from ..nn import attention as attention_lib
from ..ops import losses as losses_lib
from ..ops import metrics as metrics_lib
from ..parallel.strategy import SingleDevice, Strategy, current_strategy
from ..launch.core import heartbeat as _gang_heartbeat
from ..obs import flight as obs_flight
from ..obs import registry as obs_registry
from ..obs import spans as obs_spans
from ..utils import event_schema as evs
from ..utils import events as events_lib
from ..utils import logging as dlog
from ..utils.tree import tree_size
from .progress import ProgressLine
from .history import History


def _split_head(module):
    """(body_layers, head_layer) of a Sequential — the head is the final
    layer, which the chunked-loss path applies per token chunk."""
    layers = getattr(module, "layers", None)
    if not layers or len(layers) < 2:
        raise ValueError(
            "head_chunks needs a Sequential module with >= 2 layers "
            "(body + a tokenwise head as the LAST layer); got "
            f"{type(module).__name__}"
        )
    return layers[:-1], layers[-1]


def _constrain_step_outputs(params, opt_state):
    """Apply the ambient strategy's trace-time output constraints to a train
    step's updated (params, opt_state). ZeRO strategies pin their mixed
    placements here (replicated params next to data-sharded optimizer
    state) so GSPMD propagation cannot drift the layout between steps; for
    everything else this is the identity."""
    strat = current_strategy()
    if strat is None:
        return params, opt_state
    return strat.constrain_step(params, opt_state)


def _cast_for_compute(policy, params, dtype_hints):
    """Master->compute param cast for one forward/backward pass under a
    mixed-precision policy (identity without one, or when compute ==
    param dtype). The cast happens IN-TRACE on the f32 masters, so
    gradients flow back to f32 through the cast's VJP; ``dtype_hints``
    exempts explicitly-dtyped layers (they cast their own params, keeping
    per-layer ``dtype=`` overrides exact); and the ambient strategy may
    pin the cast copy to its shard layout (``constrain_compute_params``)
    so FSDP-family all-gathers move compute-dtype bytes."""
    if policy is None or not policy.needs_compute_cast:
        return params
    with jax.named_scope("cast"):
        cast = policy.cast_to_compute(params, dtype_hints)
        strat = current_strategy()
        if strat is not None:
            cast = strat.constrain_compute_params(cast)
    return cast


def _aux_loss_sum(state):
    """Sum of all leaves named 'aux_loss' anywhere in a state tree."""
    total = 0.0
    leaves = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves:
        if path and getattr(path[-1], "key", None) == "aux_loss":
            total = total + leaf
    return total


def _index_stream(
    n: int, batch: int, shuffle: bool, seed: Optional[int], start_step: int = 0
):
    """Yield index blocks forever; reshuffles each pass (Keras semantics:
    with steps_per_epoch the cursor carries across epochs).

    Each pass's permutation depends only on (seed, pass index), so a resumed
    run (``start_step`` = restored ``model.step``) fast-forwards to the exact
    batch the interrupted run would have consumed next — this is what makes
    checkpoint-resume match an uninterrupted run batch-for-batch."""
    base = 0 if seed is None else seed
    per_pass = max((n - batch) // batch + 1, 1)
    pass_idx, within = divmod(start_step, per_pass)
    while True:
        rng = np.random.default_rng((base, pass_idx))
        order = rng.permutation(n) if shuffle else np.arange(n)
        starts = range(0, n - batch + 1, batch)
        for start in list(starts)[within:]:
            yield order[start : start + batch]
        within = 0
        pass_idx += 1


def _per_host_source(source) -> bool:
    """True when a batch source emits only THIS process's rows of each
    global batch — specifically a (process_index, process_count) ``shard``
    tuple, the shape data.Pipeline(shard=...) sets. NOT any ``shard``
    attribute: a tf.data-style .shard() METHOD must not trigger per-host
    placement. One definition shared by fit/evaluate/predict so the three
    entry points cannot disagree about what counts as a sharded source.

    A sharded source whose shard count disagrees with the live world size
    raises here, on all three entry points: the slices could never
    assemble into a whole global batch, and the canonical way to hit this
    is a pipeline held across an elastic gang resize."""
    shard = getattr(source, "shard", None)
    if not isinstance(shard, tuple):
        return False
    count = int(shard[1])
    if count != jax.process_count():
        raise ValueError(
            f"per-host-sharded data source splits each global batch "
            f"{count} ways but this runtime has {jax.process_count()} "
            "process(es), so the shards cannot assemble into a whole "
            "batch (each process would feed the wrong fraction). After an "
            "elastic gang resize, rebuild the pipeline from the current "
            "cluster spec, call pipeline.reshard('auto'), or construct "
            "it with shard='auto'."
        )
    return True


class Model:
    """A trainable wrapper around a ``Layer`` (usually a ``Sequential``)."""

    # Bound on retained generate() compilations (LRU evicted beyond this).
    _GENERATE_CACHE_MAX = 16

    def __init__(self, module: Layer, name: Optional[str] = None):
        if module.name is None:
            module.name = module.default_name()
        self.module = module
        self.name = name or "model"
        # Scope-wraps-construction: capture the ambient strategy now.
        self.strategy: Strategy = current_strategy() or SingleDevice()
        self.params = None
        self.state = None
        self.opt_state = None
        self.built = False
        self.compiled = False
        self.input_shape: Optional[Tuple[int, ...]] = None
        self.step = 0  # global optimizer step (checkpoint/resume cursor)
        self.head_chunks = None  # compile(head_chunks=C): chunked head-loss
        self.steps_per_execution = None  # compile(steps_per_execution=K)
        self.precision = None  # compile(precision=...): dtype Policy
        self._dtype_hints = {}  # per-layer dtype= overrides, set by build()
        self.stop_training = False  # callbacks (EarlyStopping) set this
        self._resumed_step = None  # set by a restoring ModelCheckpoint
        self._stall_timer = None  # live StepTimer of the fit in progress
        self.last_fit_telemetry = None  # stall_report() of the last fit
        self.last_plan = None  # auto_shard.Plan of compile(strategy="auto")
        self._auto_shard = None  # planner config, set by compile()
        self._auto_grad_accum = None  # planner-chosen fit(grad_accum=) default
        self._param_hints = {}  # TP role tree, populated by build()
        self._seed = 0
        self._train_step = None
        self._multi_train_steps = {}  # accum_m -> fused K-step dispatch
        self._accum_train_steps = {}  # grad_accum M -> jitted accum step
        self._eval_step = None
        self._predict_step = None
        self._generate_fns = {}  # (shapes, sampling config) -> jitted scan (LRU)
        self._decode_dtype = None  # cache dtype, memoized per build

    # ------------------------------------------------------------------ build
    def build(self, input_shape: Sequence[int], seed: int = 0):
        """Materialize params/state for an unbatched input shape, placed
        according to the strategy (replicated under DP). The ``build`` span
        and its children (``plan``, ``init``, ``place``, ``opt_state``)
        block on nothing the code did not: placement is asynchronous, so
        ``build/place`` can end with transfers still in flight
        (docs/OBSERVABILITY.md)."""
        with obs_spans.span("build"):
            self.input_shape = tuple(int(d) for d in input_shape)
            self._seed = seed
            if self._auto_shard is not None and self.compiled:
                # compile(strategy="auto"): pick the strategy/precision/K
                # BEFORE materializing — the planner prices candidates from
                # abstract shapes, so the 3x-params optimizer tree is never
                # built under a layout that would then be thrown away.
                with obs_spans.span("plan"):
                    self._commit_auto_plan()
            key = jax.random.PRNGKey(seed)
            with obs_spans.span("init"):
                params, state, _ = self.module.init(key, self.input_shape)
            # Tensor-parallel role tree (empty for unhinted models);
            # strategies without a model axis ignore it.
            self._param_hints = self.module.sharding_hints()
            # Per-layer explicit dtype= overrides: Policy.cast_to_compute
            # skips these subtrees so the layer's own cast wins over the
            # policy.
            self._dtype_hints = self.module.dtype_hints()
            if self.precision is not None:
                # Master-weight storage dtype (f32 for every mixed_* preset,
                # so this is a no-op there; a custom all-low-precision policy
                # casts here, at build).
                params = self.precision.cast_params_to_storage(params)
            with obs_spans.span("place"):
                self.params = self.strategy.put_params(
                    params, hints=self._param_hints)
                self.state = self.strategy.put_params(state)
            if self.compiled:
                with obs_spans.span("opt_state"):
                    self.opt_state = self.strategy.init_opt_state(
                        self.tx, self.params)
            self.built = True
            self._decode_dtype = None  # re-derived on next generate()
            self._generate_fns = {}
        return self

    def compile(
        self,
        optimizer="sgd",
        loss="sparse_categorical_crossentropy",
        metrics: Iterable = ("accuracy",),
        grad_clip: Optional[float] = None,
        gradient_accumulation_steps: Optional[int] = None,
        head_chunks: Optional[int] = None,
        steps_per_execution: Optional[int] = None,
        precision=None,
        strategy=None,
        hbm_cap_bytes: Optional[int] = None,
        measure: bool = False,
        auto_options: Optional[dict] = None,
        **optimizer_kwargs,
    ):
        """``strategy``: override the construction-scope strategy. A
        ``Strategy`` instance replaces it directly (live params are
        re-placed). The string ``"auto"`` hands the choice to the
        auto-shard planner (``parallel.auto_shard.plan_sharding``): at
        build time it enumerates strategy x precision x grad_accum x
        steps_per_execution candidates over the live topology, prices
        per-device state bytes (via ``jax.eval_shape`` — no tree is
        materialized per candidate) and per-step collective traffic
        (``Strategy.comm_bytes_estimate``), prunes configs that exceed
        ``hbm_cap_bytes`` (the ``Feasibility`` predicate), ranks the rest
        by a compute+comm+dispatch cost model, and commits the winner —
        including its precision policy, ``steps_per_execution``, and a
        default ``fit(grad_accum=...)``. Dimensions you set explicitly
        (``precision=...``, ``steps_per_execution=...``) are PINNED, not
        searched. ``measure=True`` times the top-k shortlist with short
        real dispatches before committing (materializes params per
        shortlisted candidate — the estimate-only default does not).
        ``auto_options`` passes planner knobs through (``batch_size``,
        ``devices``, ``grad_accums``, ``precisions``, ``include_tp``,
        ``include_pp``, ``top_k``). The decision record lands in ``model.last_plan``,
        ``model.last_fit_telemetry["plan"]``, and the JSONL event log
        (``auto_shard_plan``); see docs/API.md "Autotuned sharding".

        ``head_chunks=C``: fused chunked head-loss for token models.
        The module's FINAL layer (the vocab head) and the loss are applied
        over C chunks of the flattened token axis inside a rematerialized
        ``lax.scan`` — the full (tokens, vocab) logits tensor never
        materializes, in forward OR backward. This is the standard
        long-context memory lever for big-vocab LMs: at T=65k, V=32k the
        logits alone are 4.3 GB in bf16 (plus the same again for their
        cotangent), which is exactly what a 16 GB chip cannot afford next
        to params and activations. Costs one extra head forward per step
        (the scan recompute). Requires a Sequential whose last layer is a
        stateless tokenwise map ((..., D) -> (..., V), e.g. Dense) and
        metrics with the standard (sum, count) protocol. predict() still
        materializes full logits — slice or chunk calls at extreme T.

        ``grad_clip``: global-norm gradient clipping applied before the
        optimizer update (optax.clip_by_global_norm); the norm reduction
        happens inside the jitted step, so under data parallelism it clips
        the *global* (all-reduced) gradient, not per-replica shards.

        ``gradient_accumulation_steps=N``: accumulate gradients over N
        ``fit`` steps and apply the (mean-gradient) optimizer update on
        every N-th (optax.MultiSteps) — trains with an effective global
        batch of N x batch_size without the activation memory. Clipping
        composes on the ACCUMULATED gradient (the clip transform sits
        inside the MultiSteps wrapper). ``model.step`` still advances per
        micro-step and checkpoints resume mid-accumulation exactly (the
        accumulator rides in the optimizer state) — but LEARNING-RATE
        SCHEDULES advance once per optimizer update, i.e. once per N fit
        steps: size a schedule in UPDATES (total_fit_steps / N), not fit
        steps.

        ``steps_per_execution=K``: fuse K optimizer steps into ONE jitted
        dispatch. ``fit`` stacks K host batches into a ``[K, batch, ...]``
        super-batch, transfers it once, and runs a single ``lax.scan``
        over the K slices with params/state/opt_state donated across the
        whole dispatch; loss and metric (sum, count) accumulators stay on
        device inside the scan. This amortizes per-step host overhead
        (dispatch, placement, the per-step Python bookkeeping) over K
        steps — the Keras ``steps_per_execution`` lever, and the cure for
        host-bound small-model training (docs/API.md "Multi-step
        execution"). Numerics match K=1 to float tolerance (same batch
        order, same per-step RNG fold). Callbacks, the progress line, and
        ``model.step`` advance at K-step granularity; validation is
        unaffected (evaluate already syncs once per call). Composes with
        ``head_chunks`` and ``gradient_accumulation_steps``.

        ``precision``: a mixed-precision dtype policy — ``"float32"``
        (explicit f32 policy), ``"mixed_bfloat16"`` (bf16 compute, f32
        master weights — the TPU-native mode: ~2x MXU rate, half the
        activation/collective bytes, no loss scaling needed),
        ``"mixed_float16"`` (f16 compute + dynamic loss scaling, for
        f16-only backends), or a ``precision.Policy``. Params and
        optimizer state stay f32 (master weights) under the mixed
        presets: every jitted step casts the params once to the compute
        dtype for the forward/backward pass, gradients come back f32
        through the cast's VJP, and the update applies to the masters —
        so checkpoints always persist f32 and a policy change between
        save and restore round-trips cleanly. Loss/metric accumulation
        keeps its existing f32 paths; per-layer ``dtype=`` still
        overrides the policy for that layer. Under ``FSDP`` /
        ``ZeroDataParallel`` the compute cast happens before the
        sharding-constraint-driven all-gathers, halving the per-layer
        param-gather traffic under bf16 (docs/API.md "Mixed
        precision"). ``None`` (default) disables the policy machinery
        entirely — the pre-policy f32 behavior, byte-for-byte."""
        with obs_spans.span("compile"):
            if strategy is None:
                # A plain recompile keeps the current strategy but drops any
                # pending auto plan (and its fit-default grad_accum): the new
                # optimizer/loss configuration invalidates the old decision.
                self._auto_shard = None
                self._auto_grad_accum = None
                self.last_plan = None
            elif isinstance(strategy, str) and strategy == "auto":
                self._auto_shard = {
                    "hbm_cap_bytes": hbm_cap_bytes,
                    "measure": bool(measure),
                    "pinned_precision": precision is not None,
                    "pinned_k": steps_per_execution is not None,
                    **(dict(auto_options) if auto_options else {}),
                }
                self.last_plan = None
                self._auto_grad_accum = None
            elif isinstance(strategy, Strategy):
                self._auto_shard = None
                self._auto_grad_accum = None
                self.last_plan = None
                self.strategy = strategy
                if self.built:
                    # Re-place live params/state under the new strategy (the
                    # opt state re-inits below, like every recompile).
                    self.params = strategy.put_params(
                        self.params, hints=self._param_hints
                    )
                    self.state = strategy.put_params(self.state)
            else:
                raise ValueError(
                    "strategy must be None, the string 'auto', or a "
                    f"parallel.Strategy instance; got {strategy!r}"
                )
            self.precision = precision_lib.get(precision)
            self.tx = optim.get(optimizer, **optimizer_kwargs)
            if grad_clip is not None:
                if grad_clip <= 0:
                    raise ValueError(f"grad_clip must be > 0, got {grad_clip}")
                self.tx = optax.chain(
                    optax.clip_by_global_norm(float(grad_clip)), self.tx
                )
            if gradient_accumulation_steps is not None:
                n = gradient_accumulation_steps
                if not isinstance(n, (int, np.integer)) or n < 1:
                    raise ValueError(
                        "gradient_accumulation_steps must be an integer >= 1, "
                        f"got {gradient_accumulation_steps!r}"
                    )
                if n > 1:
                    self.tx = optax.MultiSteps(self.tx, every_k_schedule=int(n))
            if self.precision is not None and self.precision.loss_scaling:
                # Outermost wrapper: the step body reads opt_state.scale to
                # multiply the loss before autodiff, and the wrapper unscales
                # + finite-checks the gradients before anything else (clip,
                # accumulation, the optimizer) sees them.
                self.tx = optim.dynamic_loss_scaling(
                    self.tx,
                    init_scale=self.precision.initial_loss_scale,
                    growth_interval=self.precision.loss_scale_growth_interval,
                    factor=self.precision.loss_scale_factor,
                )
            self.loss_fn = losses_lib.get(loss)
            self.metric_fns = [(metrics_lib.name_of(m), metrics_lib.get(m)) for m in metrics]
            if head_chunks is not None:
                if not isinstance(head_chunks, (int, np.integer)) or head_chunks < 1:
                    raise ValueError(
                        f"head_chunks must be an integer >= 1, got {head_chunks!r}"
                    )
                _split_head(self.module)  # fail fast on unsuitable modules
            self.head_chunks = int(head_chunks) if head_chunks else None
            if steps_per_execution is not None:
                if (
                    not isinstance(steps_per_execution, (int, np.integer))
                    or steps_per_execution < 1
                ):
                    raise ValueError(
                        "steps_per_execution must be an integer >= 1, got "
                        f"{steps_per_execution!r}"
                    )
            self.steps_per_execution = (
                int(steps_per_execution) if steps_per_execution else None
            )
            self.compiled = True
            # Every cached compiled function depends on the (loss, metrics,
            # optimizer, precision) configuration set here — including predict
            # and the generate scans, whose compute dtype follows the policy.
            self._train_step = self._eval_step = self._predict_step = None
            self._multi_train_steps = {}
            self._accum_train_steps = {}
            self._decode_dtype = None
            self._generate_fns = {}
            if self.built:
                if self._auto_shard is not None:
                    # Already built: plan now (input shape is known) and
                    # re-place the live tree under the winner.
                    self._commit_auto_plan(replace_live=True)
                self.opt_state = self.strategy.init_opt_state(self.tx, self.params)
        return self

    # -------------------------------------------------------- auto sharding
    def _commit_auto_plan(self, replace_live: bool = False):
        """Run the auto-shard planner (``compile(strategy="auto")``) and
        commit its winner: strategy, precision policy,
        ``steps_per_execution``, and the default ``fit(grad_accum=...)``.
        The Plan is kept on ``self.last_plan``, summarized into
        ``last_fit_telemetry["plan"]`` at fit end, and emitted to the
        JSONL event log as ``auto_shard_plan``. ``replace_live=True``
        re-places already-materialized params/state under the winner (the
        compile-after-build path)."""
        from ..parallel import auto_shard as auto_lib
        from ..utils import events as events_lib

        cfg = dict(self._auto_shard)
        measure = cfg.pop("measure", False)
        hbm_cap = cfg.pop("hbm_cap_bytes", None)
        pinned_precision = cfg.pop("pinned_precision", False)
        pinned_k = cfg.pop("pinned_k", False)
        if pinned_precision and "precisions" not in cfg:
            cfg["precisions"] = (
                self.precision.name if self.precision is not None else None,
            )
        if pinned_k and "steps_per_execution" not in cfg:
            cfg["steps_per_execution"] = (self.steps_per_execution or 1,)
        devices = cfg.get("devices")
        measure_fn = self._measure_candidate if measure else None
        plan = auto_lib.plan_sharding(
            self.module, self.input_shape, tx=self.tx,
            hbm_cap_bytes=hbm_cap, measure=measure, measure_fn=measure_fn,
            seed=self._seed, **cfg,
        )
        chosen = plan.chosen_candidate()
        self.strategy = chosen.build_strategy(devices)
        if not pinned_k:
            self.steps_per_execution = (
                chosen.steps_per_execution
                if chosen.steps_per_execution > 1 else None
            )
        current = self.precision.name if self.precision is not None else None
        if chosen.precision != current:
            # Only reachable when precision was NOT pinned at compile, so
            # the tx cannot already carry a loss-scaling wrapper.
            self.precision = precision_lib.get(chosen.precision)
            if self.precision is not None and self.precision.loss_scaling:
                self.tx = optim.dynamic_loss_scaling(
                    self.tx,
                    init_scale=self.precision.initial_loss_scale,
                    growth_interval=(
                        self.precision.loss_scale_growth_interval
                    ),
                    factor=self.precision.loss_scale_factor,
                )
        self._auto_grad_accum = (
            chosen.grad_accum if chosen.grad_accum > 1 else None
        )
        self.last_plan = plan
        # Strategy/precision changed under every cached compiled step.
        self._train_step = self._eval_step = self._predict_step = None
        self._multi_train_steps = {}
        self._accum_train_steps = {}
        self._decode_dtype = None
        self._generate_fns = {}
        if replace_live:
            self.params = self.strategy.put_params(
                self.params, hints=self._param_hints
            )
            self.state = self.strategy.put_params(self.state)
        summary = plan.summary()
        events_lib.emit(evs.AUTO_SHARD_PLAN, **summary)
        if jax.process_index() == 0:
            dlog.event("auto_shard_plan", **summary)
            dlog.info(
                f"auto-shard: chose {plan.chosen['label']} "
                f"(est {plan.chosen['est_step_seconds']:.4f}s/step, "
                f"{plan.chosen['state_bytes_per_device']} state B/dev; "
                f"{len(plan.candidates)} feasible, {len(plan.pruned)} "
                f"pruned, tie_break={plan.tie_break})"
            )
        return plan

    def _measure_candidate(self, cand, ctx, steps: int = 3):
        """Time one shortlisted candidate with short REAL dispatches:
        materialize params/opt under its strategy, run the actual jitted
        train-step body on a synthetic batch (input dtype/label shape from
        the planner's abstract forward probe), and return seconds per
        step (first dispatch — the compile — excluded). Returns None when
        the candidate can't be timed (e.g. a loss that rejects the
        synthetic labels); the planner then falls back to its estimate
        order for that row."""
        strat = cand.build_strategy(ctx["devices"])
        prev_strategy, prev_precision = self.strategy, self.precision
        try:
            self.strategy = strat
            self.precision = precision_lib.get(cand.precision)
            key = jax.random.PRNGKey(self._seed)
            params, state, _ = self.module.init(key, self.input_shape)
            hints = self.module.sharding_hints()
            params = strat.put_params(params, hints=hints)
            state = strat.put_params(state)
            opt = strat.init_opt_state(self.tx, params)
            b = ctx["batch_size"]
            x = np.zeros((b,) + self.input_shape,
                         np.dtype(jnp.dtype(ctx["x_dtype"]).name))
            y = np.zeros(ctx["logits_shape"][:-1], np.int32)
            batch = strat.put_batch({"x": x, "y": y})
            step = jax.jit(self._train_step_body(), donate_argnums=(0, 1, 2))
            policy = self.precision

            def run(*args):
                with strat.scope():
                    if policy is None:
                        return step(*args)
                    with policy.scope():
                        return step(*args)

            rng = jax.random.PRNGKey(0)
            params, state, opt, loss, _ = run(
                params, state, opt, batch["x"], batch["y"], rng
            )
            np.asarray(jax.device_get(loss))  # compile + warm, excluded
            t0 = time.perf_counter()
            for _ in range(max(1, steps)):
                params, state, opt, loss, _ = run(
                    params, state, opt, batch["x"], batch["y"], rng
                )
            np.asarray(jax.device_get(loss))
            return (time.perf_counter() - t0) / max(1, steps)
        except Exception as e:
            dlog.warning(
                f"auto-shard: could not measure {cand.label()}: {e}"
            )
            return None
        finally:
            self.strategy, self.precision = prev_strategy, prev_precision

    @property
    def num_params(self) -> int:
        if not self.built:
            raise ValueError("Model not built")
        return tree_size(self.params)

    # -------------------------------------------------------- learning rate
    def set_learning_rate(self, lr: float):
        """Change the learning rate of the CURRENT optimizer state without
        recompiling (named optimizers carry their hyperparameters in the
        state via optax.inject_hyperparams). Raises for raw optax
        transforms that weren't built injectable."""
        if self.opt_state is None:
            raise RuntimeError("compile() and build() the model first")
        self.opt_state = optim.set_hyperparam(
            self.opt_state, "learning_rate", lr
        )
        return self

    def get_learning_rate(self) -> float:
        if self.opt_state is None:
            raise RuntimeError("compile() and build() the model first")
        return float(
            np.asarray(
                jax.device_get(
                    optim.get_hyperparam(self.opt_state, "learning_rate")
                )
            )
        )

    # ------------------------------------------------------------- train step
    def _get_train_step(self):
        if self._train_step is not None:
            return self._train_step
        self._train_step = self._scoped(
            jax.jit(self._train_step_body(), donate_argnums=(0, 1, 2))
        )
        return self._train_step

    def lower_train_step(self, x, y):
        """AOT-lower the single-step train program for one host batch
        ``(x, y)`` under this model's strategy and precision scopes — the
        body ``fit`` jits, on the batch placement ``fit`` uses. The
        result's ``.as_text()`` is the StableHLO the step asks for (each
        Pallas kernel is a ``tpu_custom_call`` carrying its ``kernel_name``
        and per-shard operand shapes); ``.compile()`` is served from the
        persistent compile cache once ``fit`` has compiled the same step."""
        batch = self.strategy.put_batch(
            {"x": np.asarray(x), "y": np.asarray(y)}
        )
        jitted = jax.jit(self._train_step_body(), donate_argnums=(0, 1, 2))
        return self._scoped(jitted.lower)(
            self.params, self.state, self.opt_state, batch["x"], batch["y"],
            self._step_rng(),
        )

    def _train_step_body(self):
        """The uncompiled single-step train body (plain or chunked-head):
        ``(params, state, opt_state, x, y, rng) -> (params, state,
        opt_state, loss, {metric: value})``. ``_get_train_step`` jits it
        directly (the K=1 path, unchanged); ``_get_multi_step_train_step``
        scans it K times inside one jit."""
        grad_eval = self._grad_eval_body()
        tx = self.tx

        # Device scopes of a step (``op_name`` metadata only, no instruction
        # changes; docs/OBSERVABILITY.md): ``cast``, ``loss``, ``metrics``
        # and ``optimizer`` in this file, the parameter path of each layer
        # from ``nn.core.child_scope``; forward and backward are JAX's own
        # ``jvp(...)`` and ``transpose(jvp(...))`` wrappers. Opened inline,
        # never through a helper that calls on: see ``child_scope``.
        def step(params, state, opt_state, x, y, rng):
            # Under mixed_float16 the live loss scale rides in the
            # (outermost) optimizer state; the loss is scaled before
            # autodiff and the tx wrapper unscales/finite-checks.
            scale = optim.loss_scale_value(opt_state)
            loss, new_state, grads, mvals = grad_eval(
                params, state, x, y, rng, scale
            )
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                new_params, new_opt = _constrain_step_outputs(
                    new_params, new_opt
                )
            return new_params, new_state, new_opt, loss, mvals

        return step

    def _grad_eval_body(self):
        """The forward+backward half of a train step — ``(params, state, x,
        y, rng) -> (loss, new_state, grads, mvals)`` — shared by the
        one-shot step (gradient straight into the optimizer) and the
        ``fit(grad_accum=M)`` scan (gradients accumulated across M
        microbatches before ONE update). Plain or chunked-head."""
        if self.head_chunks and self.head_chunks > 1:
            return self._chunked_grad_eval_body()
        module, loss_fn = self.module, self.loss_fn
        metric_fns = tuple(self.metric_fns)
        policy, dtype_hints = self.precision, self._dtype_hints

        def grad_eval(params, state, x, y, rng, scale=None):
            def loss_f(p):
                # Mixed precision: one master->compute cast of the param
                # tree per pass; grads flow back f32 through the cast VJP.
                pc = _cast_for_compute(policy, p, dtype_hints)
                logits, new_state = module.apply(
                    pc, state, x, train=True, rng=rng
                )
                # Layers may report auxiliary objectives (e.g. MoE router
                # load-balance loss) through state keys named "aux_loss";
                # they join the objective so their gradients flow.
                with jax.named_scope("loss"):
                    if policy is not None:
                        logits = policy.cast_output(logits)
                    loss = loss_fn(logits, y) + _aux_loss_sum(new_state)
                # Loss scaling (mixed_float16): autodiff sees scale*loss;
                # the reported loss stays unscaled via the aux output.
                scaled = loss if scale is None else loss * scale
                return scaled, (loss, new_state, logits)

            (_, (loss, new_state, logits)), grads = jax.value_and_grad(
                loss_f, has_aux=True
            )(params)
            with jax.named_scope("metrics"):
                mvals = {name: fn(logits, y) for name, fn in metric_fns}
            return loss, new_state, grads, mvals

        return grad_eval

    def _chunked_head_scan(self, params, state, h, y, weights, train):
        """Shared by the chunked train and eval paths: apply the head +
        loss (+ sum-count metrics) over ``head_chunks`` chunks of the
        flattened token axis under jax.checkpoint, so no more than one
        chunk of logits is ever live — forward or backward.

        ``weights``: per-token validity weights (None during training,
        where every token counts). Returns (loss_sum, valid_count,
        {metric: (sum, count)}).
        """
        import jax.lax as lax

        C = self.head_chunks
        loss_fn = self.loss_fn
        metric_fns = tuple(self.metric_fns)
        per_ex = losses_lib.get_per_example(loss_fn)
        _, head = _split_head(self.module)
        if state.get(head.name):
            raise ValueError(
                "head_chunks requires a STATELESS head layer; "
                f"{head.name!r} carries state"
            )
        if h.ndim < 2:
            raise ValueError(
                f"head_chunks expects token activations (..., D); got "
                f"shape {h.shape}"
            )
        d = h.shape[-1]
        n_tok = int(np.prod(h.shape[:-1]))
        if n_tok % C:
            raise ValueError(
                f"head_chunks={C} must divide the token count {n_tok} "
                f"(= batch x seq)"
            )
        hf = h.reshape(C, n_tok // C, d)
        yf = y.reshape(C, n_tok // C)
        if weights is None:
            wf = jnp.ones((C, n_tok // C), jnp.float32)
        else:
            wf = weights.reshape(C, n_tok // C).astype(jnp.float32)
        head_params = params.get(head.name, {})

        def chunk(carry, hyw):
            h_i, y_i, w_i = hyw
            with _child_scope(head.name):
                logits_i, _ = head.apply(head_params, {}, h_i, train=train)
            with jax.named_scope("loss"):
                if per_ex is not None:
                    elems = per_ex(logits_i, y_i)
                    lsum = jnp.sum(elems * w_i.astype(elems.dtype))
                else:
                    # Custom loss without a per-example form: whole-chunk
                    # mean weighted by the chunk's valid count (exact when
                    # unpadded).
                    lsum = loss_fn(logits_i, y_i) * jnp.sum(w_i)
            msums = []
            with jax.named_scope("metrics"):
                for name, fn in metric_fns:
                    scores = metrics_lib.per_example(fn)
                    if scores is not None:
                        s_elems = scores(logits_i, y_i)
                        msums.append(
                            (jnp.sum(s_elems * w_i.astype(s_elems.dtype)),
                             jnp.sum(w_i)))
                    else:
                        # No per-example form: rescale the chunk's (sum,
                        # count) by its valid-token weight, mirroring the
                        # plain eval step's mask treatment (exact when
                        # unpadded).
                        s, c = fn(logits_i, y_i)
                        w_sum = jnp.sum(w_i)
                        msums.append(
                            (s * w_sum / jnp.maximum(c, 1.0), w_sum))
            loss_c, m_c = carry
            m_new = tuple(
                (a + jnp.float32(s), b + jnp.float32(c))
                for (a, b), (s, c) in zip(m_c, msums)
            )
            return (loss_c + jnp.float32(lsum), m_new), None

        init = (
            jnp.float32(0.0),
            tuple((jnp.float32(0.0), jnp.float32(0.0)) for _ in metric_fns),
        )
        (loss_sum, msums), _ = lax.scan(
            jax.checkpoint(chunk), init, (hf, yf, wf)
        )
        mvals = {name: m for (name, _), m in zip(metric_fns, msums)}
        return loss_sum, jnp.sum(wf), mvals

    def _chunked_grad_eval_body(self):
        """Grad-eval for compile(head_chunks=C): body applies once, the
        head + loss run chunk-by-chunk (see _chunked_head_scan)."""
        body_layers, _ = _split_head(self.module)
        policy, dtype_hints = self.precision, self._dtype_hints

        def grad_eval(params, state, x, y, rng, scale=None):
            def loss_f(p):
                pc = _cast_for_compute(policy, p, dtype_hints)
                h, new_state = _apply_layers(
                    body_layers, pc, state, x, train=True, rng=rng
                )
                loss_sum, n_tok, mvals = self._chunked_head_scan(
                    pc, state, h, y, None, train=True
                )
                with jax.named_scope("loss"):
                    loss = loss_sum / n_tok + _aux_loss_sum(new_state)
                scaled = loss if scale is None else loss * scale
                return scaled, (loss, new_state, mvals)

            (_, (loss, new_state, mvals)), grads = jax.value_and_grad(
                loss_f, has_aux=True
            )(params)
            return loss, new_state, grads, mvals

        return grad_eval

    def _accum_train_step_body(self, m: int):
        """Train body for ``fit(grad_accum=M)``: same ``(params, state,
        opt_state, x, y, rng) -> (params, state, opt_state, loss, mvals)``
        signature as ``_train_step_body``, but x/y carry a leading ``[M]``
        microbatch axis. The M forward/backward passes run as a
        ``lax.scan`` (so peak activation memory is ONE microbatch's, the
        whole point), gradients accumulate in f32 as a carry, metrics as
        (sum, count), and a SINGLE optimizer update applies the mean
        gradient at the end — the update an M-times-larger batch would
        take, with the optimizer state advancing once. Per-microbatch RNG
        is ``fold_in(step_rng, i)``; the reported loss is the mean of the
        microbatch means. Slots anywhere ``_train_step_body`` does,
        including under the K-step fused dispatch."""
        grad_eval = self._grad_eval_body()
        tx = self.tx
        metric_names = tuple(name for name, _ in self.metric_fns)
        # Same CPU unroll rationale as _get_multi_step_train_step: XLA:CPU
        # runs while-loop bodies ~2x slower than straight-line code.
        unroll_full = self._device_platform() == "cpu"

        def step(params, state, opt_state, xs, ys, rng):
            scale = optim.loss_scale_value(opt_state)

            def one(carry, slice_i):
                gsum, state, loss_sum, msums = carry
                x, y, i = slice_i
                loss, state, grads, mvals = grad_eval(
                    params, state, x, y, jax.random.fold_in(rng, i), scale
                )
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), gsum, grads
                )
                loss_sum = loss_sum + jnp.float32(loss)
                msums = tuple(
                    (s + jnp.float32(mvals[n][0]),
                     c + jnp.float32(mvals[n][1]))
                    for (s, c), n in zip(msums, metric_names)
                )
                return (gsum, state, loss_sum, msums), None

            # f32 accumulator regardless of param/grad compute dtype (bf16
            # partial sums over M microbatches would lose the low bits the
            # equivalent big batch keeps); the shared precision helper is
            # the single implementation, and the trace-time assert pins
            # master-precision accumulation under any policy.
            acc0 = precision_lib.grad_accum_init(params)
            precision_lib.assert_f32_accumulator(acc0)
            init = (
                acc0,
                state,
                jnp.float32(0.0),
                tuple(
                    (jnp.float32(0.0), jnp.float32(0.0))
                    for _ in metric_names
                ),
            )
            (gsum, new_state, loss_sum, msums), _ = jax.lax.scan(
                one, init, (xs, ys, jnp.arange(m)),
                unroll=m if unroll_full else 1,
            )
            grads = precision_lib.cast_like(
                jax.tree_util.tree_map(lambda a: a / m, gsum), params
            )
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                new_params, new_opt = _constrain_step_outputs(
                    new_params, new_opt
                )
            mvals = {n: p for n, p in zip(metric_names, msums)}
            return new_params, new_state, new_opt, loss_sum / m, mvals

        return step

    def _get_accum_train_step(self, m: int):
        fn = self._accum_train_steps.get(m)
        if fn is None:
            fn = self._scoped(
                jax.jit(self._accum_train_step_body(m), donate_argnums=(0, 1, 2))
            )
            self._accum_train_steps[m] = fn
        return fn

    def _get_multi_step_train_step(self, accum_m: int = 1):
        """Fused K-step dispatch for compile(steps_per_execution=K): one
        jitted ``lax.scan`` over the leading axis of a ``[K, batch, ...]``
        super-batch, running the SAME per-step body the K=1 path jits
        (plain or chunked-head). Params/state/opt_state are donated once
        per dispatch and thread through the scan carry; the loss and every
        metric's (sum, count) accumulate on device — the host fetches
        nothing until the epoch boundary. Per-step RNG is
        ``fold_in(base_rng, step0 + i)``, bit-identical to the K=1 loop's
        ``_step_rng`` at the same global step, so dropout/augmentation
        draws match across K. K is read from the super-batch shape, so a
        shorter remainder dispatch (epoch tail, resume) just compiles a
        second program.

        On CPU the scan is emitted FULLY UNROLLED (``unroll=K``): XLA:CPU
        executes a while-loop body ~2x slower than the same ops outside it
        (measured on the mnist_cnn step — loop-carry buffer copies defeat
        the in-place reuse the straight-line program gets), which would
        eat the entire dispatch saving. Accelerator backends keep the
        rolled loop: the carry stays in place there and compile time stays
        O(1) in K.

        ``accum_m > 1`` composes ``fit(grad_accum=M)`` with the fused
        dispatch: the per-step body becomes the M-microbatch accumulation
        scan, and the super-batch arrives as ``[K*M, micro, ...]`` (one
        stacked placement), reshaped to ``[K, M, micro, ...]`` in-trace —
        K optimizer steps per dispatch, each from M accumulated
        microbatch gradients."""
        cached = self._multi_train_steps.get(accum_m)
        if cached is not None:
            return cached
        body = (
            self._train_step_body() if accum_m == 1
            else self._accum_train_step_body(accum_m)
        )
        metric_names = tuple(name for name, _ in self.metric_fns)
        unroll_full = self._device_platform() == "cpu"

        def multi(params, state, opt_state, xs, ys, base_rng, step0):
            k = xs.shape[0] // accum_m
            if accum_m > 1:
                xs = xs.reshape((k, accum_m) + xs.shape[1:])
                ys = ys.reshape((k, accum_m) + ys.shape[1:])

            def one(carry, slice_i):
                params, state, opt_state, loss_sum, msums = carry
                x, y, i = slice_i
                rng = jax.random.fold_in(base_rng, step0 + i)
                params, state, opt_state, loss, mvals = body(
                    params, state, opt_state, x, y, rng
                )
                loss_sum = loss_sum + jnp.float32(loss)
                msums = tuple(
                    (s + jnp.float32(mvals[n][0]), c + jnp.float32(mvals[n][1]))
                    for (s, c), n in zip(msums, metric_names)
                )
                return (params, state, opt_state, loss_sum, msums), None

            init = (
                params, state, opt_state, jnp.float32(0.0),
                tuple(
                    (jnp.float32(0.0), jnp.float32(0.0)) for _ in metric_names
                ),
            )
            (params, state, opt_state, loss_sum, msums), _ = jax.lax.scan(
                one, init, (xs, ys, jnp.arange(k)),
                unroll=k if unroll_full else 1,
            )
            mvals = {n: m for n, m in zip(metric_names, msums)}
            return params, state, opt_state, loss_sum, mvals

        fn = self._scoped(jax.jit(multi, donate_argnums=(0, 1, 2)))
        self._multi_train_steps[accum_m] = fn
        return fn

    def _device_platform(self) -> str:
        """Platform ('cpu'/'tpu'/...) of the devices this model's strategy
        places work on."""
        mesh = getattr(self.strategy, "mesh", None)
        if mesh is not None:
            return mesh.devices.flat[0].platform
        device = getattr(self.strategy, "device", None)
        return (device or jax.devices()[0]).platform

    def _scoped(self, jitted):
        """Run the jitted fn with this model's strategy (and precision
        policy, when compiled with one) as the ambient context: jit traces
        on first call, and trace-time code — MultiHeadAttention's
        ring-attention detection reads current_strategy(), layer dtype
        resolution reads precision.current_policy(). Per-call cost is a
        thread-local set/reset."""
        strategy = self.strategy
        policy = self.precision

        def call(*args):
            with strategy.scope():
                if policy is None:
                    return jitted(*args)
                with policy.scope():
                    return jitted(*args)

        return call

    def _get_eval_step(self):
        if self._eval_step is not None:
            return self._eval_step
        if self.head_chunks and self.head_chunks > 1:
            return self._get_chunked_eval_step()
        module, loss_fn = self.module, self.loss_fn
        metric_fns = tuple(self.metric_fns)
        per_ex = losses_lib.get_per_example(self.loss_fn)
        policy, dtype_hints = self.precision, self._dtype_hints

        def step(params, state, x, y, mask):
            params = _cast_for_compute(policy, params, dtype_hints)
            # Publish per-example validity to batch-statistic layers (MoE
            # routing) so pad rows neither route nor bias aux losses —
            # but only when the loss can ALSO mask per element: a custom
            # whole-batch-mean loss would average the zeroed-out pad
            # outputs, a worse approximation than letting the pad clones
            # route normally (they are copies of the last real row).
            import contextlib

            weights_ctx = (
                _eval_sample_weights(mask) if per_ex is not None
                else contextlib.nullcontext()
            )
            with weights_ctx:
                logits, new_state = module.apply(
                    params, state, x, train=False
                )
            if policy is not None:
                logits = policy.cast_output(logits)
            # Token-level models have per-element losses of shape y.shape
            # (e.g. (B, T) for an LM); the pad mask is per-example (B,).
            # Broadcast it to the label rank and count *elements*, so the
            # reported loss is a per-token mean matching the training
            # objective (loss_fn's whole-batch mean).
            def weights_like(elems):
                m = mask.reshape(mask.shape + (1,) * (elems.ndim - 1))
                return jnp.broadcast_to(m, elems.shape).astype(elems.dtype)

            if per_ex is not None:
                loss_elems = per_ex(logits, y)
                w = weights_like(loss_elems)
                loss_sum = jnp.sum(loss_elems * w)
                valid = jnp.sum(w)
            else:
                # Custom loss without a per-example form: whole-batch mean
                # weighted by valid count (exact when the batch is unpadded).
                valid = jnp.sum(mask) * (y.size / y.shape[0])
                loss_sum = loss_fn(logits, y) * valid
            # Keep evaluate() measuring the trained objective: auxiliary
            # losses (MoE load balance) join here too, computed over valid
            # rows only (eval_sample_weights above excludes batch pads).
            loss_sum = loss_sum + _aux_loss_sum(new_state) * valid
            msums = {}
            for name, fn in metric_fns:
                scores = metrics_lib.per_example(fn)
                if scores is not None:
                    s_elems = scores(logits, y)
                    w = weights_like(s_elems)
                    msums[name] = (jnp.sum(s_elems * w), jnp.sum(w))
                else:
                    s, c = fn(logits, y)
                    ex = jnp.sum(mask)
                    msums[name] = (s * ex / jnp.maximum(c, 1.0), ex)
            return loss_sum, valid, msums

        self._eval_step = self._scoped(jax.jit(step))
        return self._eval_step

    def _get_chunked_eval_step(self):
        """Eval step for compile(head_chunks=C): same masked (sum, valid)
        contract as the plain step, with the head + loss + metrics run per
        token chunk so full logits never materialize."""
        body_layers, _ = _split_head(self.module)
        policy, dtype_hints = self.precision, self._dtype_hints

        def step(params, state, x, y, mask):
            params = _cast_for_compute(policy, params, dtype_hints)
            # Same conditional as the plain eval step: weights only when
            # the loss can mask per element (see _get_eval_step).
            import contextlib

            weights_ctx = (
                _eval_sample_weights(mask)
                if losses_lib.get_per_example(self.loss_fn) is not None
                else contextlib.nullcontext()
            )
            with weights_ctx:
                h, new_state = _apply_layers(
                    body_layers, params, state, x, train=False, rng=None
                )
            # Per-example mask -> per-token weights (same broadcast the
            # plain step applies to per-element losses).
            m = mask.reshape(mask.shape + (1,) * (y.ndim - 1))
            w = jnp.broadcast_to(m, y.shape)
            loss_sum, valid, msums = self._chunked_head_scan(
                params, state, h, y, w, train=False
            )
            loss_sum = loss_sum + _aux_loss_sum(new_state) * valid
            return loss_sum, valid, msums

        self._eval_step = self._scoped(jax.jit(step))
        return self._eval_step

    def _get_predict_step(self):
        if self._predict_step is not None:
            return self._predict_step
        module = self.module
        policy, dtype_hints = self.precision, self._dtype_hints

        def step(params, state, x):
            params = _cast_for_compute(policy, params, dtype_hints)
            logits, _ = module.apply(params, state, x, train=False)
            if policy is not None:
                logits = policy.cast_output(logits)
            return logits

        self._predict_step = self._scoped(jax.jit(step))
        return self._predict_step

    def _step_rng(self):
        return jax.random.fold_in(jax.random.PRNGKey(self._seed + 1), self.step)

    # -------------------------------------------------------------------- fit
    def fit(
        self,
        x,
        y=None,
        batch_size: int = 32,
        epochs: int = 1,
        steps_per_epoch: Optional[int] = None,
        validation_data=None,
        validation_steps: Optional[int] = None,
        shuffle: bool = True,
        verbose: int = 1,
        initial_epoch: int = 0,
        seed: Optional[int] = None,
        callbacks: Sequence = (),
        prefetch: Optional[int] = None,
        grad_accum: Optional[int] = None,
    ) -> History:
        """``grad_accum=M``: split every optimizer step's ``batch_size``
        rows into M equal microbatches, run the M forward/backward passes
        sequentially ON DEVICE (a ``lax.scan`` inside the jitted step),
        accumulate the gradients in f32, and apply ONE optimizer update
        with their mean — the update the full batch would have taken, at
        the activation memory of ``batch_size / M`` rows. This is how the
        GLOBAL batch grows past what HBM fits in one shot: losses match
        the equivalent big batch to f32 summation order (bit-exact per
        microbatch, the cross-microbatch mean regroups the reduction),
        and ``tests/test_zero.py`` pins the parity. ``model.step``,
        callbacks, and LR schedules all advance per OPTIMIZER step (not
        per microbatch), unlike ``compile(gradient_accumulation_steps=N)``
        (optax.MultiSteps), which accumulates across N full-size ``fit``
        steps. Composes with ``compile(steps_per_execution=K)``: one
        dispatch stages ``[K*M, micro, ...]`` and runs K accumulated
        updates.

        ``prefetch``: device-prefetch depth — how many dispatches' input
        may be staged (host-prepped AND placed on device) ahead of the one
        executing, by a bounded background producer
        (``data.DevicePrefetcher``). Donated dispatches block the host for
        the duration of the previous step, so without prefetch every
        batch's prep + transfer sits on the step's critical path; with it
        the main thread's per-dispatch input cost is a queue pop. Default
        2 (double buffering); 0 stages synchronously inline (the
        pre-overlap loop). The staged stream is produced in order from the
        same cursor, so numerics are bit-identical at any depth, and a
        mid-epoch stop rewinds a seekable source (``data.Pipeline``) to
        the step actually trained. Per-fit stall accounting (input_wait /
        dispatch / checkpoint_wait seconds and the input-stall fraction)
        lands in ``model.last_fit_telemetry``.

        In the span timeline a fit is the phase ``fit_setup`` (this
        function's entry to the step loop's first ``input_wait``), the
        step loop's ``input_wait`` and ``dispatch`` spans (the first 8 of
        each; all of them in the histograms) and the phase
        ``fit_teardown`` (the last epoch's end to the return: callbacks'
        ``on_train_end``, the counters' read, the report)."""
        setup_phase = obs_spans.begin("fit_setup")
        if not self.compiled:
            raise RuntimeError("Call compile() before fit()")
        from .. import quant as quant_lib

        if self.built and quant_lib.is_quantized(self.params):
            raise RuntimeError(
                "model parameters are int8-quantized (quant.quantize_model)"
                " — quantized weights carry no gradients, so fit() is "
                "unavailable. Serve with generate()/predict()/serving."
                "Engine, or restore the f32 checkpoint to keep training."
            )
        self._fit_source = None  # checkpoint saves read the live source
        if y is None:
            # Iterator mode: x yields (x_batch, y_batch) — e.g. a
            # dtpu.data.Pipeline whose native threads prefetch batches ahead
            # of the device. batch_size/steps come from the source.
            if not hasattr(x, "__next__"):
                raise ValueError(
                    "fit(x) without y requires a batch iterator "
                    "(e.g. distributed_tpu.data.Pipeline)"
                )
            source = x
            # Checkpointer/ShardedCheckpointer record this source's
            # iterator cursor (state_dict) with every save taken during
            # this fit — including the preemption path's final save — so
            # mid-epoch resume can restore the stream without replay.
            self._fit_source = source
            batch_size = getattr(source, "batch_size", batch_size)
            # A per-host-sharded source (data.Pipeline(shard=(i, P))) emits
            # only this process's rows; placement assembles the global batch.
            per_host = _per_host_source(source)
            if steps_per_epoch is None:
                steps_per_epoch = getattr(source, "steps_per_pass", None)
                if steps_per_epoch is None:
                    raise ValueError(
                        "steps_per_epoch is required with a plain iterator"
                    )
            if not self.built:
                bshape = getattr(source, "batch_shape", None)
                if bshape is None:
                    raise RuntimeError(
                        "Build the model first (model.build(input_shape)) "
                        "when fitting from an iterator without batch_shape"
                    )
                self.build(tuple(bshape[1:]), seed=0 if seed is None else seed)

            def next_batch():
                return next(source)

        else:
            per_host = False
            x = np.asarray(x)
            y = np.asarray(y)
            if not self.built:
                self.build(x.shape[1:], seed=0 if seed is None else seed)
            n = x.shape[0]
            if batch_size > n:
                raise ValueError(f"batch_size {batch_size} > dataset size {n}")
            if steps_per_epoch is None:
                steps_per_epoch = n // batch_size
        if grad_accum is None:
            # compile(strategy="auto") may have planned an accumulation
            # factor (to fit the HBM cap); an explicit fit arg still wins.
            grad_accum = self._auto_grad_accum
        if grad_accum is not None and (
            not isinstance(grad_accum, (int, np.integer)) or grad_accum < 1
        ):
            raise ValueError(
                f"grad_accum must be an integer >= 1, got {grad_accum!r}"
            )
        accum_m = int(grad_accum) if grad_accum else 1
        if batch_size % accum_m:
            raise ValueError(
                f"grad_accum={accum_m} must divide batch_size {batch_size} "
                "(each optimizer step's batch splits into M equal "
                "microbatches)"
            )
        micro = batch_size // accum_m
        self.strategy.local_batch_size(micro)  # replica divisibility check
        if (
            validation_data is not None
            and hasattr(validation_data, "__next__")
            and validation_steps is None
            and getattr(validation_data, "steps_per_pass", None) is None
        ):
            # Fail now, not after the first epoch's work is spent: the
            # epoch-end validation hook would raise exactly this.
            raise ValueError(
                "validation_steps is required when validation_data is a "
                "plain iterator (sources with steps_per_pass, e.g. "
                "data.Pipeline, default to one pass)"
            )
        multi_k = self.steps_per_execution or 1
        if multi_k == 1:
            step_fn = (
                self._get_train_step() if accum_m == 1
                else self._get_accum_train_step(accum_m)
            )
        else:
            step_fn = None
        if prefetch is None:
            prefetch = int(os.environ.get("DTPU_PREFETCH_DEPTH", "2"))
        prefetch = max(0, int(prefetch))
        from ..data.prefetch import DevicePrefetcher
        from ..utils.profiler import StepTimer

        # Stall accounting for this fit: input_wait / dispatch /
        # checkpoint_wait (callbacks attribute the latter through
        # model._stall_timer). Summarized into last_fit_telemetry at exit.
        timer = StepTimer(warmup=0)
        self._stall_timer = timer
        in_timeline = obs_spans.loop_gate()
        # Reset the thread's scanned-overlap trace record so this fit's
        # telemetry can only see a record ITS OWN tracing wrote (a warm
        # jit cache writes none — the report then under-claims rather
        # than inherit another model's record).
        from ..nn import scan as _nn_scan
        _nn_scan._overlap_trace.record = None
        # Same reset for the pipeline-schedule trace record (nn/pipeline.py).
        from ..nn import pipeline as _nn_pipeline
        _nn_pipeline._pipeline_trace.record = None
        # Observability runtime (docs/OBSERVABILITY.md): per-dispatch
        # flight records + step-seconds ring, and a periodic cross-rank
        # metrics_snapshot flush over the supervisor's event-log
        # transport (no-op unsupervised). All gated on obs.enabled().
        obs_reg = obs_registry.default_registry()
        obs_rec = obs_flight.default_recorder()
        obs_flush_every = max(
            1, int(os.environ.get("DTPU_OBS_FLUSH_EVERY", "5") or 5)
        )
        obs_window: list = []  # per-STEP wall seconds since last flush

        def _flush_obs_window(force: bool = False):
            # step_seconds: per-step wall. self_seconds: wall MINUS the
            # dispatch/input waits — the rank's own host time. Collectives
            # equalize wall across a synchronous gang (victims wait in
            # dispatch while the straggler burns host time), so cross-rank
            # straggler attribution keys on self time (obs.aggregate).
            if not obs_window or (
                not force and len(obs_window) < obs_flush_every
            ):
                return
            if obs_registry.enabled() and events_lib.default_log() is not None:
                events_lib.emit(
                    evs.METRICS_SNAPSHOT,
                    rank=int(jax.process_index()),
                    world=int(jax.process_count()),
                    step=int(self.step),
                    step_seconds=[round(w, 6) for w, _ in obs_window[-64:]],
                    self_seconds=[round(s, 6) for _, s in obs_window[-64:]],
                )
            obs_window.clear()
        history = History()
        is_chief = jax.process_index() == 0
        self.stop_training = False
        self._resumed_step = None
        fit_steps_done = 0  # this fit's optimizer steps (steps/s gauge)
        for cb in callbacks:
            cb.on_train_begin(self)
        if y is not None:
            # After on_train_begin: a restoring ModelCheckpoint may have
            # advanced self.step, and the stream must fast-forward past
            # consumed batches.
            stream = _index_stream(
                n, batch_size, shuffle, seed, start_step=self.step
            )

            def next_batch():
                idx = next(stream)
                return x[idx], y[idx]

        def next_k_batches(k):
            # K host batches collated into one [K, batch, ...] super-batch.
            # A source with a native collator (data.Pipeline.next_k) fills
            # the stacked buffer directly from its prefetch ring; anything
            # else stacks k next_batch() results.
            if y is None and hasattr(source, "next_k"):
                return source.next_k(k)
            pairs = [next_batch() for _ in range(k)]
            return (
                np.stack([p[0] for p in pairs]),
                np.stack([p[1] for p in pairs]),
            )

        # Crash-restart contract: when a callback restored a checkpoint and
        # the caller didn't pass initial_epoch, `epochs` is the *total*
        # target — skip the epochs (and intra-epoch steps) already done, so
        # relaunching the identical command completes the run instead of
        # training `epochs` more. Assumes the relaunch uses the same
        # batch_size/steps_per_epoch, which "identical command" guarantees.
        resume_offset = 0
        if self._resumed_step is not None and initial_epoch == 0:
            initial_epoch, resume_offset = divmod(
                self._resumed_step, steps_per_epoch
            )
            if y is None:
                # The array path fast-forwards via _index_stream(start_step);
                # an iterator source must be advanced too or the resumed run
                # retrains on already-consumed batches. Preference order:
                # (1) the checkpoint's recorded iterator state via
                # load_state — O(1) and LOUD about stream-identity
                # mismatches (wrong seed/batch_size); (2) an O(1) seek to
                # the restored step; (3) replaying a plain-but-counting
                # iterator forward; (4) a warning.
                data_state = getattr(self, "_restored_data_state", None)
                self._restored_data_state = None
                if data_state is not None and hasattr(source, "load_state"):
                    source.load_state(data_state)
                elif hasattr(source, "seek"):
                    source.seek(self._resumed_step)  # O(1), no batch prep
                elif getattr(source, "steps_emitted", None) is not None:
                    for _ in range(
                        max(0, self._resumed_step - source.steps_emitted)
                    ):
                        next(source)
                else:
                    dlog.warning(
                        "Resuming from a plain iterator: cannot fast-forward "
                        "the data source; batch alignment with the restored "
                        f"step ({self._resumed_step}) is the caller's "
                        "responsibility"
                    )
            self._resumed_step = None
        for epoch in range(initial_epoch, epochs):
            t0 = time.perf_counter()
            for cb in callbacks:
                cb.on_epoch_begin(self, epoch)
            losses = []
            msums: Dict[str, list] = {name: [] for name, _ in self.metric_fns}
            epoch_steps = steps_per_epoch - resume_offset
            resume_offset = 0
            bar = None
            if verbose == 1 and is_chief:
                # Per-step progress with ETA (the reference's visible Keras
                # bar). Tracks host dispatch — no device fetches, keeping
                # the one-host-sync-per-epoch contract; verbose=2 gives
                # epoch lines only, as in Keras.
                bar = ProgressLine(
                    epoch_steps, prefix=f"Epoch {epoch + 1}/{epochs}: "
                )
            # Per-dispatch sizes are fixed up front ([1, 1, ...] plain;
            # [K, ..., tail] fused — an epoch tail or mid-epoch resume
            # shorter than K runs as a smaller final dispatch, so no batch
            # is skipped or replayed and resume needs no K-rounding). The
            # exact schedule lets the prefetch producer stage ahead without
            # ever over-consuming the source at a normal epoch end.
            if multi_k == 1:
                sizes = [1] * epoch_steps

                def stage(k):
                    xb, yb = next_batch()
                    if accum_m > 1:
                        # One optimizer step's batch as a [M, micro, ...]
                        # stack: leading microbatch axis replicated, rows
                        # (dim 1) sharded — the multi-step super-batch
                        # placement, reused verbatim. shape[0] (not the
                        # global micro size) so per-host row shards
                        # reshape to THEIR slice of each microbatch.
                        xb, yb = np.asarray(xb), np.asarray(yb)
                        mb = xb.shape[0] // accum_m
                        xb = xb.reshape((accum_m, mb) + xb.shape[1:])
                        yb = yb.reshape((accum_m, mb) + yb.shape[1:])
                        return self.strategy.put_batch(
                            {"x": xb, "y": yb}, per_host=per_host,
                            stacked=True, async_=True,
                        )
                    return self.strategy.put_batch(
                        {"x": xb, "y": yb}, per_host=per_host, async_=True
                    )

            else:
                sizes, left = [], epoch_steps
                while left > 0:
                    sizes.append(min(multi_k, left))
                    left -= sizes[-1]
                multi_fn = self._get_multi_step_train_step(accum_m)
                base_rng = jax.random.PRNGKey(self._seed + 1)

                def stage(k):
                    xs, ys = next_k_batches(k)
                    if accum_m > 1:
                        # [k, batch, ...] -> [k*M, micro, ...]: one stacked
                        # placement stages k optimizer steps x M
                        # microbatches; the jitted dispatch reshapes the
                        # leading axis back to [k, M].
                        xs, ys = np.asarray(xs), np.asarray(ys)
                        mb = xs.shape[1] // accum_m
                        xs = xs.reshape((k * accum_m, mb) + xs.shape[2:])
                        ys = ys.reshape((k * accum_m, mb) + ys.shape[2:])
                    return self.strategy.put_batch(
                        {"x": xs, "y": ys}, per_host=per_host, stacked=True,
                        async_=True,
                    )

            # Input overlap: a bounded producer preps + places dispatch
            # N+1 while dispatch N executes (donated dispatches block the
            # host until the previous step completes, so staged input is
            # the difference between a stalled and a saturated device).
            # depth 0 stages inline — byte-identical, just synchronous.
            staged = DevicePrefetcher(stage, sizes, depth=prefetch)
            done = 0
            setup_phase.end()  # the first epoch's; a no-op after
            last_iter_t = time.perf_counter()
            try:
                for k in sizes:
                    # input_wait / dispatch flow through obs spans (ONE
                    # attribution code path: StepTimer bucket + registry
                    # stall counter + span histogram + XProf annotation).
                    with obs_spans.span(
                            "input_wait", timer=timer,
                            timeline=in_timeline("input_wait")) as sp_in:
                        _, batch = staged.get()
                    with obs_spans.span(
                            "dispatch", timer=timer,
                            timeline=in_timeline("dispatch")) as sp_disp:
                        if multi_k == 1:
                            rng = self._step_rng()
                            (self.params, self.state, self.opt_state, loss,
                             mvals) = step_fn(
                                self.params, self.state, self.opt_state,
                                batch["x"], batch["y"], rng,
                            )
                            loss_log = loss
                        else:
                            (self.params, self.state, self.opt_state, loss,
                             mvals) = multi_fn(
                                self.params, self.state, self.opt_state,
                                batch["x"], batch["y"], base_rng,
                                np.int32(self.step),
                            )
                            # Callbacks see the dispatch's per-step mean,
                            # as a device scalar (reading it still costs a
                            # sync).
                            loss_log = loss / k
                    self.step += k
                    done += k
                    fit_steps_done += k
                    # Liveness beat for gang launchers (throttled no-op
                    # outside a gang): a worker blocked at a collective
                    # stops beating and the launcher's liveness_timeout
                    # gang-restarts it.
                    _gang_heartbeat()
                    losses.append(loss)  # per-step loss, or K-step sum
                    for name, _ in self.metric_fns:
                        msums[name].append(mvals[name])
                    # Callbacks fire once per dispatch (K-step granularity
                    # under steps_per_execution).
                    for cb in callbacks:
                        cb.on_batch_end(self, self.step, {"loss": loss_log})
                    if bar is not None:
                        bar.update(done)
                    # Per-iteration wall (input + dispatch + callbacks —
                    # everything between dispatch boundaries, which is
                    # what a cross-rank straggler comparison needs): one
                    # flight record + step-seconds ring entry, host-side
                    # only — no device value is fetched here.
                    now_t = time.perf_counter()
                    iter_wall = now_t - last_iter_t
                    last_iter_t = now_t
                    self_s = max(
                        iter_wall - sp_in.seconds - sp_disp.seconds, 0.0
                    )
                    obs_reg.ring_append("fit/step_seconds", {
                        "step": int(self.step), "k": int(k),
                        "seconds": round(iter_wall, 6),
                        "self_seconds": round(self_s, 6),
                    })
                    obs_rec.record(
                        "step", step=int(self.step), k=int(k),
                        seconds=round(iter_wall, 6),
                        input_wait_s=round(sp_in.seconds, 6),
                        dispatch_s=round(sp_disp.seconds, 6),
                        self_s=round(self_s, 6),
                    )
                    obs_reg.counter("fit/steps", k)
                    obs_window.append((iter_wall / k, self_s / k))
                    _flush_obs_window()
                    if self.stop_training:
                        # Graceful mid-epoch stop (PreemptionHandler's
                        # in-process mode): the partial epoch's metrics are
                        # reported over the steps that actually ran, and the
                        # checkpoint/step cursor resumes exactly here.
                        break
            except SystemExit:
                raise  # deliberate exit (preemption) — its own dump ran
            except BaseException as e:
                # Unhandled death of the step loop: leave the black box
                # behind (no-op unless a dump location is configured).
                obs_flight.dump(reason=f"exception:{type(e).__name__}")
                raise
            finally:
                staged.close()
                if staged.unconsumed_steps and y is None:
                    # The producer staged past a mid-epoch stop (or an
                    # error); rewind a seekable source so its cursor
                    # matches the steps actually trained — keeping
                    # steps_emitted == consumed for resume/diagnostics.
                    if hasattr(source, "seek") and (
                        getattr(source, "steps_emitted", None) is not None
                    ):
                        try:
                            source.seek(
                                source.steps_emitted - staged.unconsumed_steps
                            )
                        except ValueError:
                            pass  # source already closed; nothing to realign
            if bar is not None:
                bar.close()
            # Steps that actually ran this epoch: a graceful mid-epoch stop
            # (stop_training at a batch boundary) ends the epoch early, and
            # every per-step average below must reflect reality, not plan.
            epoch_steps = done
            # One host sync per epoch: the loss and every metric accumulator
            # fetch in a SINGLE device_get. Under multi-step execution the
            # list entries are already on-device K-step sums. This is where
            # async dispatch catches up with real compute — attributed to
            # dispatch time, like the donation waits it back-loads.
            with obs_spans.span("dispatch", timer=timer):
                losses, fetched = jax.device_get((losses, msums))
            if multi_k == 1:
                logs = {"loss": float(np.mean(losses))}
            else:
                logs = {"loss": float(np.sum(losses) / max(epoch_steps, 1))}
            # The device_get above is where async dispatch catches up with
            # real compute — beat again so the epoch-end window (sync +
            # validation + callbacks below) starts freshly armed.
            _gang_heartbeat()
            for name, pairs in fetched.items():
                s = sum(p[0] for p in pairs)
                c = sum(p[1] for p in pairs)
                logs[name] = float(s / max(c, 1.0))
            if validation_data is not None:
                # Arrays as (x, y); anything with __next__ (a Pipeline or
                # plain batch iterator) is consumed for validation_steps
                # batches (default: one pass) — the ImageNet-shaped flow
                # can validate from an iterator, not just host arrays.
                if hasattr(validation_data, "__next__"):
                    val = self.evaluate(
                        validation_data, steps=validation_steps, verbose=0
                    )
                else:
                    val = self.evaluate(
                        validation_data[0], validation_data[1],
                        batch_size=batch_size, verbose=0,
                    )
                logs.update({f"val_{k}": v for k, v in val.items()})
            dt = time.perf_counter() - t0
            history.record(epoch, logs)
            obs_rec.record("epoch_end", epoch=int(epoch),
                           steps=int(epoch_steps),
                           seconds=round(dt, 4),
                           loss=round(float(logs["loss"]), 6))
            for cb in callbacks:
                cb.on_epoch_end(self, epoch, logs)
                # Checkpoint writes etc. can be slow; keep beating between
                # callbacks so a healthy epoch boundary is never read as a
                # hang (liveness_timeout must still exceed any SINGLE
                # blocking operation — see LocalLauncher.run's docstring).
                _gang_heartbeat()
            if self.stop_training:
                epochs = epoch + 1  # for the verbose epoch counter below
            if verbose and is_chief:
                # epoch_steps, not steps_per_epoch: a resumed partial epoch
                # runs fewer steps and must report what actually ran.
                samples = batch_size * epoch_steps
                parts = " - ".join(f"{k}: {v:.4f}" for k, v in logs.items())
                dlog.info(
                    f"Epoch {epoch + 1}/{epochs} - {samples} samples - "
                    f"{dt:.2f}s ({dt / epoch_steps * 1000:.1f}ms/step) - {parts}"
                )
            if self.stop_training:
                break
        setup_phase.end()  # a fit that ran no epoch
        teardown_phase = obs_spans.begin("fit_teardown")
        for cb in callbacks:
            # on_train_end BEFORE the telemetry summary: ModelCheckpoint's
            # train-end wait() (flushing a background writer) attributes
            # its blocked time to checkpoint_wait and must be counted.
            cb.on_train_end(self, history)
        report = timer.stall_report()
        # Device-memory telemetry: the allocator's peak/current bytes when
        # the backend exposes them (HBM backends do; XLA:CPU reports None)
        # plus the measured per-device model-state footprint (params +
        # state + opt_state, from shard buffer sizes — exact on every
        # backend, and the number ZeRO sharding exists to shrink).
        from ..utils.profiler import device_memory_stats, tree_bytes_per_device
        report["device_memory"] = device_memory_stats()
        report["model_state_bytes_per_device"] = tree_bytes_per_device(
            self.params, self.state, self.opt_state
        )["max_bytes_per_device"]
        # Buddy-redundancy pricing (set by ModelCheckpoint(buddy=...) at
        # train end): the measured (1+1/N)x of holding a peer's shard
        # mirror in host RAM, next to the state bytes it insures
        # (docs/RESILIENCE.md "Recovery tiers").
        red = getattr(self, "_redundancy_report", None)
        if red is not None:
            report["redundancy"] = red
        # Collective-traffic estimate at the dtype the bytes move in: a
        # mixed policy halves FSDP's gathered-param bytes (bf16 vs f32) —
        # the number tests/test_precision.py compares across policies.
        report["precision"] = (
            self.precision.name if self.precision is not None else None
        )
        # Streaming-input telemetry: the decode-parallelism setting rides
        # next to the stall fractions it exists to shrink, so a stall
        # report names the knob to turn (docs/API.md "Streaming input").
        if y is None and getattr(source, "decode_workers", None) is not None:
            report["input_decode_workers"] = int(source.decode_workers)
        report["comm_bytes_estimate"] = self.strategy.comm_bytes_estimate(
            self.params,
            compute_dtype=(
                self.precision.compute_dtype
                if self.precision is not None else None
            ),
            hints=self._param_hints,
        )
        # Gather-overlap attribution (ScannedBlocks x Strategy.overlap_spec):
        # the trace-time record of the most recent scanned apply on this
        # thread says whether the double-buffered gather engaged.
        # exposed_comm_fraction is the analytic share of per-layer gather
        # traffic left serial with compute: all L gathers without overlap,
        # only layer 0's warm-up gather with it. Exposed collective time
        # on the chip is the benchmark's trace metric; this rides with every
        # fit so telemetry names the lever (docs/API.md "Overlap round 2").
        from ..nn.scan import last_overlap_trace
        _otrace = last_overlap_trace()
        if _otrace is None:
            # Warm jit cache = nothing traced this fit; this model's own
            # previous fit (if any) already recorded the program's shape.
            _otrace = getattr(self, "_overlap_record", None)
        else:
            self._overlap_record = _otrace
        _olayers = int(_otrace["layers"]) if _otrace else 0
        _oactive = bool(_otrace and _otrace["active"])
        report["overlap"] = {
            "overlap": _oactive,
            "exposed_comm_fraction": (
                round(1.0 / _olayers, 6) if (_oactive and _olayers) else 1.0
            ),
            "layers": _olayers,
        }
        if obs_registry.enabled() and events_lib.default_log() is not None:
            events_lib.emit(
                evs.OVERLAP_REPORT,
                overlap=report["overlap"]["overlap"],
                exposed_comm_fraction=report["overlap"][
                    "exposed_comm_fraction"],
                layers=report["overlap"]["layers"],
                strategy=type(self.strategy).__name__,
            )
        # Pipeline-schedule attribution (PipelinedBlocks x schedule): the
        # trace-time record of the most recent pipelined apply on this
        # thread — which schedule ran, its static tick count, and the
        # analytic bubble fraction (n-1)/ticks. Same warm-cache fallback
        # discipline as the overlap record above (docs/API.md "Pipeline
        # round 2").
        from ..nn.pipeline import last_pipeline_trace
        _ptrace = last_pipeline_trace()
        if _ptrace is None:
            _ptrace = getattr(self, "_pipeline_record", None)
        else:
            self._pipeline_record = _ptrace
        if _ptrace is not None:
            report["pipeline"] = dict(_ptrace)
            if obs_registry.enabled() and events_lib.default_log() is not None:
                events_lib.emit(
                    evs.PIPELINE_SCHEDULE_SELECTED,
                    schedule=_ptrace["schedule"],
                    interleave=_ptrace["interleave"],
                    num_stages=_ptrace["num_stages"],
                    num_microbatches=_ptrace["num_microbatches"],
                    strategy=type(self.strategy).__name__,
                )
                events_lib.emit(
                    evs.BUBBLE_REPORT,
                    bubble_fraction=_ptrace["bubble_fraction"],
                    ticks=_ptrace["ticks"],
                    schedule=_ptrace["schedule"],
                    interleave=_ptrace["interleave"],
                    num_stages=_ptrace["num_stages"],
                    num_microbatches=_ptrace["num_microbatches"],
                )
        # The auto-shard decision record rides with every fit it governed:
        # chosen config, predicted bytes/traffic, and the pruned
        # candidates' rationale (docs/API.md "Autotuned sharding").
        if self.last_plan is not None:
            report["plan"] = self.last_plan.summary()
        # Dropless expert layers count in their state (no sync in the step
        # loop); read once here: per layer in the report, the sums as gauges.
        moe_counters = moe_lib.counters(self.state)
        if moe_counters:
            report["moe"] = moe_counters
            total = lambda name: sum(
                c[name] for c in moe_counters.values())
            for name in ("pairs", "held_rows"):
                obs_reg.gauge(f"moe.{name}", total(name))
            # Tiles the row walks and the grouped matmuls went over, of the
            # tiles of the buffers' static worst case.
            obs_reg.gauge("moe.buffer_used_pct", 100.0 * total(
                "tiles_used") / max(total("buffer_tiles"), 1.0))
        # Attention layers that select their keys count the same way.
        select_counters = attention_lib.select_counters(self.state)
        if select_counters:
            report["select"] = select_counters
        # And those that see a window of their keys.
        window_counters = attention_lib.window_counters(self.state)
        if window_counters:
            report["window"] = window_counters
        # The legacy dict is a VIEW stored in the metrics registry
        # (key-for-key identical — pinned by the obs parity test): one
        # telemetry surface, backward-compatible reader.
        _flush_obs_window(force=True)
        obs_reg.gauge("fit/steps_per_sec", round(
            fit_steps_done / report["total_seconds"], 3))
        obs_reg.gauge("fit/input_stall_fraction",
                      report["input_stall_fraction"])
        obs_reg.gauge("fit/model_state_bytes_per_device",
                      report["model_state_bytes_per_device"])
        dm = report["device_memory"]
        if dm:
            for key, val in dm.items():
                obs_reg.gauge(f"fit/device_memory/{key}", val)
        self.last_fit_telemetry = obs_reg.set_report("model.fit", report)
        self._stall_timer = None
        teardown_phase.end()
        return history

    # --------------------------------------------------------------- evaluate
    def evaluate(self, x, y=None, batch_size: int = 32, verbose: int = 1,
                 steps: Optional[int] = None) -> Dict[str, float]:
        """Evaluate on arrays ``(x, y)`` or on a batch iterator.

        Iterator form: ``evaluate(pipe)`` where ``pipe`` yields ``(x, y)``
        batches (e.g. ``data.Pipeline``, including per-host sharded ones).
        ``steps`` gives the number of batches to consume; defaults to the
        source's ``steps_per_pass`` (one pass) when it has one. The
        iterator is advanced, not reset — each call evaluates the next
        ``steps`` batches of the stream.
        """
        if y is None:
            if hasattr(x, "__next__"):
                return self._evaluate_iterator(x, steps=steps,
                                               verbose=verbose)
            raise TypeError(
                "evaluate() needs (x, y) arrays or a batch iterator "
                f"yielding (x, y); got {type(x).__name__} without labels"
            )
        x = np.asarray(x)
        y = np.asarray(y)
        if not (self.built and self.compiled):
            raise RuntimeError("Model must be built and compiled")
        n = x.shape[0]
        # Keep the step shape static: partial batches (including n < batch)
        # are padded and masked, so one compile covers everything and the
        # replica-divisibility of batch_size is preserved under DP.
        self.strategy.local_batch_size(batch_size)
        step_fn = self._get_eval_step()
        results = []  # device values; one host sync at the end
        for start in range(0, n, batch_size):
            xb = x[start : start + batch_size]
            yb = y[start : start + batch_size]
            valid = xb.shape[0]
            if valid < batch_size:  # pad to keep shapes static (one compile)
                pad = batch_size - valid
                xb = np.concatenate([xb, np.repeat(xb[-1:], pad, axis=0)])
                yb = np.concatenate([yb, np.repeat(yb[-1:], pad, axis=0)])
            mask = np.zeros((batch_size,), np.float32)
            mask[:valid] = 1.0
            batch = self.strategy.put_batch({"x": xb, "y": yb, "m": mask})
            results.append(
                step_fn(self.params, self.state, batch["x"], batch["y"], batch["m"])
            )
            _gang_heartbeat()
        return self._finish_eval(results, n, verbose)

    def _evaluate_iterator(self, source, *, steps=None, verbose=1):
        if not (self.built and self.compiled):
            raise RuntimeError("Model must be built and compiled")
        if steps is None:
            steps = getattr(source, "steps_per_pass", None)
            if steps is None:
                raise ValueError(
                    "steps is required when evaluating from a plain "
                    "iterator (sources with steps_per_pass, e.g. "
                    "data.Pipeline, default to one pass)"
                )
        # A sharded Pipeline emits only this host's rows of each batch.
        per_host = _per_host_source(source)
        step_fn = self._get_eval_step()
        results = []
        rows = 0
        for step_i in range(int(steps)):
            try:
                xb, yb = next(source)
            except StopIteration:
                raise ValueError(
                    f"validation iterator exhausted after {step_i} of "
                    f"{int(steps)} batches — a finite iterator cannot be "
                    "re-consumed across epochs; use a repeating source "
                    "(data.Pipeline) or pass a smaller steps/"
                    "validation_steps"
                ) from None
            mask = np.ones((xb.shape[0],), np.float32)
            batch = self.strategy.put_batch(
                {"x": xb, "y": yb, "m": mask}, per_host=per_host
            )
            results.append(
                step_fn(self.params, self.state, batch["x"], batch["y"],
                        batch["m"])
            )
            rows += xb.shape[0]
            _gang_heartbeat()
        # Report GLOBAL rows: a sharded source yields only this host's
        # (1/P)-slice of every batch, so scale by the shard count when the
        # source doesn't carry an explicit global batch_size.
        n = getattr(source, "batch_size", None)
        if per_host:
            n = n * int(steps) if n else rows * int(source.shard[1])
        else:
            n = rows
        return self._finish_eval(results, n, verbose)

    def _finish_eval(self, results, n, verbose):
        results = jax.device_get(results)
        loss_sum = sum(float(r[0]) for r in results)
        count = sum(float(r[1]) for r in results)
        out = {"loss": loss_sum / max(count, 1.0)}
        for name, _ in self.metric_fns:
            s = sum(float(r[2][name][0]) for r in results)
            c = sum(float(r[2][name][1]) for r in results)
            out[name] = s / max(c, 1.0)
        if verbose and jax.process_index() == 0:
            parts = " - ".join(f"{k}: {v:.4f}" for k, v in out.items())
            dlog.info(f"Evaluate - {n} samples - {parts}")
        return out

    # ---------------------------------------------------------------- predict
    def predict(self, x, batch_size: int = 32, steps: Optional[int] = None
                ) -> np.ndarray:
        """Logits as a NumPy array. ``x``: host array, or a batch iterator
        (e.g. ``data.Pipeline`` — Keras's predict(generator) shape); an
        iterator yields (x_batch, y_batch) or bare x_batch for ``steps``
        batches (default: one pass for sources with ``steps_per_pass``);
        on the iterator path ``batch_size`` is IGNORED — batch shape comes
        from the source.
        NOTE a Pipeline drops the non-divisible remainder (its one pass is
        floor(n / batch_size) batches), so iterator predictions cover
        batch_size * steps rows — pass host arrays when you need logits
        for every row."""
        if not self.built:
            raise RuntimeError("Model not built")
        if hasattr(x, "__next__"):
            if steps is None:
                steps = getattr(x, "steps_per_pass", None)
                if steps is None:
                    raise ValueError(
                        "steps is required when predicting from a plain "
                        "iterator (sources with steps_per_pass, e.g. "
                        "data.Pipeline, default to one pass)"
                    )
            # A per-host-sharded Pipeline emits only this process's rows of
            # each batch; placement assembles the global batch (the same
            # detection fit()/evaluate() use).
            per_host = _per_host_source(x)
            step_fn = self._get_predict_step()
            # _to_host, not device_get: per-host batches make the logits
            # span non-addressable devices on multi-process runs; the
            # checkpoint helper gathers those collectively.
            from ..checkpoint.core import _to_host

            outs = []
            for step_i in range(int(steps)):
                try:
                    batch = next(x)
                except StopIteration:
                    raise ValueError(
                        f"prediction iterator exhausted after {step_i} of "
                        f"{int(steps)} batches — pass a smaller steps or a "
                        "repeating source (data.Pipeline)"
                    ) from None
                xb = batch[0] if isinstance(batch, tuple) else batch
                xb = self.strategy.put_batch(
                    {"x": np.asarray(xb)}, per_host=per_host
                )["x"]
                outs.append(np.asarray(
                    _to_host(step_fn(self.params, self.state, xb))
                ))
            return np.concatenate(outs, axis=0)
        x = np.asarray(x)
        n = x.shape[0]
        self.strategy.local_batch_size(batch_size)
        step_fn = self._get_predict_step()
        # Per-batch outputs stay DEVICE arrays: a blocking device_get after
        # every dispatch used to serialize host and device (each batch
        # waited out the previous one's transfer). A small sliding window
        # keeps dispatch running ahead while bounding how many batches of
        # logits are resident on device at once; everything left in the
        # window is drained in one fetch at the end.
        window = 16
        pending = []  # not-yet-fetched device outputs, oldest first
        fetched = []  # host arrays, in batch order
        valids = []
        for start in range(0, n, batch_size):
            xb = x[start : start + batch_size]
            valids.append(xb.shape[0])
            if xb.shape[0] < batch_size:
                xb = np.concatenate(
                    [xb, np.repeat(xb[-1:], batch_size - xb.shape[0], axis=0)]
                )
            xb = self.strategy.put_batch({"x": xb})["x"]
            pending.append(step_fn(self.params, self.state, xb))
            if len(pending) >= window:
                fetched.append(np.asarray(jax.device_get(pending.pop(0))))
        # Tail drain: one batched readiness wait over EVERYTHING still in
        # the window, then the fetches — not a per-array device_get chain,
        # where each array would serialize a full transport round-trip
        # behind the previous one's.
        pending = jax.block_until_ready(pending)
        fetched.extend(np.asarray(o) for o in jax.device_get(pending))
        return np.concatenate(
            [o[:v] for o, v in zip(fetched, valids)], axis=0
        )

    # --------------------------------------------------------------- generate
    def decode_dtype(self):
        """KV-cache / activation dtype for autoregressive decode, shared by
        ``generate()`` and ``serving.Engine``. Under a precision policy it
        IS the policy's compute dtype (no abstract trace needed — and a
        bare trace would miss the scope-resolved layer dtypes); without
        one it comes from an abstract trace of the forward pass (the
        logits dtype equals the activation dtype for these models).
        Memoized per build/compile/load."""
        if not self.built:
            raise RuntimeError("Model not built")
        if self._decode_dtype is None:
            if self.precision is not None:
                self._decode_dtype = self.precision.compute_dtype
            else:
                module, params, state = self.module, self.params, self.state
                self._decode_dtype = jax.eval_shape(
                    lambda p: module.apply(
                        p, state, jnp.zeros((1, 1), jnp.int32)
                    )[0],
                    params,
                ).dtype
        return self._decode_dtype

    @staticmethod
    def _sample_logits(logits, key, temperature, top_k):
        logits = logits.astype(jnp.float32)
        if top_k is not None:
            k = min(int(top_k), logits.shape[-1])
            kth = jax.lax.top_k(logits, k)[0][:, -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / jnp.float32(temperature)
        ).astype(jnp.int32)

    def generate(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        seed: int = 0,
    ) -> np.ndarray:
        """Autoregressive sampling from a token LM with a KV cache.

        ``prompt``: (B, T_p) int tokens. Returns (B, T_p + max_new_tokens).
        ``temperature=0`` is greedy argmax; ``top_k`` restricts sampling to
        the k highest-probability tokens. The whole prefill + decode loop is
        one ``lax.scan`` inside one jit: the prompt is teacher-forced through
        the same cached step the sampled tokens use, so there is exactly one
        compile and O(T) attention per step (nn layers' ``decode``/
        ``init_cache``; scanned AND pipelined stacks decode through stacked
        per-block caches).

        The reference has no generation surface at all (its only model is a
        classifier CNN, /root/reference/README.md:58-68); this is part of
        the LM tier the framework adds.
        """
        if not self.built:
            raise RuntimeError("Model not built")
        prompt = np.asarray(prompt)
        if prompt.ndim != 2:
            raise ValueError(f"prompt must be (batch, tokens); got {prompt.shape}")
        b, t_p = prompt.shape
        if t_p < 1:
            raise ValueError(
                "prompt must contain at least one token (the decode scan is "
                f"seeded from prompt[:, 0]); got shape {prompt.shape}"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if top_k is not None and int(top_k) < 1:
            raise ValueError(f"top_k must be >= 1; got {top_k}")
        max_len = t_p + max_new_tokens
        # Bucket the scan length (multiple of 64) so serving loops with
        # naturally varying prompt lengths reuse a handful of compilations
        # instead of one per exact (t_p, max_len) pair; the prompt length
        # itself flows in as a dynamic argument to the teacher-forcing mask.
        bucket = max(64, -(-max_len // 64) * 64)
        module, params, state = self.module, self.params, self.state
        decode_dtype = self.decode_dtype()
        try:
            cache = module.init_cache(params, b, bucket, decode_dtype)
        except ValueError:
            # Bucketed length exceeds the model's capacity (e.g. a learned
            # positional table shorter than the bucket): fall back to the
            # exact requested length.
            bucket = max_len
            cache = module.init_cache(params, b, bucket, decode_dtype)
        padded = np.zeros((b, bucket), np.int32)
        padded[:, :t_p] = prompt

        # jit cache keyed by the static configuration: params/state/prompt/
        # seed/prompt-length flow in as arguments, so repeat generate()
        # calls with the same bucketed shapes reuse the compiled scan. The
        # cache is LRU-bounded so a long-lived serving loop cannot retain
        # unbounded compilations.
        sig = (b, bucket, float(temperature), top_k)
        run = self._generate_fns.pop(sig, None)
        if run is None:
            while len(self._generate_fns) >= self._GENERATE_CACHE_MAX:
                self._generate_fns.pop(next(iter(self._generate_fns)))
            # _scoped: decode paths read current_strategy() at trace time
            # (PipelinedBlocks picks its memory-sharded ring decode from
            # the ambient pipe mesh, exactly as apply() picks its schedule).
            run = self._scoped(jax.jit(
                functools.partial(
                    _generate_scan, module, bucket, temperature, top_k,
                    self.precision, self._dtype_hints,
                )
            ))
        self._generate_fns[sig] = run  # (re-)insert as most recent

        toks = np.asarray(
            jax.device_get(
                run(params, state, cache, jnp.asarray(padded),
                    jnp.int32(t_p), jnp.int32(max_len - 1),
                    jax.random.PRNGKey(seed))
            )
        )
        return np.concatenate(
            [prompt[:, :1].astype(np.int32), toks[:, : max_len - 1]], axis=1
        )

    # ---------------------------------------------------------------- weights
    def save_weights(self, path):
        """Keras-shaped convenience: export this model's parameters AND
        state (BatchNorm running stats — Keras counts them as
        non-trainable weights) to an HDF5 file (npz if ``path`` ends in
        .npz). Chief-only write; see checkpoint.Checkpointer for
        step-tagged training checkpoints and checkpoint.ShardedCheckpointer
        for per-process sharded saves."""
        from .. import checkpoint as ckpt

        if not self.built:
            raise RuntimeError("Model not built")
        tree = {"params": self.params, "state": self.state}
        path = str(path)
        if path.endswith(".npz"):
            return ckpt.save_npz(path, tree)
        return ckpt.export_hdf5(path, tree)

    def load_weights(self, path):
        """Load weights saved by :meth:`save_weights` (HDF5 or npz) and
        re-place them under this model's strategy/sharding. Also accepts a
        bare params tree (the ``export_hdf5(path, model.params)``
        interchange format); state is left untouched in that case."""
        from .. import checkpoint as ckpt

        if not self.built:
            raise RuntimeError(
                "Build the model first (model.build(input_shape)) so the "
                "loaded weights can be placed under its strategy"
            )
        path = str(path)
        if path.endswith(".npz"):
            loaded = ckpt.load_npz(path)
            tree = loaded[0] if isinstance(loaded, tuple) else loaded
        else:
            tree, _ = ckpt.import_hdf5(path)
        if "params" in tree and set(tree) <= {"params", "state"}:
            # save_weights wrapper. A stateless model's empty state dict is
            # dropped by the flat file format, so "state" may be absent.
            params, state = tree["params"], tree.get("state")
        else:  # bare params interchange
            params, state = tree, None
        ref = jax.tree_util.tree_structure(self.params)
        got = jax.tree_util.tree_structure(params)
        if ref != got:
            raise ValueError(
                f"Loaded weight tree does not match the model: {got} vs {ref}"
            )
        # Shape-check every leaf up front: a same-architecture-different-
        # width file would otherwise load silently and fail later with an
        # opaque shape error inside the jitted step.
        for (kpath, have), want in zip(
            jax.tree_util.tree_leaves_with_path(self.params),
            jax.tree_util.tree_leaves(params),
        ):
            if tuple(have.shape) != tuple(want.shape):
                raise ValueError(
                    f"Loaded weight shape mismatch at "
                    f"{jax.tree_util.keystr(kpath)}: file has "
                    f"{tuple(want.shape)}, model expects {tuple(have.shape)}"
                )
        if state is not None:
            sref = jax.tree_util.tree_structure(self.state)
            sgot = jax.tree_util.tree_structure(state)
            if sref != sgot:
                raise ValueError(
                    f"Loaded state tree does not match the model: "
                    f"{sgot} vs {sref}"
                )
        self.params = self.strategy.put_params(
            params, self.module.sharding_hints()
        )
        if state is not None:
            self.state = self.strategy.put_params(state)
        # Placements (and possibly dtypes) changed: every cached compiled
        # step is stale, as is the memoized decode dtype (mirrors build()).
        self._train_step = self._eval_step = self._predict_step = None
        self._multi_train_steps = {}
        self._accum_train_steps = {}
        self._decode_dtype = None
        self._generate_fns = {}
        if self.compiled:
            self.opt_state = self.strategy.init_opt_state(self.tx, self.params)
        return self

    # ---------------------------------------------------------------- summary
    def summary(self):
        if self.input_shape is None:
            raise ValueError("Build the model (or fit once) before summary()")
        rows = self.module.summary_lines(self.input_shape)
        width = max(len(r[0]) for r in rows) + 2
        lines = [f"Model: {self.name}", "-" * (width + 30)]
        total = 0
        for name, shape, count in rows:
            lines.append(f"{name:<{width}}{str(shape):<22}{count}")
            total += count
        lines.append("-" * (width + 30))
        lines.append(f"Total params: {total}")
        text = "\n".join(lines)
        if jax.process_index() == 0:
            print(text)
        return text


def _generate_scan(module, bucket, temperature, top_k, policy, dtype_hints,
                   params, state, cache, padded, t_p, n_steps, key):
    """Prefill + decode as one lax.scan (jitted per static config by
    Model.generate): teacher-force tokens < t_p (a dynamic scalar, so
    prompt length never forces a recompile), sample afterwards. The scan
    spans the full bucketed length, but iterations past ``n_steps``
    (= requested max_len - 1, also dynamic) take a no-op ``lax.cond``
    branch, so runtime decode cost tracks the requested length, not the
    bucket. The caller slices off the dead tail. Under a precision policy
    the f32 master params are cast once to the compute dtype, outside the
    scan — every decode step then reads compute-dtype weights."""
    params = _cast_for_compute(policy, params, dtype_hints)

    def step(carry, t):
        def live(carry):
            cache, tok, key = carry
            logits, cache = module.decode(params, state, cache, tok[:, None],
                                          pos=t)
            key, sub = jax.random.split(key)
            sampled = Model._sample_logits(logits[:, 0], sub, temperature,
                                           top_k)
            next_tok = jnp.where(t + 1 < t_p, padded[:, t + 1], sampled)
            return (cache, next_tok, key), next_tok

        def dead(carry):
            return carry, carry[1]

        return jax.lax.cond(t < n_steps, live, dead, carry)

    _, toks = jax.lax.scan(
        step, (cache, padded[:, 0], key), jnp.arange(bucket - 1)
    )  # (bucket-1, B)
    return toks.T
