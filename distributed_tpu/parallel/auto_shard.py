"""Automatic sharding: a cost-model planner, plus Pallas kernel routing.

Two jobs live here:

1. **The auto-shard planner** (``plan_sharding`` / ``Plan`` /
   ``Feasibility``), shipped to users as
   ``model.compile(strategy="auto", hbm_cap_bytes=..., measure=False)``.
   The configuration matrix this framework grew — DP x ZeRO-1 x FSDP x TP
   x ``grad_accum`` x ``steps_per_execution`` x precision — is navigable
   by experts only; the planner picks the fastest FEASIBLE config from a
   cost model, with every input it needs already measurable through
   existing seams:

   - per-device state bytes via ``jax.eval_shape`` over the module's init
     (abstract ``ShapeDtypeStruct`` trees with the candidate strategy's
     ``params_sharding`` / ``opt_state_sharding`` attached, priced by
     ``utils.profiler.tree_bytes_per_device`` — no 30M-param tree is ever
     materialized per candidate);
   - per-step collective traffic via ``Strategy.comm_bytes_estimate``
     (unified schema across all strategies, int8/bf16-aware);
   - an HBM-cap feasibility predicate (``Feasibility``): does a
     candidate's state fit under a per-device cap;
   - a rank over survivors: estimated step seconds = compute (analytic
     FLOPs / device peak, precision-aware) + comm (bytes / link bandwidth)
     + dispatch overhead (amortized by ``steps_per_execution``). Constants
     are order-of-magnitude per backend — only RATIOS between candidates
     matter, and ties (within ``TIE_REL_TOL``) break toward more HBM
     headroom under a cap, else toward the simpler config.

   ``measure=True`` additionally times the top-k shortlist with short real
   dispatches before committing (the only path that materializes params).
   The chosen ``Plan`` — config, predicted bytes/traffic, and the pruned
   candidates' rationale — lands in ``model.last_fit_telemetry["plan"]``
   and the JSONL event log (``auto_shard_plan``).

2. **Pallas kernel routing** (``shard_rows``): XLA's SPMD partitioner
   cannot see inside a Pallas kernel, so under a sharded mesh it wraps the
   call in all-gather(inputs) -> replicated compute -> dynamic-slice
   (output): correct, but the kernel then runs the GLOBAL problem on every
   device (verified by compiling flash attention under a 'data'-sharded
   batch and finding the all-gather in the HLO). The fix is shard_map: run
   the kernel per-shard on local data, which is exactly right for
   row/batch-blocked kernels (fused xent, flash attention) whose grid
   never crosses rows. ``shard_rows(fn, arrays, specs)`` wraps fn in
   shard_map over the ambient strategy's mesh when — and only when — that
   is safe:

   - every mesh axis of size > 1 is either the strategy's batch axis or
     the Megatron 'model' axis (axes with bespoke schedules — 'pipe',
     'seq' — keep the plain path; their strategies have their own
     machinery);
   - every array dim sharded by a spec divides evenly.

   Otherwise the plain call runs and a warning says why: GSPMD
   replication is still correct, but on real chips it is an all-gather in
   front of an opaque custom call. Inside a shard_map body
   (PipelinedBlocks) the operands are already local and the plain call is
   the per-shard call — no warning.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def ambient_mesh() -> Tuple[Optional[Mesh], Optional[str], Optional[str]]:
    """(mesh, batch_axis, model_axis) from the ambient strategy scope.

    model_axis is 'model' when present in the mesh (the Megatron TP axis,
    parallel.mesh.AXES), else None. mesh is None outside any mesh strategy.
    """
    from .strategy import current_strategy

    strat = current_strategy()
    mesh = getattr(strat, "mesh", None)
    if mesh is None:
        return None, None, None
    batch_axis = getattr(strat, "axis", None)
    if batch_axis not in mesh.axis_names:
        batch_axis = None
    model_axis = "model" if "model" in mesh.axis_names else None
    return mesh, batch_axis, model_axis


def shard_rows(fn, arrays: Sequence, in_specs: Sequence[PartitionSpec],
               out_spec: PartitionSpec, *, allowed_axes=None):
    """Apply fn(*arrays) under shard_map over the ambient mesh when safe
    (see module docstring), else call it plainly — with a warning, because
    on real chips the plain call puts an all-gather in front of an opaque
    custom call.

    ``allowed_axes``: override the default {batch, model} axis allowlist —
    for callers that deliberately shard over another axis (e.g. Ulysses
    attention sharding heads over 'seq') and have already validated it."""
    mesh, batch_axis, model_axis = ambient_mesh()
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        # Off-mesh, or already inside a shard_map body (PipelinedBlocks):
        # the operands are local, the plain call IS the per-shard call.
        return fn(*arrays)
    if allowed_axes is not None:
        allowed = set(allowed_axes) | {None}
    else:
        allowed = {batch_axis, model_axis, None}
    for name in mesh.axis_names:
        if int(mesh.shape[name]) > 1 and name not in allowed:
            return _gathered_call(
                fn, arrays,
                f"mesh axis {name!r} (size {int(mesh.shape[name])}) is not "
                f"one shard_rows may shard over ({sorted(map(str, allowed))})",
            )
    for arr, spec in zip(arrays, in_specs):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            if int(mesh.shape[axis]) > 1 and arr.shape[dim] % int(
                mesh.shape[axis]
            ):
                return _gathered_call(
                    fn, arrays,
                    f"dim {dim} of shape {tuple(arr.shape)} does not divide "
                    f"over mesh axis {axis!r} (size {int(mesh.shape[axis])})",
                )
    return shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_spec,
        check_vma=False,
    )(*arrays)


def _gathered_call(fn, arrays, why: str):
    from ..utils import logging as dlog

    name = getattr(getattr(fn, "func", fn), "__name__", repr(fn))
    dlog.warning(
        f"shard_rows: {name} runs UNSHARDED on a multi-device mesh — {why}; "
        "GSPMD gathers its operands and every device computes the global "
        "problem"
    )
    return fn(*arrays)


# ===========================================================================
# The auto-shard planner (ROADMAP item 3): estimate -> prune -> rank ->
# (optionally) measure. Everything below is pure w.r.t. its inputs — same
# module/topology/knobs => byte-identical Plan (pinned by tests).
# ===========================================================================

#: Relative cost band treated as a tie (dispatch jitter on small models is
#: far larger than this; the tie-break rules below decide inside the band).
TIE_REL_TOL = 0.05

#: Analytic per-device peak FLOP/s and per-device collective bandwidth by
#: ``device_kind``. Order-of-magnitude on purpose: the cost model ranks
#: candidates for ONE model on ONE device kind, so only the relative weight
#: of compute vs comm vs dispatch matters, not the absolute seconds. A
#: device kind that is not listed is an error, never priced as another's.
_DEVICE_CONSTANTS = {
    # TPU v5e: 197 TFLOP/s bf16 (Google Cloud "TPU v5e").
    "TPU v5 lite": {"peak_flops": 2.0e14, "comm_bw": 9.0e10,
                    "dispatch_s": 5e-4, "reduced_speedup": 2.0},
    # XLA:CPU EMULATES bf16 (a mixed policy ran slower than f32 there),
    # so reduced precision gets a PENALTY there, not a speedup — the
    # planner must not recommend a policy the backend runs slower.
    "cpu": {"peak_flops": 5.0e10, "comm_bw": 1.0e10, "dispatch_s": 1.5e-3,
            "reduced_speedup": 0.85},
}

_STRATEGY_RANK = {  # simplicity order for tie-breaking (lower = simpler)
    "single_device": 0, "dp": 1, "zero1": 2, "fsdp": 3, "tp": 4, "pp": 5,
}


def _device_constants(device) -> dict:
    try:
        return _DEVICE_CONSTANTS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"auto-shard planner has no cost constants for device kind "
            f"{device.device_kind!r} (platform {device.platform!r}); known: "
            f"{sorted(_DEVICE_CONSTANTS)}"
        ) from None


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the configuration matrix the planner scores."""

    strategy: str                # single_device | dp | zero1 | fsdp | tp | pp
    model_parallel: int = 1            # > 1 only for strategy == "tp"
    pipeline_parallel: int = 1         # > 1 only for strategy == "pp"
    num_microbatches: int = 1          # pipeline schedule M (pp only)
    precision: Optional[str] = None    # None | precision preset name
    grad_accum: int = 1
    steps_per_execution: int = 1

    def label(self) -> str:
        parts = [self.strategy]
        if self.model_parallel > 1:
            parts[-1] += f"{self.model_parallel}"
        if self.pipeline_parallel > 1:
            parts[-1] += f"{self.pipeline_parallel}"
            parts.append(f"m{self.num_microbatches}")
        if self.precision:
            parts.append(self.precision)
        if self.grad_accum > 1:
            parts.append(f"accum{self.grad_accum}")
        if self.steps_per_execution > 1:
            parts.append(f"k{self.steps_per_execution}")
        return "/".join(parts)

    def config(self) -> dict:
        return {
            "strategy": self.strategy,
            "model_parallel": self.model_parallel,
            "pipeline_parallel": self.pipeline_parallel,
            "num_microbatches": self.num_microbatches,
            "precision": self.precision,
            "grad_accum": self.grad_accum,
            "steps_per_execution": self.steps_per_execution,
        }

    def complexity(self) -> tuple:
        """Tie-break key: simpler configs sort first."""
        return (
            _STRATEGY_RANK.get(self.strategy, 99),
            self.model_parallel,
            self.pipeline_parallel,
            self.num_microbatches,
            0 if self.precision is None else 1,
            self.grad_accum,
            self.steps_per_execution,
        )

    def build_strategy(self, devices=None):
        """Instantiate the concrete Strategy for this candidate over
        ``devices`` (default: all local devices)."""
        from . import strategy as S

        devices = list(devices) if devices is not None else list(jax.devices())
        if self.strategy == "single_device":
            return S.SingleDevice(devices[0])
        if self.strategy == "dp":
            return S.DataParallel(devices)
        if self.strategy == "zero1":
            return S.ZeroDataParallel(devices)
        if self.strategy == "fsdp":
            return S.FSDP(devices)
        if self.strategy == "tp":
            return S.DataTensorParallel(
                devices, model_parallel=self.model_parallel
            )
        if self.strategy == "pp":
            return S.DataPipelineParallel(
                devices, pipeline_parallel=self.pipeline_parallel,
                num_microbatches=self.num_microbatches,
            )
        raise ValueError(f"unknown candidate strategy {self.strategy!r}")


class Feasibility:
    """Reusable HBM-cap predicate: a replicated state over the cap
    cannot train, a sharded one under it can
    (tests/test_autoshard.py). ``check`` returns None when the
    candidate fits, else a human-readable pruning reason recorded in the
    Plan."""

    def __init__(self, hbm_cap_bytes: Optional[int] = None):
        self.hbm_cap_bytes = (
            int(hbm_cap_bytes) if hbm_cap_bytes is not None else None
        )

    def check(self, state_bytes_per_device: int,
              activation_bytes_per_device: int = 0) -> Optional[str]:
        if self.hbm_cap_bytes is None:
            return None
        need = int(state_bytes_per_device) + int(activation_bytes_per_device)
        if need <= self.hbm_cap_bytes:
            return None
        return (
            f"needs {need} bytes/device (state {int(state_bytes_per_device)}"
            f" + activations {int(activation_bytes_per_device)}) "
            f"> hbm_cap {self.hbm_cap_bytes}"
        )


@dataclasses.dataclass
class Plan:
    """The planner's decision record: the chosen config + its predicted
    numbers, every candidate's row, and the rationale for pruned ones.
    ``summary()`` is the JSON-safe dict that lands in
    ``model.last_fit_telemetry["plan"]`` and the JSONL event log
    (``auto_shard_plan``)."""

    chosen: dict
    candidates: List[dict]
    pruned: List[dict]
    devices: int
    backend: str
    batch_size: int
    n_params: int
    hbm_cap_bytes: Optional[int]
    measured: Optional[List[dict]] = None
    tie_break: Optional[str] = None

    def chosen_candidate(self) -> Candidate:
        return Candidate(**self.chosen["config"])

    def summary(self) -> dict:
        return {
            "chosen": self.chosen,
            "devices": self.devices,
            "backend": self.backend,
            "batch_size": self.batch_size,
            "n_params": self.n_params,
            "hbm_cap_bytes": self.hbm_cap_bytes,
            "candidates": self.candidates,
            "pruned": self.pruned,
            "measured": self.measured,
            "tie_break": self.tie_break,
        }


# ------------------------------------------------------------ abstraction --
def abstract_model_state(module, input_shape, tx, *, seed: int = 0) -> dict:
    """Abstract (ShapeDtypeStruct) params/state/opt-state of ``module`` +
    ``tx`` via ``jax.eval_shape`` — the dry-run twin of Model.build that
    costs shapes, not HBM. One call serves every candidate (shapes don't
    depend on the strategy)."""
    key = jax.random.PRNGKey(seed)
    params, state = jax.eval_shape(
        lambda k: module.init(k, tuple(input_shape))[:2], key
    )
    opt = jax.eval_shape(tx.init, params)
    n_params = sum(
        int(np.prod(l.shape, dtype=np.int64))
        for l in jax.tree_util.tree_leaves(params)
    )
    return {
        "params": params,
        "state": state,
        "opt": opt,
        "hints": module.sharding_hints(),
        "n_params": n_params,
    }


def probe_forward(module, params, state, input_shape, batch_size: int):
    """Abstract forward probe: ``(x_dtype, logits ShapeDtypeStruct)``.
    Tries float32 input first (images/features), then int32 (token
    models — a float index makes the embedding gather raise at trace
    time, which is the detection)."""
    import jax.numpy as jnp

    last_err = None
    for dtype in (jnp.float32, jnp.int32):
        x = jax.ShapeDtypeStruct((int(batch_size),) + tuple(input_shape),
                                 dtype)
        try:
            logits = jax.eval_shape(
                lambda p, s, xx: module.apply(p, s, xx, train=False)[0],
                params, state, x,
            )
            return dtype, logits
        except Exception as e:  # wrong input dtype (or rank) for this model
            last_err = e
    raise TypeError(
        f"could not trace {type(module).__name__} abstractly with float32 "
        f"or int32 input of shape {tuple(input_shape)}: {last_err}"
    )


def _attach_shardings(tree, sharding_tree):
    """ShapeDtypeStructs with shardings attached, for
    tree_bytes_per_device's abstract path. ``sharding_tree=None`` (the
    SingleDevice case) leaves leaves bare — counted once."""
    if sharding_tree is None:
        return tree
    return jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        tree, sharding_tree,
    )


# -------------------------------------------------------------- estimation --
def _check_divisibility(cand: Candidate, n_devices: int, batch_size: int,
                        abstracts: dict) -> Optional[str]:
    """Structural feasibility: batch math and TP/PP shard divisibility.
    Returns a pruning reason or None."""
    if cand.strategy != "single_device" and n_devices % cand.model_parallel:
        return (f"{n_devices} devices not divisible by model_parallel="
                f"{cand.model_parallel}")
    if n_devices % cand.pipeline_parallel:
        return (f"{n_devices} devices not divisible by pipeline_parallel="
                f"{cand.pipeline_parallel}")
    replicas = (
        1 if cand.strategy == "single_device"
        else n_devices // (cand.model_parallel * cand.pipeline_parallel)
    )
    if batch_size % cand.grad_accum:
        return (f"grad_accum={cand.grad_accum} does not divide the global "
                f"batch {batch_size}")
    micro = batch_size // cand.grad_accum
    if micro % replicas:
        return (f"microbatch {micro} not divisible by {replicas} replicas")
    if cand.strategy == "tp":
        m = cand.model_parallel
        bad = _tp_indivisible(abstracts["params"], abstracts["hints"], m)
        if bad:
            return (f"TP shard dim {bad[1]} of {bad[0]} not divisible by "
                    f"model_parallel={m}")
    if cand.strategy == "pp":
        pp = cand.pipeline_parallel
        stages = _pipe_stage_count(abstracts["params"], abstracts["hints"])
        if stages is None:
            return "no 'pipe'-hinted stacks to place stages from"
        if stages % pp:
            return (f"{stages} pipeline stages not divisible by "
                    f"pipeline_parallel={pp}")
        per_replica = micro // max(replicas, 1)
        if per_replica % cand.num_microbatches:
            return (f"per-replica batch {per_replica} not divisible by "
                    f"num_microbatches={cand.num_microbatches}")
    return None


def _pipe_stage_count(params, hints) -> Optional[int]:
    """Leading (stage) dim of the first 'pipe'-hinted leaf — the number of
    schedulable stages a PipelinedBlocks stack exposes. None when nothing
    is pipe-hinted (the module has no pipeline stack to place)."""

    def walk(p, h):
        if isinstance(p, dict):
            for k, v in p.items():
                hit = walk(v, h.get(k, {}) if isinstance(h, dict) else h)
                if hit is not None:
                    return hit
            return None
        shape = tuple(getattr(p, "shape", ()))
        if h == "pipe" and shape:
            return int(shape[0])
        return None

    return walk(params, hints or {})


def _tp_indivisible(params, hints, m: int):
    """First (path, shape) whose hinted TP dim doesn't divide by ``m``."""

    def walk(p, h, path):
        if isinstance(p, dict):
            for k, v in p.items():
                hit = walk(v, h.get(k, {}) if isinstance(h, dict) else h,
                           path + (k,))
                if hit:
                    return hit
            return None
        role = h if isinstance(h, str) else None
        shape = tuple(getattr(p, "shape", ()))
        dim = None
        if role == "col" and shape:
            dim = shape[-1]
        elif role == "row" and shape:
            dim = shape[0]
        elif role == "row1" and len(shape) >= 2:
            dim = shape[1]
        if dim is not None and dim % m:
            return ("/".join(path), shape)
        return None

    return walk(params, hints or {}, ())


def estimate_candidate(cand: Candidate, ctx: dict) -> dict:
    """One candidate's predicted row: per-device state/activation bytes,
    per-step comm traffic, and the cost-model step seconds. Pure
    arithmetic over the shared abstract trees — nothing is placed."""
    from .. import precision as precision_lib
    from ..utils.profiler import tree_bytes_per_device

    abstracts, devices = ctx["abstracts"], ctx["devices"]
    consts = ctx["consts"]
    batch_size, tokens = ctx["batch_size"], ctx["tokens"]
    strat = cand.build_strategy(devices)
    hints = abstracts["hints"]
    policy = precision_lib.get(cand.precision)
    compute_dtype = policy.compute_dtype if policy is not None else None
    compute_itemsize = (
        policy.compute_itemsize if policy is not None else 4
    )

    from .strategy import _params_sharding_tree

    params_sh = _params_sharding_tree(strat, abstracts["params"], hints)
    state_sh = _params_sharding_tree(strat, abstracts["state"], None)
    opt_sh = strat.opt_state_sharding(
        abstracts["opt"], abstracts["params"], hints
    )
    trees = [
        _attach_shardings(abstracts["params"], params_sh),
        _attach_shardings(abstracts["state"], state_sh),
        _attach_shardings(abstracts["opt"], opt_sh),
    ]
    if cand.grad_accum > 1:
        # The in-jit accumulation scan carries an f32 params-shaped
        # gradient accumulator, placed like the params.
        acc = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jax.numpy.float32),
            abstracts["params"],
        )
        trees.append(_attach_shardings(acc, params_sh))
    state_bytes = tree_bytes_per_device(*trees)["max_bytes_per_device"]

    replicas = int(getattr(strat, "num_replicas_in_sync", 1))
    n_active = 1 if cand.strategy == "single_device" else len(devices)
    tokens_local = max(tokens // max(replicas, 1), 1)

    # Coarse activation proxy — the two tensors knowable without tracing
    # the module's internals: the input and the logits (whose cotangent
    # doubles them in backward), per microbatch, plus the staged
    # super-batch when steps_per_execution stacks K inputs on device.
    input_bytes = ctx["input_bytes"]
    logits_bytes = ctx["logits_elems"] * compute_itemsize
    act_bytes = (
        (input_bytes + 2 * logits_bytes)
        // max(replicas, 1) // cand.grad_accum
        + input_bytes * cand.steps_per_execution // max(replicas, 1)
    )

    comm = strat.comm_bytes_estimate(
        abstracts["params"], compute_dtype=compute_dtype, hints=hints
    )
    # Per optimizer step: FSDP/ZeRO gathers repeat per microbatch; the
    # gradient reduce happens once on the accumulated value; TP's
    # activation all-reduces total the same tokens regardless of M.
    comm_bytes = (
        comm["gathered_param_bytes_per_device"] * cand.grad_accum
        + comm["grad_reduce_bytes_per_device"]
        + comm["activation_reduce_bytes_per_token_per_device"] * tokens_local
        + comm.get("pipeline_hop_bytes_per_token_per_device", 0)
        * tokens_local
    )

    flops = 6.0 * abstracts["n_params"] * tokens
    speed = consts["peak_flops"] * n_active
    if compute_dtype is not None and compute_itemsize < 4:
        speed *= consts["reduced_speedup"]
    compute_s = flops / speed
    if cand.model_parallel > 1:
        # Megatron splitting narrows every sharded matmul's contraction or
        # output dim by the TP factor, dropping arithmetic efficiency
        # (under-filled MXU tiles, per-layer blocking all-reduces on the
        # critical path) — the standard reason TP is sized to the minimum
        # that fits, not the maximum available. Priced as a +15% compute
        # penalty per doubling of the TP factor.
        compute_s *= 1.0 + 0.15 * float(np.log2(cand.model_parallel))
    if cand.pipeline_parallel > 1:
        # GPipe bubble: of M+n-1 schedule ticks only M do useful work per
        # stage, so devices idle a (n-1)/(M+n-1) fraction of the step —
        # the planner prices pipelining as slower at equal memory, picking
        # it only when flat layouts are pruned (the design intent: PP is
        # the capacity axis of last resort, like TP's efficiency penalty).
        m_pipe = max(int(cand.num_microbatches), 1)
        compute_s *= (m_pipe + cand.pipeline_parallel - 1) / m_pipe
    comm_s = comm_bytes / consts["comm_bw"]
    dispatch_s = consts["dispatch_s"] / cand.steps_per_execution
    return {
        "config": cand.config(),
        "label": cand.label(),
        "state_bytes_per_device": int(state_bytes),
        "activation_bytes_per_device": int(act_bytes),
        "comm_bytes_per_step_per_device": int(comm_bytes),
        "comm_bytes_estimate": comm,
        "est_step_seconds": compute_s + comm_s + dispatch_s,
        "cost_breakdown": {
            "compute_s": compute_s,
            "comm_s": comm_s,
            "dispatch_s": dispatch_s,
        },
    }


# -------------------------------------------------------------- enumeration --
def enumerate_candidates(
    n_devices: int,
    *,
    hints=None,
    precisions: Sequence[Optional[str]] = (None,),
    grad_accums: Sequence[int] = (1, 2, 4),
    steps_per_execution: Sequence[int] = (1, 8),
    include_tp: bool = True,
    include_pp: bool = True,
) -> List[Candidate]:
    """The candidate matrix for a device count: strategies x precision x
    grad_accum x steps_per_execution. TP mesh shapes come from the
    divisors of the device count and are proposed only when the module
    carries Megatron sharding hints (an unhinted model would shard
    nothing); PP stage counts likewise come from the divisors and are
    proposed only when the hints carry a 'pipe' role (a PipelinedBlocks
    stack), each at microbatch counts M in {n, 2n} — the bubble/MXU
    trade's two canonical points."""
    strategies: List[Tuple[str, int, int, int]] = []  # (name, tp, pp, M)
    if n_devices == 1:
        strategies.append(("single_device", 1, 1, 1))
    else:
        strategies += [("single_device", 1, 1, 1), ("dp", 1, 1, 1),
                       ("zero1", 1, 1, 1), ("fsdp", 1, 1, 1)]
        if include_tp and hints:
            for m in range(2, n_devices + 1):
                if n_devices % m == 0:
                    strategies.append(("tp", m, 1, 1))
        if include_pp and _hints_have_pipe(hints):
            for pp in range(2, n_devices + 1):
                if n_devices % pp == 0:
                    for mb in (pp, 2 * pp):
                        strategies.append(("pp", 1, pp, mb))
    out = []
    for name, m, pp, mb in strategies:
        for prec in precisions:
            for ga in grad_accums:
                for k in steps_per_execution:
                    out.append(Candidate(
                        strategy=name, model_parallel=m,
                        pipeline_parallel=pp, num_microbatches=mb,
                        precision=prec,
                        grad_accum=int(ga), steps_per_execution=int(k),
                    ))
    return out


def _hints_have_pipe(hints) -> bool:
    """True when any node of the hint tree carries the 'pipe' role."""
    if hints == "pipe":
        return True
    if isinstance(hints, dict):
        return any(_hints_have_pipe(v) for v in hints.values())
    return False


# ------------------------------------------------------------------ planning --
def plan_sharding(
    module,
    input_shape,
    *,
    tx=None,
    optimizer="adam",
    batch_size: int = 32,
    devices=None,
    hbm_cap_bytes: Optional[int] = None,
    precisions: Optional[Sequence[Optional[str]]] = None,
    grad_accums: Optional[Sequence[int]] = None,
    steps_per_execution: Optional[Sequence[int]] = None,
    include_tp: bool = True,
    include_pp: bool = True,
    measure: bool = False,
    measure_fn: Optional[
        Callable[[Candidate, dict], Optional[float]]
    ] = None,
    top_k: int = 3,
    seed: int = 0,
) -> Plan:
    """Plan the fastest feasible sharding config for ``module`` on the
    live topology. Deterministic for fixed inputs (measure=False).

    ``tx``: the optax transform whose state is being priced (defaults to
    ``optim.get(optimizer)``). ``precisions`` defaults backend-aware:
    ``(None, "mixed_bfloat16")`` on accelerators, ``(None,)`` on XLA:CPU
    (which emulates bf16 and runs it slower than f32: the planner
    must not recommend what the backend loses on). ``measure=True``
    times the ``top_k`` estimate-ranked survivors with ``measure_fn``
    (seconds per step, or None to skip one candidate) and commits to the
    fastest measured."""
    from .. import optim

    devices = list(devices) if devices is not None else list(jax.devices())
    backend = devices[0].platform
    consts = _device_constants(devices[0])
    if tx is None:
        tx = optim.get(optimizer)
    if precisions is None:
        precisions = (
            (None, "mixed_bfloat16") if backend == "tpu" else (None,)
        )
    if grad_accums is None:
        grad_accums = (1, 2, 4)
    if steps_per_execution is None:
        steps_per_execution = (1, 8)

    abstracts = abstract_model_state(module, input_shape, tx, seed=seed)
    x_dtype, logits = probe_forward(
        module, abstracts["params"], abstracts["state"], input_shape,
        batch_size,
    )
    tokens = int(np.prod(logits.shape[:-1], dtype=np.int64))
    ctx = {
        "abstracts": abstracts,
        "devices": devices,
        "consts": consts,
        "batch_size": int(batch_size),
        "tokens": tokens,
        "input_bytes": int(
            np.prod((batch_size,) + tuple(input_shape), dtype=np.int64)
        ) * jax.numpy.dtype(x_dtype).itemsize,
        "logits_elems": int(np.prod(logits.shape, dtype=np.int64)),
        "logits_shape": tuple(logits.shape),
        "x_dtype": x_dtype,
    }

    feasibility = Feasibility(hbm_cap_bytes)
    candidates = enumerate_candidates(
        len(devices), hints=abstracts["hints"], precisions=precisions,
        grad_accums=grad_accums, steps_per_execution=steps_per_execution,
        include_tp=include_tp, include_pp=include_pp,
    )
    feasible, pruned = [], []
    for cand in candidates:
        reason = _check_divisibility(cand, len(devices), batch_size,
                                     abstracts)
        if reason is not None:
            pruned.append({"config": cand.config(), "label": cand.label(),
                           "reason": reason})
            continue
        row = estimate_candidate(cand, ctx)
        reason = feasibility.check(
            row["state_bytes_per_device"],
            row["activation_bytes_per_device"],
        )
        if reason is not None:
            row["reason"] = reason
            pruned.append(row)
        else:
            row["reason"] = None
            feasible.append((cand, row))
    if not feasible:
        raise ValueError(
            "auto-shard planner found NO feasible candidate under "
            f"hbm_cap_bytes={hbm_cap_bytes} for batch {batch_size}: "
            + "; ".join(f"{p['label']}: {p['reason']}" for p in pruned[:6])
        )

    # Rank: cost ascending; inside the tie band prefer more HBM headroom
    # when a cap binds (activations/fragmentation live in the slack), else
    # the simpler config.
    feasible.sort(key=lambda cr: cr[1]["est_step_seconds"])
    best_cost = feasible[0][1]["est_step_seconds"]
    band = [
        cr for cr in feasible
        if cr[1]["est_step_seconds"] <= best_cost * (1.0 + TIE_REL_TOL)
    ]
    if hbm_cap_bytes is not None and len(band) > 1:
        band.sort(key=lambda cr: (cr[1]["state_bytes_per_device"],
                                  cr[0].complexity()))
        tie_break = "hbm_headroom"
    else:
        band.sort(key=lambda cr: cr[0].complexity())
        tie_break = "simplicity"
    ordered = band + [cr for cr in feasible if cr not in band]

    measured_rows = None
    if measure and measure_fn is not None:
        shortlist = ordered[: max(1, int(top_k))]
        measured_rows = []
        timed = []
        for cand, row in shortlist:
            secs = measure_fn(cand, ctx)
            measured_rows.append({
                "config": cand.config(), "label": cand.label(),
                "seconds_per_step": secs,
            })
            if secs is not None:
                timed.append((secs, cand, row))
        if timed:
            timed.sort(key=lambda t: t[0])
            _, cand0, row0 = timed[0]
            ordered = (
                [(cand0, row0)]
                + [cr for cr in ordered if cr[0] is not cand0]
            )
            tie_break = "measured"

    chosen_cand, chosen_row = ordered[0]
    plan = Plan(
        chosen=chosen_row,
        candidates=[r for _, r in ordered],
        pruned=pruned,
        devices=len(devices),
        backend=backend,
        batch_size=int(batch_size),
        n_params=abstracts["n_params"],
        hbm_cap_bytes=(
            int(hbm_cap_bytes) if hbm_cap_bytes is not None else None
        ),
        measured=measured_rows,
        tie_break=tie_break,
    )
    plan._ctx = ctx  # probe results, for Model's measure path
    return plan
