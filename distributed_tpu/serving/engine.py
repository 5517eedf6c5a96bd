"""Serving engine: continuous batching over a paged KV cache.

``Engine(model, max_slots, block_size)`` turns a built token LM into a
synchronous serving loop (``engine.run(requests)``) built from three
pieces:

- **Continuous batching** (``serving.scheduler``): requests are admitted
  into decode SLOTS the moment one frees up — per decode step, not per
  static batch — and finished sequences release their slot and KV blocks
  immediately. Under heterogeneous prompt/response lengths this is the
  throughput lever: the static ``generate()`` batch decodes until its
  LAST member finishes, so early finishers burn slots as padding.
- **Paged KV cache** (``serving.kv_cache`` +
  ``nn.MultiHeadAttention.paged_decode``): one HBM pool of fixed-size
  blocks shared by all slots, allocated on demand and freed on eviction,
  with the cache dtype derived from the model's precision policy
  (``Model.decode_dtype()``). Stacked-block models (``ScannedBlocks``,
  and ``PipelinedBlocks`` on its sequential off-mesh path) serve through
  the same pools, stacked per layer under one reserved ``"stacked"`` key
  (``nn.scan.STACKED_POOL_KEY``) — a LIVE pipe mesh raises instead
  (docs/SERVING.md "Stacked blocks").
- **Prefill/decode split**: a prompt is cached by its own PARALLEL
  dispatch (optionally chunked via ``prefill_chunk``, which bounds how
  much work ever sits between two decode steps) instead of crawling
  through the one-token decode path; the decode loop for already-running
  sequences proceeds between prefill chunks.

The decode step is ONE jitted function over fixed shapes — ``(S,)``
tokens, ``(S, nb)`` block tables, ``(S,)`` positions — so there is no
per-step recompile however the batch composition churns; the scheduler
expresses admissions/evictions purely by editing the host-side tables
(dead or mid-prefill slots point at the trash block, à la the
``steps_per_execution`` carry discipline of keeping the compiled program
fixed and moving the bookkeeping to the host).

Telemetry rides the existing ``StepTimer.attribute`` stall keys:
``queue_wait`` (request admission waits), ``prefill`` / ``decode``
(dispatch walls), plus ``kv_utilization`` (mean/peak block-pool
occupancy) in ``engine.last_run_telemetry``.

Sampled decode is deterministic PER REQUEST: token keys derive from
(engine seed, request seed, token index) alone, so rollouts with pinned
seeds are bit-identical across runs, ``max_slots``, and preemption
histories; ``run(return_logprobs=True)`` additionally captures each
token's sampling logprob (computed in the fixed dispatch either way —
the toggle never recompiles). ``update_weights(params)`` hot-swaps the
served weights between decode steps under a documented staleness
contract (docs/RL.md): in-flight sequences keep their KV, and the
``weights_version`` boundary is recorded per token row.
"""

from __future__ import annotations

import functools
import time
from typing import List, Optional, Sequence as SequenceT

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import registry as obs_registry
from ..obs import spans as obs_spans
from ..optim import EmaBaseline
from ..training.model import Model, _cast_for_compute
from ..utils import event_schema as evs
from ..utils import events as events_lib
from ..utils.profiler import StepTimer
from .kv_cache import PagedKVCache
from .scheduler import Request, Scheduler


_M64 = (1 << 64) - 1

#: Adaptive speculative-k ladder: the ONLY verify widths an adaptive
#: engine ever dispatches (0 = draft off for that tenant, plain decode).
#: A fixed ladder is what keeps batch churn recompile-free — at most one
#: trace per rung of the one _verify_jit, never one per batch mix.
SPEC_K_LADDER = (0, 2, 4, 8)
#: Cold-start k for a tenant with no accept-rate evidence yet: explore
#: at mid-ladder rather than assuming the draft wins (8) or loses (0).
SPEC_K_DEFAULT = 4
#: Per-tenant accept-rate EMA decay (optim.EmaBaseline: first update
#: adopts outright) and the observation floor before the ladder reacts —
#: one unlucky round must not permanently disable a good draft.
SPEC_EMA_DECAY = 0.7
SPEC_MIN_ROUNDS = 2


def _ladder_k(accept_ema: float) -> int:
    """Ladder rung for an accept-rate EMA: the break-even thresholds of
    docs/SERVING.md "Draft models & gossip": below 0.25 the draft's dispatch
    cost exceeds the verify savings at ANY k, so it switches off."""
    if accept_ema < 0.25:
        return 0
    if accept_ema < 0.5:
        return 2
    if accept_ema < 0.75:
        return 4
    return 8


def _validate_swap(ref_params, params, label: str) -> None:
    """Hot-swap gate shared by ``Engine.update_weights`` (target and
    draft arms) and ``fleet.ServingFleet.update_weights``: tree
    structure, leaf shapes AND dtypes must match the served tree exactly
    — a mismatch would silently retrace the fixed decode dispatch, so it
    raises ``ValueError`` loudly instead."""
    ref_paths = jax.tree_util.tree_leaves_with_path(ref_params)
    ref_struct = jax.tree_util.tree_structure(ref_params)
    got_struct = jax.tree_util.tree_structure(params)
    if ref_struct != got_struct:
        raise ValueError(
            f"{label}: new param tree structure does not match "
            f"the served tree: {got_struct} vs {ref_struct}"
        )
    for (kpath, have), want in zip(
        ref_paths, jax.tree_util.tree_leaves(params)
    ):
        if tuple(have.shape) != tuple(getattr(want, "shape", ())):
            raise ValueError(
                f"{label}: shape mismatch at "
                f"{jax.tree_util.keystr(kpath)}: new weights have "
                f"{tuple(getattr(want, 'shape', ()))}, engine serves "
                f"{tuple(have.shape)}"
            )
        if jnp.dtype(jnp.result_type(want)) != jnp.dtype(have.dtype):
            raise ValueError(
                f"{label}: dtype mismatch at "
                f"{jax.tree_util.keystr(kpath)}: new weights are "
                f"{jnp.result_type(want)}, engine serves {have.dtype} "
                "(a dtype change would retrace the fixed decode "
                "dispatch)"
            )


def _mix_seed(engine_seed: int, request_seed: int) -> int:
    """One 64-bit mix of (engine seed, request seed) — the per-request
    sampling-stream identity. Pure host arithmetic so deriving a key never
    costs a device dispatch."""
    return (
        (int(engine_seed) + 1) * 0xD1342543DE82EF95
        + (int(request_seed) + 1) * 0x9E3779B97F4A7C15
    ) & _M64


def _token_key(sample_seed: int, index: int) -> np.ndarray:
    """Deterministic uint32[2] sampling key for generated-token ``index``
    of the request identified by ``sample_seed`` (splitmix64 finalizer
    over the pair). The key depends on NOTHING else — not the slot, not
    the decode step the scheduler ran, not ``max_slots`` — which is what
    makes sampled rollouts bit-reproducible across runs and engine
    shapes."""
    x = (
        int(sample_seed) + (int(index) + 1) * 0xBF58476D1CE4E5B9
    ) & _M64
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & _M64
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & _M64
    x ^= x >> 31
    return np.array([x >> 32, x & 0xFFFFFFFF], np.uint32)


def _sample_with_logprob(logits, keys, temperature, top_k):
    """Sample every slot's next token AND its sampling logprob in one
    pass: ``logits`` (S, V), ``keys`` (S, 2) per-slot uint32 key data.
    The logprob is under the distribution actually sampled from —
    top_k-truncated, temperature-scaled softmax (raw softmax when greedy:
    temperature <= 0 takes the argmax, and its reported logprob is the
    token's unscaled log-likelihood, the reference-scoring convention).
    Computed unconditionally: one (S, V) log_softmax rides free next to
    the matmuls that produced the logits, so toggling host-side capture
    (``run(return_logprobs=...)``) never changes the compiled program."""
    logits = logits.astype(jnp.float32)
    if top_k is not None:
        k = min(int(top_k), logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    t = float(temperature) if temperature > 0.0 else 1.0
    scaled = logits / jnp.float32(t)
    logp_all = jax.nn.log_softmax(scaled, axis=-1)
    if temperature <= 0.0:
        toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        toks = jax.vmap(jax.random.categorical)(keys, scaled).astype(
            jnp.int32
        )
    logp = jnp.take_along_axis(logp_all, toks[:, None], axis=-1)[:, 0]
    return toks, logp


def _prefill_dispatch(module, temperature, top_k, policy, dtype_hints,
                      params, state, caches, tokens, block_table, start,
                      last_idx, key):
    """One prompt-chunk prefill for one sequence: tokens (1, Cb) covering
    absolute positions [start, start+Cb) (right-padded past the real
    chunk), KV scattered into the sequence's blocks, and the next token
    (plus its sampling logprob) sampled from the last REAL position's
    logits (meaningful only on the final chunk; earlier chunks' samples
    are discarded host-side)."""
    params = _cast_for_compute(policy, params, dtype_hints)
    out, caches = module.paged_prefill(
        params, state, caches, tokens, block_table=block_table, start=start
    )
    last = jax.lax.dynamic_slice_in_dim(out[0], last_idx, 1, axis=0)
    tok, logp = _sample_with_logprob(last, key[None], temperature, top_k)
    return tok[0], logp[0], caches


def _decode_dispatch(module, temperature, top_k, policy, dtype_hints,
                     params, state, caches, tokens, block_tables, positions,
                     keys):
    """One continuous-batching decode step over every slot: tokens (S,),
    per-slot block tables, positions, and sampling keys. Slots not
    currently decoding carry all-trash tables, so their scatter writes
    are harmless and their sampled tokens are ignored by the
    scheduler."""
    params = _cast_for_compute(policy, params, dtype_hints)
    logits, caches = module.paged_decode(
        params, state, caches, tokens[:, None],
        block_tables=block_tables, positions=positions,
    )
    sampled, logp = _sample_with_logprob(
        logits[:, 0], keys, temperature, top_k
    )
    return sampled, logp, caches


def _verify_dispatch(module, temperature, top_k, policy, dtype_hints,
                     params, state, caches, tokens, block_tables, positions,
                     keys):
    """One speculative VERIFY step over every slot: tokens (S, K) — per
    slot, its real last token followed by K-1 draft proposals — scored by
    the target model in one fixed-shape dispatch (``paged_verify``).
    Column j's sampled token is exactly what K=1 decode would have
    produced after accepting columns < j, and ``keys`` (S, K, 2) carries
    the per-GENERATED-TOKEN-INDEX sampling keys (PR 12 derivation), so
    accepted sampled tokens are bit-identical to the vanilla stream. The
    host-side acceptance walk decides how many columns commit; slots not
    speculating ride all-trash tables exactly as in decode."""
    params = _cast_for_compute(policy, params, dtype_hints)
    logits, caches = module.paged_verify(
        params, state, caches, tokens,
        block_tables=block_tables, positions=positions,
    )
    s, kw, v = logits.shape
    sampled, logp = _sample_with_logprob(
        logits.reshape(s * kw, v), keys.reshape(s * kw, 2),
        temperature, top_k,
    )
    return sampled.reshape(s, kw), logp.reshape(s, kw), caches


class _PairedKV:
    """Target + draft paged caches moving in lockstep through the
    scheduler seams (admit/reserve/release) so a speculating engine's two
    pools can never drift: a slot holds blocks in BOTH or NEITHER.

    The draft pool reserves first (it is fully provisioned, so in
    practice it never fails) and the target second; on a target-side
    admission failure the draft's adoption is rolled back. A draft
    over-reservation left by a failed target ``reserve`` is harmless —
    the blocks are already table-mapped for the slot and are consumed by
    the retry or dropped by the release that follows preemption."""

    def __init__(self, target: PagedKVCache, draft: PagedKVCache):
        self.target = target
        self.draft = draft

    def blocks_for(self, tokens: int) -> int:
        return self.target.blocks_for(tokens)

    def admit(self, slot: int, tokens):
        if not self.draft.reserve(slot, len(tokens)):
            return None
        cached = self.target.admit(slot, tokens)
        if cached is None:
            self.draft.release(slot)
            return None
        return cached

    def reserve(self, slot: int, upto_len: int) -> bool:
        if not self.draft.reserve(slot, upto_len):
            return False
        return self.target.reserve(slot, upto_len)

    def release(self, slot: int) -> None:
        self.target.release(slot)
        self.draft.release(slot)


class Engine:
    """Synchronous continuous-batching serving loop for a built token LM.

    ``max_slots``: decode-batch width (the fixed S of the jitted step).
    ``block_size``: KV-cache block granularity in positions.
    ``max_len``: per-sequence context cap (prompt + generated); sizes the
    block tables. ``num_blocks``: total pool blocks INCLUDING the
    reserved trash block — default fully provisions
    ``max_slots * ceil(max_len/block_size) + 1`` (no paging pressure);
    set it lower to serve more slots than worst-case HBM would allow,
    at the cost of possible preemptions. ``prefill_chunk``: cache prompts
    in chunks of at most this many positions per dispatch (None = whole
    prompt in one dispatch), bounding how long a long prompt can ever
    delay the running batch's next decode step.

    Sampling mirrors ``generate()``: ``temperature=0`` greedy (the
    configuration whose outputs are token-identical to per-request
    ``generate()``), ``top_k`` truncation otherwise; ``eos_id`` stops a
    sequence early when sampled.

    Memory-economy levers (docs/SERVING.md "Prefix caching & speculative
    decoding"), each off by default and token-exact when on:

    ``prefix_cache=True``: content-addressed sharing of full prompt
    blocks across requests — N requests with a common leading span store
    and prefill it once (refcounted blocks, copy-on-write on divergence,
    refcount-aware LRU eviction under pool pressure).
    ``kv_dtype="int8"``: quantized KV pools (~4x fewer bytes than f32,
    so ~4x the concurrent slots per HBM byte) with per-(position, head)
    dynamic scales; fidelity-gated rather than bit-exact — see the
    int8-KV contract in docs/SERVING.md.
    ``draft_model`` + ``spec_k``: speculative decoding — the draft
    proposes ``spec_k - 1`` greedy tokens per slot and the target scores
    all ``spec_k`` candidates in ONE fixed-shape verify dispatch,
    committing the longest agreeing run (1..spec_k tokens per dispatch;
    token-exact, greedy or sampled, because verification samples each
    position with the same per-token-index key vanilla decode would
    use). The draft must be a built LM over the same vocabulary; it
    keeps its own fully-provisioned paged cache and re-prefills fully on
    (re-)admission. ``spec_k="adaptive"`` lets per-tenant accept-rate
    EMAs pick each round's k from ``SPEC_K_LADDER`` — speculation turns
    itself off (k=0) for tenants where the draft loses — with headroom
    reserved at the ladder max and every width a fixed shape, so tenant
    churn never recompiles.
    """

    def __init__(self, model: Model, max_slots: int, block_size: int, *,
                 max_len: int = 512, num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 eos_id: Optional[int] = None, seed: int = 0,
                 prefix_cache: bool = False, kv_dtype=None,
                 draft_model: Optional[Model] = None, spec_k: int = 4,
                 decode_kernel: str = "reference"):
        if not model.built:
            raise RuntimeError("Model not built")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.model = model
        self.max_slots = int(max_slots)
        self.block_size = int(block_size)
        self.max_len = int(max_len)
        self.prefill_chunk = (
            int(prefill_chunk) if prefill_chunk is not None else None
        )
        self.temperature = float(temperature)
        self.top_k = top_k
        self.eos_id = eos_id
        self.seed = int(seed)
        # Decode-kernel selection: 'reference' keeps the _paged_view +
        # dense-attention path; 'fused' traces the decode and verify
        # dispatches through the fused Pallas gather+attention kernel
        # (ops.paged_attention — token-parity pinned in tests; the
        # throughput on the chip is not measured yet). Prefill is
        # chunk-parallel, not table-bound, and always uses the reference
        # path.
        from ..ops import paged_attention as paged_ops
        if decode_kernel not in paged_ops.KINDS:
            raise ValueError(
                f"decode_kernel must be one of {paged_ops.KINDS}, got "
                f"{decode_kernel!r}"
            )
        self.decode_kernel = decode_kernel
        self._paged_ops = paged_ops
        # Served weights are an engine-owned SNAPSHOT of the model's
        # params/state, taken here and replaced only through
        # update_weights() — so a trainer sharing the model object in the
        # same process (rl.PostTrainer) can step the masters freely while
        # the engine keeps serving the last synced version.
        self._params = model.params
        self._state = model.state
        self._weights_version = 0
        # Positional capacity check up front (abstract: no allocation) —
        # the paged path cannot raise at trace time the way init_cache
        # does, so a too-short learned positional table must fail HERE,
        # not produce silently clamped rows mid-serve.
        jax.eval_shape(
            lambda p: model.module.init_cache(p, 1, self.max_len,
                                              jnp.float32),
            model.params,
        )
        nb_per_seq = -(-self.max_len // self.block_size)
        if num_blocks is None:
            num_blocks = self.max_slots * nb_per_seq + 1
        self.kv = PagedKVCache(
            model.module, model.params,
            max_slots=self.max_slots, block_size=self.block_size,
            max_blocks_per_seq=nb_per_seq, num_blocks=int(num_blocks),
            dtype=kv_dtype if kv_dtype is not None else model.decode_dtype(),
            prefix_cache=bool(prefix_cache),
        )
        # Speculative decoding: the draft LM gets its own (fully
        # provisioned — it is small, and a draft-side admission stall
        # would serve nothing) paged cache and greedy-pinned dispatches;
        # the target gains a K-wide verify dispatch. self._kvs is the
        # cache handle the scheduler seams use: the paired wrapper keeps
        # both pools' slot ownership in lockstep, and degenerates to the
        # target cache when no draft is configured.
        self._draft = draft_model
        # spec_k="adaptive": per-tenant accept-rate EMAs pick each
        # round's verify width from SPEC_K_LADDER; headroom/reservation
        # math uses the ladder MAX so a tenant stepping up never needs
        # blocks the admission didn't grant.
        self._adaptive_k = spec_k == "adaptive"
        if self._adaptive_k:
            self._spec_k = SPEC_K_LADDER[-1]
        elif isinstance(spec_k, str):
            raise ValueError(
                f"spec_k must be an int >= 2 or 'adaptive', got {spec_k!r}"
            )
        else:
            self._spec_k = int(spec_k)
        self._accept_ema = {}    # tenant -> EmaBaseline of round accepts
        self._tenant_k = {}      # tenant -> current ladder k
        self._tenant_rounds = {}  # tenant -> speculative rounds observed
        self._tenant_moved = {}  # tenant -> round of its last rung move
        self._k_adjustments = 0
        if draft_model is not None:
            if not draft_model.built:
                raise RuntimeError("draft model not built")
            if self._spec_k < 2:
                raise ValueError(
                    f"spec_k must be >= 2 (k=1 is plain decode), got "
                    f"{spec_k}"
                )
            jax.eval_shape(
                lambda p: draft_model.module.init_cache(
                    p, 1, self.max_len, jnp.float32
                ),
                draft_model.params,
            )
            self._draft_kv = PagedKVCache(
                draft_model.module, draft_model.params,
                max_slots=self.max_slots, block_size=self.block_size,
                max_blocks_per_seq=nb_per_seq,
                num_blocks=self.max_slots * nb_per_seq + 1,
                dtype=draft_model.decode_dtype(),
            )
            self._kvs = _PairedKV(self.kv, self._draft_kv)
            # Draft weights are an engine-owned snapshot too (same
            # discipline as self._params): a DraftDistiller training the
            # shared draft model in-process publishes through
            # update_weights(draft_params=...), never by side effect.
            self._draft_params = draft_model.params
            self._draft_state = draft_model.state
        else:
            self._draft_kv = None
            self._kvs = self.kv
            self._draft_params = None
            self._draft_state = None
        # Draft staleness: how many target swaps the served draft has
        # NOT been re-synced across (0 = in sync). Acceptance-only —
        # proposals are always verified by the live target.
        self._draft_version = 0
        self._draft_staleness = 0
        # Both dispatches jit once (decode shapes are fixed; prefill
        # retraces only per distinct bucketed chunk length) under the
        # model's strategy/precision scopes — same discipline as every
        # Model step function. The raw jitted objects are kept
        # (self._*_jit) so tests can pin the no-recompile contract via
        # _cache_size() across weight swaps and logprob-capture toggles.
        self._prefill_jit = jax.jit(
            functools.partial(
                _prefill_dispatch, model.module, self.temperature,
                self.top_k, model.precision, model._dtype_hints,
            ),
            donate_argnums=(2,),
        )
        self._decode_jit = jax.jit(
            functools.partial(
                _decode_dispatch, model.module, self.temperature,
                self.top_k, model.precision, model._dtype_hints,
            ),
            donate_argnums=(2,),
        )
        self._prefill_fn = self.model._scoped(self._prefill_jit)
        self._decode_fn = self._with_kernel(
            self.model._scoped(self._decode_jit)
        )
        if draft_model is not None:
            # Target-side verify: K candidates per slot, one dispatch.
            self._verify_jit = jax.jit(
                functools.partial(
                    _verify_dispatch, model.module, self.temperature,
                    self.top_k, model.precision, model._dtype_hints,
                ),
                donate_argnums=(2,),
            )
            self._verify_fn = self._with_kernel(
                self.model._scoped(self._verify_jit)
            )
            # Draft dispatches are GREEDY regardless of the engine's
            # sampling config: proposals are only hints — acceptance
            # compares them against the target's (possibly sampled)
            # tokens — and a deterministic draft maximizes the agreement
            # run without touching the output distribution.
            self._draft_prefill_jit = jax.jit(
                functools.partial(
                    _prefill_dispatch, draft_model.module, 0.0, None,
                    draft_model.precision, draft_model._dtype_hints,
                ),
                donate_argnums=(2,),
            )
            self._draft_decode_jit = jax.jit(
                functools.partial(
                    _decode_dispatch, draft_model.module, 0.0, None,
                    draft_model.precision, draft_model._dtype_hints,
                ),
                donate_argnums=(2,),
            )
            self._draft_prefill_fn = draft_model._scoped(
                self._draft_prefill_jit
            )
            self._draft_decode_fn = self._with_kernel(
                draft_model._scoped(self._draft_decode_jit)
            )
        from ..ops._pallas_common import interpret as pallas_interpret

        events_lib.emit(
            evs.DECODE_KERNEL_SELECTED,
            kernel=self.decode_kernel,
            backend=jax.default_backend(),
            interpret=pallas_interpret(),
        )
        self.last_run_telemetry = None
        self._sched: Optional[Scheduler] = None  # live during run()

    def _with_kernel(self, fn):
        """Wrap a scoped decode/verify dispatch so its FIRST (tracing)
        call — and every later one, harmlessly — runs inside the engine's
        decode_kernel_scope: the attention layer reads the ambient choice
        at trace time (ops.paged_attention.current_decode_kernel), so the
        traced program bakes the kernel in. 'reference' returns ``fn``
        unwrapped — byte-for-byte the pre-knob call path."""
        if self.decode_kernel == self._paged_ops.REFERENCE:
            return fn
        kind = self.decode_kernel
        scope = self._paged_ops.decode_kernel_scope

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with scope(kind):
                return fn(*args, **kwargs)

        return wrapped

    # ------------------------------------------------------- live signals
    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot RIGHT NOW (0 when idle). A live
        signal — the fleet router and the queue-depth autoscaler read it
        mid-run instead of guessing load from finished-run telemetry."""
        return len(self._sched.waiting) if self._sched is not None else 0

    @property
    def free_blocks(self) -> int:
        """KV pool blocks currently unallocated — the admission headroom
        signal (a request needs ``kv.blocks_for(context)`` of these)."""
        return self.kv.allocator.num_free

    # --------------------------------------------------------- weight swap
    @property
    def weights_version(self) -> int:
        """Monotonic counter of served-weight generations: 0 for the
        construction-time snapshot, +1 per ``update_weights``. Threaded
        through ``last_run_telemetry`` and per-token request rows so every
        generated token names the weights that produced it."""
        return self._weights_version

    def update_weights(self, params=None, *, draft_params=None) -> int:
        """Hot-swap the served weights WITHOUT a restart: validate the new
        tree against the live one, re-place it under the engine model's
        strategy (the ``quant.quantize_model`` quantize-on-load
        re-placement idiom, generalized to any same-shape tree), and bump
        ``weights_version``. Returns the new version.

        Staleness contract (docs/RL.md, docs/SERVING.md "Weight
        hot-swap"): the swap is atomic at DISPATCH granularity. In-flight
        sequences keep their KV cache — same shapes, new weights — so a
        sequence straddling a swap decodes its remaining tokens with new
        weights attending over KV written by old ones; its per-token
        ``weights_versions`` rows record exactly where the boundary fell.
        No KV is recomputed and no request is evicted: the trade
        production RL rollout loops make deliberately (the alternative —
        flushing the pool — costs a full re-prefill of every running
        sequence for a one-update-old prefix).

        Tree structure, leaf shapes AND dtypes must match the live params
        exactly (a shape/dtype change would silently retrace the fixed
        decode program; a different architecture needs a new Engine) —
        mismatches raise ``ValueError`` loudly. State (e.g. BatchNorm
        stats) is not swapped; serving LMs carry none, and a model that
        does should rebuild its engine.

        ``draft_params``: re-sync the speculative draft's served snapshot
        (same validation, placed under the DRAFT model's strategy) — the
        ``rl.distill.DraftDistiller`` publish path. A target swap that
        does NOT carry ``draft_params`` leaves the draft one version
        staler (``draft_staleness`` in run telemetry counts the gap):
        acceptance-only drift, never correctness, since every proposal is
        verified by the live target. Syncing emits a ``draft_sync`` event
        recording how stale the draft had grown.
        """
        if params is None and draft_params is None:
            raise ValueError(
                "update_weights: pass params, draft_params, or both"
            )
        if params is not None:
            _validate_swap(self._params, params, "update_weights")
            placed = self.model.strategy.put_params(
                params, hints=self.model.module.sharding_hints()
            )
            # Block until resident: the next dispatch must read the new
            # weights, and the latency reported by callers (the RL loop's
            # weight-sync row) must cover the transfer, not enqueue it.
            jax.block_until_ready(placed)
            self._params = placed
            self._weights_version += 1
            # The staleness contract extends to the prefix store: cached
            # blocks were computed under the OLD weights, and while
            # in-flight sequences deliberately keep theirs (the per-token
            # version rows record the boundary), a NEW request must not
            # silently seed from a one-version-old prefix — flush the
            # store's references; live sharers keep their copies alive.
            if self.kv.prefix is not None:
                self.kv.prefix.flush(self.kv.allocator)
            if self._draft is not None and draft_params is None:
                self._draft_staleness += 1
        if draft_params is not None:
            if self._draft is None:
                raise ValueError(
                    "update_weights: draft_params given but the engine "
                    "has no draft model"
                )
            _validate_swap(
                self._draft_params, draft_params,
                "update_weights(draft_params)",
            )
            placed = self._draft.strategy.put_params(
                draft_params, hints=self._draft.module.sharding_hints()
            )
            jax.block_until_ready(placed)
            staleness = self._draft_staleness
            self._draft_params = placed
            self._draft_version = self._weights_version
            self._draft_staleness = 0
            events_lib.emit(
                evs.DRAFT_SYNC,
                weights_version=int(self._weights_version),
                staleness=int(staleness),
                source="update_weights",
            )
        return self._weights_version

    # ------------------------------------------------------------- helpers

    def _bucket(self, c: int, start: int) -> int:
        """Chunk lengths round up to a multiple of 64 (one compile per
        bucket, exactly like generate()'s length bucketing), capped so
        the padded chunk never runs past max_len — the positional
        table's dynamic slice must not clamp, which would misalign the
        REAL rows, and block indices must stay inside the table width."""
        return min(max(64, -(-c // 64) * 64), self.max_len - start)

    def _prefill_chunks(self, seq):
        """(start, length) chunks covering seq's current context — minus
        the leading span admission found already cached (prefix-store
        adoption caps ``cached_len`` at context-1, so the final chunk —
        whose logits sample the continuation — always exists)."""
        total = seq.context_len
        begin = min(seq.cached_len, total - 1)
        step = self.prefill_chunk or (total - begin)
        return [
            (s, min(step, total - s)) for s in range(begin, total, step)
        ]

    def _observe_accept(self, seq, frac: float) -> None:
        """Fold one speculative round's accept fraction (accepted /
        proposed, this slot) into its tenant's EMA and re-pick the
        tenant's ladder rung. The rung only moves after SPEC_MIN_ROUNDS
        observations — one cold round must not lock a tenant out — and
        then dwells SPEC_MIN_ROUNDS more between moves (an EMA sitting
        ON a threshold must not flap the rung every round). Each move
        emits ``spec_k_adjust`` (rare once the EMA settles, so the
        fsync-per-record transport is safe)."""
        tenant = str(getattr(seq, "tenant", "default"))
        ema = self._accept_ema.get(tenant)
        if ema is None:
            ema = self._accept_ema[tenant] = EmaBaseline(SPEC_EMA_DECAY)
        ema.update(float(frac))
        rounds = self._tenant_rounds.get(tenant, 0) + 1
        self._tenant_rounds[tenant] = rounds
        if rounds < SPEC_MIN_ROUNDS:
            return
        if rounds - self._tenant_moved.get(tenant, 0) < SPEC_MIN_ROUNDS:
            return
        old = self._tenant_k.get(tenant, SPEC_K_DEFAULT)
        new = _ladder_k(float(ema.value))
        self._tenant_k[tenant] = new
        if new != old:
            self._k_adjustments += 1
            self._tenant_moved[tenant] = rounds
            events_lib.emit(
                evs.SPEC_K_ADJUST, tenant=tenant, old_k=int(old),
                new_k=int(new), accept_ema=round(float(ema.value), 4),
                rounds=int(rounds),
            )

    # ---------------------------------------------------------------- run
    def run(self, requests: SequenceT, *, return_logprobs: bool = False,
            on_decode_step=None, tenants=None) -> List[np.ndarray]:
        """Serve ``requests`` (a sequence of ``serving.Request``, or
        (prompt, max_new_tokens) pairs) to completion; returns each
        request's prompt+generated tokens in submission order —
        row-compatible with ``generate()`` per request. Telemetry for the
        run lands in ``engine.last_run_telemetry``.

        ``return_logprobs=True`` records each generated token's sampling
        logprob into the per-request telemetry rows (``"logprobs"``) —
        the rollout capture RL training consumes. The logprobs are
        computed inside the fixed-shape dispatches either way (one
        log_softmax next to the logits), so toggling this NEVER
        recompiles; the flag only switches the host-side bookkeeping.

        ``on_decode_step``: optional ``fn(engine, decode_step)`` hook
        called after every decode dispatch — the seam a driver uses to
        interleave control actions (e.g. ``update_weights`` mid-run, the
        hot-swap staleness-contract tests) with a live batch.

        ``tenants``: optional per-request tenant names (parallel to
        ``requests``; default ``"default"``) — the identity the adaptive
        spec_k accept-rate EMAs key on. The fleet router sets tenants on
        its own sequences; this is the direct-Engine equivalent."""
        reqs = [
            r if isinstance(r, Request) else Request(r[0], r[1])
            for r in requests
        ]
        if tenants is not None and len(tenants) != len(reqs):
            raise ValueError(
                f"tenants covers {len(tenants)} requests but "
                f"{len(reqs)} were submitted"
            )
        # Speculating engines need spec_k - 1 positions of table headroom
        # past the last committed token: the verify dispatch scatters K
        # consecutive candidate rows unconditionally, and clamping them
        # would corrupt live positions.
        cap = self.max_len - (
            self._spec_k - 1 if self._draft is not None else 0
        )
        for r in reqs:
            need = r.prompt.size + r.max_new_tokens
            if need > cap:
                raise ValueError(
                    f"request {r.request_id}: prompt {r.prompt.size} + "
                    f"max_new_tokens {r.max_new_tokens} exceeds engine "
                    f"max_len {self.max_len}"
                    + (
                        f" minus speculative headroom spec_k-1="
                        f"{self._spec_k - 1}"
                        if self._draft is not None else ""
                    )
                )
        timer = StepTimer(warmup=0)
        # The first dispatches of each kind enter the span timeline, the
        # rest accrue into their histograms alone (obs.spans).
        in_timeline = obs_spans.loop_gate()
        obs_reg = obs_registry.default_registry()
        sched = Scheduler(self.max_slots)
        self._sched = sched
        t0 = time.perf_counter()
        seqs = [sched.submit(r, now=0.0) for r in reqs]
        for i, seq in enumerate(seqs):
            r = seq.request
            seq.sample_seed = _mix_seed(
                self.seed, r.seed if r.seed is not None else r.request_id
            )
            seq.tenant = (
                str(tenants[i]) if tenants is not None else "default"
            )
            # Per-request speculation ledger (lifecycle rows).
            seq.spec_proposed = 0
            seq.spec_accepted = 0
            seq.spec_tokens = 0
        version_at_start = self._weights_version
        results = {}
        ttft = {}
        util_samples = []
        queue_samples = []
        free_blocks_min = self.kv.allocator.num_free
        decode_steps = 0
        prefill_dispatches = 0
        preemptions = 0
        prefix_hit_tokens = 0
        spec_rounds = 0
        spec_proposed = 0
        spec_accepted = 0
        spec_tokens = 0
        # (seq, chunk list, next chunk index): at most ONE chunk runs per
        # loop iteration, so running sequences keep decoding between a
        # long prompt's chunks instead of stalling behind all of them.
        prefill_jobs = []

        def elapsed():
            return time.perf_counter() - t0

        def finish(seq):
            sched.finish(seq, self._kvs)
            seq.finished_at = elapsed()
            results[seq.request.request_id] = seq.output()

        while not (sched.idle and not prefill_jobs):
            # -- admit: fill every free slot the pool can back ------------
            while True:
                seq = sched.next_admittable(self._kvs)
                if seq is None:
                    break
                timer.attribute("queue_wait", elapsed() - seq.enqueued_at)
                if seq.admitted_at is None:
                    seq.admitted_at = elapsed()
                if seq.cached_len > 0:
                    prefix_hit_tokens += seq.cached_len
                    events_lib.emit(
                        evs.PREFIX_CACHE_HIT,
                        request_id=int(seq.request.request_id),
                        cached_tokens=int(seq.cached_len),
                        blocks=seq.cached_len // self.block_size,
                    )
                prefill_jobs.append([seq, self._prefill_chunks(seq), 0])
            if not sched.running:
                # Nothing running and nothing admittable: the queue head's
                # context cannot fit even an EMPTY pool.
                head = sched.waiting[0]
                raise RuntimeError(
                    f"request {head.request.request_id}: context of "
                    f"{head.context_len} tokens needs "
                    f"{self.kv.blocks_for(head.context_len)} blocks but "
                    f"the pool only has {self.kv.allocator.num_allocatable}"
                    " allocatable — raise num_blocks or lower max_len"
                )
            # -- one prefill chunk, if any are pending --------------------
            if prefill_jobs:
                job = prefill_jobs[0]
                seq, chunks, idx = job
                if seq.slot is None:  # preempted mid-prefill: job is moot
                    prefill_jobs.pop(0)
                    continue
                start, c = chunks[idx]
                cb = self._bucket(c, start)
                buf = np.zeros((1, cb), np.int32)
                buf[0, :c] = seq.tokens[start:start + c]
                # prefill attribution flows through the span tracer (same
                # name lands on XProf timelines and in the registry).
                with obs_spans.span("prefill", timer=timer,
                                    timeline=in_timeline("prefill")):
                    tok, logp, self.kv.caches = self._prefill_fn(
                        self._params, self._state, self.kv.caches, buf,
                        self.kv.block_tables[seq.slot],
                        np.int32(start),
                        np.int32(seq.context_len - 1 - start
                                 if idx == len(chunks) - 1 else c - 1),
                        _token_key(seq.sample_seed, seq.num_generated),
                    )
                    prefill_dispatches += 1
                    job[2] = idx + 1
                    final_chunk = job[2] == len(chunks)
                    if final_chunk:
                        # Final chunk: the sampled continuation is real.
                        first, first_lp = jax.device_get((tok, logp))
                        first = int(first)
                if final_chunk:
                    prefill_jobs.pop(0)
                    # The slot's prompt blocks are now fully written:
                    # publish them for future admissions to adopt. Only
                    # the PROMPT span — generated tokens (present in a
                    # re-admitted preempted context) are private.
                    self.kv.insert_prefix(
                        seq.slot, seq.tokens[:seq.prompt_len]
                    )
                    if self._draft is not None:
                        # Draft prefill of the FULL context (the draft
                        # has no prefix store; its pool is cheap). Runs
                        # chunk-bucketed like the target so long prompts
                        # reuse the same compile buckets; the sampled
                        # continuation is discarded — proposals start
                        # from the target's real first token.
                        for dstart in range(
                            0, seq.context_len,
                            self.prefill_chunk or seq.context_len,
                        ):
                            dc = min(
                                self.prefill_chunk or seq.context_len,
                                seq.context_len - dstart,
                            )
                            dcb = self._bucket(dc, dstart)
                            dbuf = np.zeros((1, dcb), np.int32)
                            dbuf[0, :dc] = seq.tokens[dstart:dstart + dc]
                            _, _, self._draft_kv.caches = (
                                self._draft_prefill_fn(
                                    self._draft_params, self._draft_state,
                                    self._draft_kv.caches, dbuf,
                                    self._draft_kv.block_tables[seq.slot],
                                    np.int32(dstart), np.int32(dc - 1),
                                    _token_key(seq.sample_seed, 0),
                                )
                            )
                        self._draft_kv.positions[seq.slot] = seq.context_len
                    self.kv.positions[seq.slot] = seq.context_len
                    seq.tokens.append(first)
                    seq.token_versions.append(self._weights_version)
                    if return_logprobs:
                        seq.logprobs.append(float(first_lp))
                    seq.num_generated += 1
                    if seq.num_generated == 1:
                        ttft[seq.request.request_id] = elapsed()
                        seq.first_token_at = elapsed()
                    if seq.finished or first == self.eos_id:
                        finish(seq)
            # -- decode: every running slot whose prefill is done ---------
            mid_prefill = {
                id(j[0]) for j in prefill_jobs if j[0].slot is not None
            }
            ready = [
                s for s in sched.running if id(s) not in mid_prefill
            ]
            # Grow each ready slot's table to cover its next write
            # position; under pool pressure evict the youngest runner
            # back to the queue (its generated tokens ride along and are
            # re-prefilled on re-admission).
            # A speculating engine reserves spec_k - 1 extra positions:
            # the verify dispatch scatters K candidate rows past the
            # committed context, and those writes must land in real,
            # owned blocks.
            headroom = self._spec_k - 1 if self._draft is not None else 0
            for seq in ready:
                if seq.slot is None:
                    continue  # evicted by an older peer this pass
                while not self._kvs.reserve(
                    seq.slot, seq.context_len + headroom
                ):
                    victim = sched.preempt_youngest(self._kvs, protect=seq)
                    if victim is None:
                        raise RuntimeError(
                            f"request {seq.request.request_id}: cannot "
                            f"back {seq.context_len} positions with "
                            f"{self.kv.num_blocks - 1} pool blocks even "
                            "alone — raise num_blocks"
                        )
                    preemptions += 1
                    victim.enqueued_at = elapsed()
                    # Any in-flight prefill job of the victim is void: on
                    # re-admission it gets a fresh job starting at chunk 0.
                    prefill_jobs[:] = [
                        j for j in prefill_jobs if j[0] is not victim
                    ]
            ready = [s for s in ready if s.slot is not None]
            if not ready:
                continue
            # Round width: the static spec_k, or (adaptive) the MAX of
            # the ready tenants' ladder rungs — one verify dispatch
            # serves the whole batch, and each slot's acceptance walk is
            # capped at its OWN tenant's k below. Every width is a
            # ladder rung, so _verify_jit holds at most len(ladder)-1
            # traces however the batch churns. kw < 2 (no draft, or
            # every ready tenant opted out) falls through to plain
            # decode.
            kw = 0
            slot_limit = None
            if self._draft is not None:
                if self._adaptive_k:
                    slot_limit = {
                        id(s): self._tenant_k.get(
                            getattr(s, "tenant", "default"),
                            SPEC_K_DEFAULT,
                        )
                        for s in ready
                    }
                    kw = max(slot_limit.values())
                else:
                    kw = self._spec_k
            if kw >= 2:
                # ---- speculative round: draft proposes, target verifies.
                # Candidate matrix column 0 is each slot's REAL last
                # token; columns 1..K-1 are the draft's greedy chain.
                # One K-wide verify dispatch then scores all columns, and
                # the host walk commits the longest run where the draft's
                # next proposal agreed with the target's token — 1..K
                # tokens per dispatch, bit-identical to vanilla decode.
                ready_mask = np.zeros((self.max_slots,), bool)
                cand = np.zeros((self.max_slots, kw), np.int32)
                keys = np.zeros((self.max_slots, kw, 2), np.uint32)
                for seq in ready:
                    ready_mask[seq.slot] = True
                    cand[seq.slot, 0] = seq.last_token
                    for j in range(kw):
                        keys[seq.slot, j] = _token_key(
                            seq.sample_seed, seq.num_generated + j
                        )
                dtables = np.where(
                    ready_mask[:, None], self._draft_kv.block_tables,
                    np.int32(0),
                )
                dpos = np.where(
                    ready_mask, self._draft_kv.positions, 0
                ).astype(np.int32)
                dummy_keys = np.zeros((self.max_slots, 2), np.uint32)
                cur = cand[:, 0].copy()
                with obs_spans.span("draft", timer=timer,
                                    timeline=in_timeline("draft")):
                    for j in range(1, kw):
                        prop, _, self._draft_kv.caches = (
                            self._draft_decode_fn(
                                self._draft_params, self._draft_state,
                                self._draft_kv.caches, cur, dtables,
                                dpos, dummy_keys,
                            )
                        )
                        prop = np.asarray(jax.device_get(prop))
                        cand[:, j] = prop
                        cur = prop.astype(np.int32)
                        # Non-speculating slots advance through the trash
                        # block (positions 1..K-2 of table row 0).
                        dpos = (dpos + 1).astype(np.int32)
                tables = np.where(
                    ready_mask[:, None], self.kv.block_tables, np.int32(0)
                )
                positions = np.where(
                    ready_mask, self.kv.positions, 0
                ).astype(np.int32)
                with obs_spans.span(
                        "decode", timer=timer,
                        timeline=in_timeline("decode")) as sp_dec:
                    toks, logps, self.kv.caches = self._verify_fn(
                        self._params, self._state, self.kv.caches, cand,
                        tables, positions, keys,
                    )
                    toks, logps = jax.device_get((toks, logps))
                    toks = np.asarray(toks)
                decode_steps += 1
                spec_rounds += 1
                util = self.kv.utilization()
                util_samples.append(util)
                queue_samples.append(len(sched.waiting))
                free_blocks_min = min(
                    free_blocks_min, self.kv.allocator.num_free
                )
                obs_reg.gauge("engine/kv_utilization", float(util))
                obs_reg.gauge("engine/queue_depth", len(sched.waiting))
                obs_reg.ring_append("engine/step_seconds", {
                    "step": int(decode_steps),
                    "seconds": round(sp_dec.seconds, 6),
                    "running": len(ready),
                })
                for seq in ready:
                    # Adaptive: this slot commits at most its OWN
                    # tenant's k columns (k=0 rides the round but
                    # commits exactly column 0 — the plain-decode
                    # token, bit-identical by the verify contract).
                    limit = (
                        kw if slot_limit is None
                        else max(1, slot_limit[id(seq)])
                    )
                    m = 0
                    while True:
                        tok = int(toks[seq.slot, m])
                        seq.tokens.append(tok)
                        seq.token_versions.append(self._weights_version)
                        if return_logprobs:
                            seq.logprobs.append(float(logps[seq.slot, m]))
                        seq.num_generated += 1
                        m += 1
                        if seq.finished or tok == self.eos_id:
                            break
                        # Accept the next column only if the draft's
                        # proposal there IS the token the target just
                        # produced — then column m's logits were
                        # conditioned on the true prefix.
                        if m >= limit or int(cand[seq.slot, m]) != tok:
                            break
                    spec_tokens += m
                    spec_accepted += m - 1
                    spec_proposed += limit - 1
                    seq.spec_tokens += m
                    seq.spec_accepted += m - 1
                    seq.spec_proposed += limit - 1
                    if self._adaptive_k and limit >= 2:
                        self._observe_accept(seq, (m - 1) / (limit - 1))
                    # Invariant: positions = committed rows = next write.
                    self.kv.positions[seq.slot] = seq.context_len - 1
                    self._draft_kv.positions[seq.slot] = (
                        seq.context_len - 1
                    )
                    if seq.finished or seq.last_token == self.eos_id:
                        finish(seq)
                if on_decode_step is not None:
                    on_decode_step(self, decode_steps)
                continue
            tokens = np.zeros((self.max_slots,), np.int32)
            ready_mask = np.zeros((self.max_slots,), bool)
            keys = np.zeros((self.max_slots, 2), np.uint32)
            for seq in ready:
                tokens[seq.slot] = seq.last_token
                ready_mask[seq.slot] = True
                keys[seq.slot] = _token_key(
                    seq.sample_seed, seq.num_generated
                )
            # Slots that are free or mid-prefill get all-trash tables for
            # this dispatch: their scatter writes must not touch blocks a
            # live (possibly half-prefilled) sequence owns.
            tables = np.where(
                ready_mask[:, None], self.kv.block_tables, np.int32(0)
            )
            positions = np.where(ready_mask, self.kv.positions, 0).astype(
                np.int32
            )
            with obs_spans.span("decode", timer=timer,
                                timeline=in_timeline("decode")) as sp_dec:
                sampled, logps, self.kv.caches = self._decode_fn(
                    self._params, self._state, self.kv.caches, tokens,
                    tables, positions, keys,
                )
                sampled, logps = jax.device_get((sampled, logps))
                sampled = np.asarray(sampled)
            decode_steps += 1
            util = self.kv.utilization()
            util_samples.append(util)
            queue_samples.append(len(sched.waiting))
            free_blocks_min = min(free_blocks_min, self.kv.allocator.num_free)
            # Live registry signals (the fleet router/autoscaler read the
            # properties mid-run; exporters read these):
            obs_reg.gauge("engine/kv_utilization", float(util))
            obs_reg.gauge("engine/queue_depth", len(sched.waiting))
            obs_reg.ring_append("engine/step_seconds", {
                "step": int(decode_steps),
                "seconds": round(sp_dec.seconds, 6),
                "running": len(ready),
            })
            for seq in ready:
                tok = int(sampled[seq.slot])
                self.kv.positions[seq.slot] = seq.context_len
                seq.tokens.append(tok)
                seq.token_versions.append(self._weights_version)
                if return_logprobs:
                    seq.logprobs.append(float(logps[seq.slot]))
                seq.num_generated += 1
                if seq.finished or tok == self.eos_id:
                    finish(seq)
            if on_decode_step is not None:
                on_decode_step(self, decode_steps)
        report = timer.stall_report()
        report["kv_utilization"] = {
            "mean": round(float(np.mean(util_samples)), 4)
            if util_samples else 0.0,
            "peak": round(float(np.max(util_samples)), 4)
            if util_samples else 0.0,
        }
        report["generated_tokens"] = int(
            sum(len(results[r.request_id]) - r.prompt.size for r in reqs)
        )
        report["tokens_per_sec"] = round(
            report["generated_tokens"] / report["total_seconds"], 3
        )
        vals = list(ttft.values())
        report["time_to_first_token"] = {
            "mean": round(float(np.mean(vals)), 4),
            "p50": round(float(np.percentile(vals, 50)), 4),
            "p99": round(float(np.percentile(vals, 99)), 4),
            "max": round(float(np.max(vals)), 4),
        }
        # Per-request lifecycle rows: the p50/p99 inputs, and the raw
        # signal a router/autoscaler replays when tuning admission (mean
        # TTFT alone hides the tail that SLOs are written against).
        # weights_versions compacts the per-token version list into
        # [{"version", "tokens"}] spans: one span per run for a request
        # that never straddled an update_weights, and the exact boundary
        # token when one did (the hot-swap staleness record). "logprobs"
        # (full precision — RL forms importance ratios from these) rides
        # along when the run captured them.
        def _version_spans(versions):
            spans = []
            for v in versions:
                if spans and spans[-1]["version"] == v:
                    spans[-1]["tokens"] += 1
                else:
                    spans.append({"version": int(v), "tokens": 1})
            return spans

        report["requests"] = [
            {
                "request_id": s.request.request_id,
                "enqueued_s": round(float(s.submitted_at), 4),
                "admitted_s": round(float(s.admitted_at), 4),
                "first_token_s": round(float(s.first_token_at), 4),
                "finished_s": round(float(s.finished_at), 4),
                "preemptions": s.preemptions,
                "weights_versions": _version_spans(
                    s.token_versions[: s.request.max_new_tokens]
                ),
                **(
                    {
                        "spec_tokens": int(getattr(s, "spec_tokens", 0)),
                        "spec_proposed": int(
                            getattr(s, "spec_proposed", 0)
                        ),
                        "accept_rate": (
                            round(s.spec_accepted / s.spec_proposed, 4)
                            if getattr(s, "spec_proposed", 0) else None
                        ),
                    }
                    if self._draft is not None else {}
                ),
                **(
                    {"logprobs": [
                        float(lp) for lp in
                        s.logprobs[: s.request.max_new_tokens]
                    ]}
                    if return_logprobs else {}
                ),
            }
            for s in seqs
        ]
        report["weights_version"] = self._weights_version
        report["weight_swaps"] = self._weights_version - version_at_start
        report["queue_depth"] = {
            "mean": round(float(np.mean(queue_samples)), 4)
            if queue_samples else 0.0,
            "peak": int(np.max(queue_samples)) if queue_samples else 0,
        }
        report["free_blocks_min"] = int(free_blocks_min)
        report["decode_steps"] = decode_steps
        report["prefill_dispatches"] = prefill_dispatches
        report["preemptions"] = preemptions
        if self.kv.prefix is not None:
            st = self.kv.prefix
            lookups = st.hits + st.misses
            hit_rate = st.hits / lookups if lookups else 0.0
            # Bytes the pool did NOT have to hold/recompute because
            # admissions adopted already-cached blocks.
            bytes_saved = st.hits * self.kv.bytes_per_block()
            report["prefix_cache"] = {
                "hit_rate": round(hit_rate, 4),
                "hit_blocks": int(st.hits),
                "hit_tokens": int(prefix_hit_tokens),
                "insertions": int(st.insertions),
                "evictions": int(st.evictions),
                "cow_copies": int(self.kv.cow_copies),
                "kv_bytes_saved": int(bytes_saved),
            }
            obs_reg.gauge("engine/prefix_hit_rate", round(hit_rate, 4))
            obs_reg.gauge("engine/kv_bytes_saved", int(bytes_saved))
        if self._draft is not None:
            accept_rate = (
                spec_accepted / spec_proposed if spec_proposed else 0.0
            )
            tpd = spec_tokens / spec_rounds if spec_rounds else 0.0
            report["speculative"] = {
                "k": (
                    "adaptive" if self._adaptive_k else int(self._spec_k)
                ),
                "rounds": int(spec_rounds),
                "proposed": int(spec_proposed),
                "accepted": int(spec_accepted),
                "accept_rate": round(accept_rate, 4),
                "tokens_per_dispatch": round(tpd, 3),
                "draft_version": int(self._draft_version),
                "draft_staleness": int(self._draft_staleness),
                **(
                    {
                        "max_k": int(self._spec_k),
                        "tenant_k": {
                            t: int(k)
                            for t, k in sorted(self._tenant_k.items())
                        },
                        "k_adjustments": int(self._k_adjustments),
                    }
                    if self._adaptive_k else {}
                ),
            }
            obs_reg.gauge("engine/spec_accept_rate", round(accept_rate, 4))
            # One per-run aggregate (the transport fsyncs per record).
            events_lib.emit(
                evs.SPEC_VERIFY, rounds=int(spec_rounds),
                proposed=int(spec_proposed), accepted=int(spec_accepted),
                accept_rate=round(accept_rate, 4),
                tokens_per_dispatch=round(tpd, 3),
            )
        obs_reg.counter("engine/generated_tokens", report["generated_tokens"])
        obs_reg.counter("engine/requests", len(reqs))
        obs_reg.counter("engine/preemptions", preemptions)
        obs_reg.gauge("engine/tokens_per_sec", report["tokens_per_sec"])
        # Legacy dict = registry view, key-for-key (obs parity test).
        self.last_run_telemetry = obs_reg.set_report("engine.run", report)
        return [results[r.request_id] for r in reqs]


__all__ = ["Engine"]
