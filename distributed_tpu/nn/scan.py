"""Weight-stacked sequential block execution via ``lax.scan``.

TPU-first rationale: a deep stack of structurally identical blocks
(ResNet stage tails, transformer blocks) unrolled as separate layers
compiles to O(depth) static HLO ops. On TPU the XLA program is traced and
scheduled per static op, so depth inflates compile time and — on runtimes
with per-op dispatch cost — step time; in the pre-PR-1 v5e profile
(docs/PERF_ROUNDS_1-5.md) a ResNet-50 train step spent more time on per-op overhead
(~3,500 static ops) than on convolution FLOPs. Stacking the blocks' parameters with a
leading (S, ...) dim and scanning one block body over them emits the body
ONCE: static op count, compile time, and the optimizer's per-tensor update
ops all become depth-independent. This is the flax ``remat_scan`` /
praxis ``repeat`` idiom, built on this framework's own Layer contract.

Unlike :class:`~distributed_tpu.nn.pipeline.PipelinedBlocks` (its
pipeline-parallel sibling), ScannedBlocks supports *stateful* blocks:
per-block state (BatchNorm running stats) is stacked alongside the params
and threaded through the scan as per-iteration inputs/outputs.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..obs import registry as obs_registry
from ..utils import event_schema as evs, events as events_lib
from .core import Layer, Shape, child_scope, reports, trace_record


@reports("overlap")
def _overlap_report(record, strategy):
    """Gather-overlap attribution (ScannedBlocks x Strategy.overlap_spec),
    from the record the step program's scanned apply handed at trace time:
    whether the double-buffered gather engaged, over how many layers, and
    ``exposed_comm_fraction``, the analytic share of per-layer gather
    traffic left serial with compute: all L gathers without overlap, only
    layer 0's warm-up gather with it. Exposed collective time on the chip
    is the benchmark's trace metric; this rides with every fit so telemetry
    names the lever (docs/API.md "Overlap round 2")."""
    entry = dict(record or {"overlap": False, "exposed_comm_fraction": 1.0,
                            "layers": 0})
    if obs_registry.enabled() and events_lib.default_log() is not None:
        events_lib.emit(
            evs.OVERLAP_REPORT,
            overlap=entry["overlap"],
            exposed_comm_fraction=entry["exposed_comm_fraction"],
            layers=entry["layers"],
            strategy=type(strategy).__name__,
        )
    return entry


def init_stacked_blocks(
    block_fn, template, num_blocks, key, input_shape, *,
    require_stateless=False, container="ScannedBlocks",
):
    """Init ``num_blocks`` fresh blocks and stack their params (and state)
    with a leading (S, ...) dim. Shared by ScannedBlocks and
    PipelinedBlocks so the stacked-layout contract stays in one place.

    Returns (stacked_params, stacked_state)."""
    shape = tuple(input_shape)
    keys = jax.random.split(key, num_blocks)
    per_block_p, per_block_s = [], []
    for i in range(num_blocks):
        # Fresh instance per block: container naming is stateful and the
        # template must not accumulate names.
        block = template if i == 0 else block_fn()
        p, s, out = block.init(keys[i], shape)
        if require_stateless and s:
            raise ValueError(
                f"{container} requires stateless blocks (got state keys "
                f"{list(s)}); running stats can't ride a microbatch "
                "schedule"
            )
        if tuple(out) != shape:
            raise ValueError(
                f"{container} blocks must preserve shape: {shape} -> {out}"
            )
        per_block_p.append(p)
        per_block_s.append(s)
    if not jax.tree_util.tree_leaves(per_block_p[0]):
        raise ValueError(
            f"{container} requires parameterized blocks (the template "
            "block has no params); wrap param-free layers directly in "
            "a Sequential instead"
        )
    params = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_block_p
    )
    state = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_block_s
    )
    return params, state


def scan_stacked(block, stacked_p, stacked_s, x, *, train, rngs,
                 overlap_gather=None):
    """Apply a stack of block params (and optional stacked state) to x as
    one ``lax.scan``. Returns (y, stacked_new_state). Shared by
    ScannedBlocks and PipelinedBlocks' sequential path — the 'identical
    numerics' contract both promise lives here.

    ``overlap_gather`` (from ``Strategy.overlap_spec``): when given, the
    scan double-buffers the per-layer parameter gather. Iteration i's
    carry already holds layer i's GATHERED params; the body's first act is
    to issue layer i+1's gather (its xs slice arrives SHARDED — the
    stacked params ride through the scan rolled by -1 so slice i is layer
    i+1), which depends only on the slice, not on layer i's compute — the
    scheduler is free to run the all-gather behind the layer's matmuls
    instead of serializing it in front of them. Only layer 0's warm-up
    gather (issued before the scan) has nothing to hide behind. The final
    iteration's wrap-around gather (layer 0 again, from the roll) is
    dead code XLA drops. Values are identical to the plain body:
    gathering is a layout constraint, not arithmetic."""

    def body(h, per_iter):
        p, s, r = per_iter
        with child_scope("blocks"):
            y, new_s = block.apply(p, s, h, train=train, rng=r)
        # Carry dtype must be stable across iterations (a bf16-compute
        # block in an f32 stream behaves like any mixed-precision layer).
        return y.astype(h.dtype), new_s

    if overlap_gather is not None:
        p0 = jax.tree_util.tree_map(lambda l: l[0], stacked_p)
        g0 = overlap_gather(p0)
        rolled = jax.tree_util.tree_map(
            lambda l: jnp.roll(l, -1, axis=0), stacked_p
        )

        def body_overlap(carry, per_iter):
            h, g = carry
            p_next, s, r = per_iter
            g_next = overlap_gather(p_next)
            with child_scope("blocks"):
                y, new_s = block.apply(g, s, h, train=train, rng=r)
            return (y.astype(h.dtype), g_next), new_s

        if rngs is None:
            (out, _), new_s = lax.scan(
                lambda c, ps: body_overlap(c, (ps[0], ps[1], None)),
                (x, g0),
                (rolled, stacked_s),
            )
        else:
            (out, _), new_s = lax.scan(
                body_overlap, (x, g0), (rolled, stacked_s, rngs)
            )
        return out, new_s

    if rngs is None:
        return lax.scan(
            lambda h, ps: body(h, (ps[0], ps[1], None)),
            x,
            (stacked_p, stacked_s),
        )
    return lax.scan(body, x, (stacked_p, stacked_s, rngs))


def stacked_init_cache(block, num_blocks, stacked_p, batch, max_len, dtype):
    """Stacked (S, ...) decode caches for a block stack — shared by
    ScannedBlocks and PipelinedBlocks so the cache layout can't diverge.
    Broadcasts the template's cache rather than allocating zeros: a layer
    whose cache initializes non-zero must start every block's slice from
    those values, exactly as the unrolled form would."""
    p0 = jax.tree_util.tree_map(lambda l: l[0], stacked_p)
    c0 = block.init_cache(p0, batch, max_len, dtype)
    if not jax.tree_util.tree_leaves(c0):
        return {}
    return {
        "blocks": jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (num_blocks,) + l.shape).copy(),
            c0,
        )
    }


def stacked_decode(block, stacked_p, stacked_s, cache, x, *, pos):
    """One-token step through a block stack: scan the template's cached
    decode over the stacked (params, state, cache), writing each block's
    new KV rows back into its slice. Returns (y, new_cache_tree_or_cache).
    Shared by ScannedBlocks and PipelinedBlocks (which passes an empty
    state stack — its blocks are validated stateless at init)."""

    def body(h, per_block):
        p, s, c = per_block
        y, new_c = block.decode(p, s, c, h, pos=pos)
        return y.astype(h.dtype), new_c

    out, new_cache = lax.scan(
        body, x, (stacked_p, stacked_s, cache.get("blocks", {}))
    )
    if jax.tree_util.tree_leaves(new_cache):
        return out, {"blocks": new_cache}
    return out, cache


# The reserved key the stacked PAGED pools live under. serving/kv_cache's
# pool walkers key on it: leaves below carry a leading (S, ...) stage dim,
# so the pool-block axis is 1, not 0 (copy-on-write and per-block byte
# accounting must index/skip accordingly). A dict key (not a wrapper type)
# keeps the pools an ordinary pytree for jit/donation.
STACKED_POOL_KEY = "stacked"


def stacked_init_paged_cache(block, num_blocks, stacked_p, pool_blocks,
                             block_size, dtype):
    """Stacked (S, ...) paged pools for a block stack, under
    ``STACKED_POOL_KEY`` — shared by ScannedBlocks and PipelinedBlocks'
    sequential path so the layout can't diverge. Broadcasts the template's
    pools (same rationale as ``stacked_init_cache``)."""
    p0 = jax.tree_util.tree_map(lambda l: l[0], stacked_p)
    c0 = block.init_paged_cache(p0, pool_blocks, block_size, dtype)
    if not jax.tree_util.tree_leaves(c0):
        return {}
    return {
        STACKED_POOL_KEY: jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (num_blocks,) + l.shape).copy(),
            c0,
        )
    }


def _stacked_paged_step(step_name, block, stacked_p, stacked_s, cache, x,
                        **kw):
    """Scan one of the template block's paged hooks over the stacked
    (params, state, pools). The block tables and per-slot positions are
    closed over — every layer of every block addresses the same tables,
    exactly as the unrolled Sequential's per-layer pools do — and each
    block's paged step reads/writes only its own (S,) slice of the pools."""
    step = getattr(block, step_name)

    def body(h, per_block):
        p, s, c = per_block
        y, new_c = step(p, s, c, h, **kw)
        return y.astype(h.dtype), new_c

    out, new_cache = lax.scan(
        body, x, (stacked_p, stacked_s, cache.get(STACKED_POOL_KEY, {}))
    )
    if jax.tree_util.tree_leaves(new_cache):
        return out, {STACKED_POOL_KEY: new_cache}
    return out, cache


def stacked_paged_decode(block, stacked_p, stacked_s, cache, x, *,
                         block_tables, positions):
    return _stacked_paged_step(
        "paged_decode", block, stacked_p, stacked_s, cache, x,
        block_tables=block_tables, positions=positions,
    )


def stacked_paged_verify(block, stacked_p, stacked_s, cache, x, *,
                         block_tables, positions):
    return _stacked_paged_step(
        "paged_verify", block, stacked_p, stacked_s, cache, x,
        block_tables=block_tables, positions=positions,
    )


def stacked_paged_prefill(block, stacked_p, stacked_s, cache, x, *,
                          block_table, start):
    return _stacked_paged_step(
        "paged_prefill", block, stacked_p, stacked_s, cache, x,
        block_table=block_table, start=start,
    )


class ScannedBlocks(Layer):
    """S structurally identical, shape-preserving blocks run as one scan.

    ``block_fn()`` must return a fresh ``Layer`` with identical structure
    each call. Blocks may hold state (running stats); its leaves are
    stacked with a leading (S, ...) dim like the params. Deterministic
    computation is numerically identical to the unrolled
    ``Sequential([block_fn() for _ in range(S)])`` given the same per-block
    parameters (asserted in tests/test_scanned_blocks.py). Rng ROUTING
    differs, though: apply() splits one key into S per-block streams, while
    an unrolled Sequential splits across all rng-consuming layers globally —
    Dropout/augmentation masks therefore differ between the scanned and
    unrolled forms (each is still a valid i.i.d. mask stream).
    """

    # Incremental decode IS supported (unlike PipelinedBlocks): the KV
    # caches are stacked with a leading (S, ...) block dim like the params,
    # and decode() scans the template block's cached one-token step over
    # them. decode_safe stays False so a template whose own decode would
    # silently be wrong (position-mixing layers without a cached override)
    # still fails loudly inside the scan body.
    decode_safe = False

    def __init__(
        self,
        block_fn: Callable[[], Layer],
        num_blocks: int,
        *,
        overlap: str = "auto",
        name: Optional[str] = None,
    ):
        """``overlap``: comm/compute overlap for the per-layer parameter
        gather. 'auto' (default) double-buffers the gather whenever the
        AMBIENT strategy provides one (``Strategy.overlap_spec`` — the
        FSDP family; resolved at trace time, so one module serves every
        strategy); 'off' keeps the plain scan body under every strategy;
        'require' raises at trace time if the strategy has no gather to
        overlap (use it to make a perf assumption loud)."""
        super().__init__(name)
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if overlap not in ("auto", "off", "require"):
            raise ValueError(
                "overlap must be 'auto', 'off' or 'require', got "
                f"{overlap!r}"
            )
        self.num_blocks = int(num_blocks)
        self.overlap = overlap
        self.block_fn = block_fn
        self.block = block_fn()  # template: defines structure + names

    def default_name(self) -> str:
        return "scanned_blocks"

    @property
    def needs_rng(self) -> bool:
        return getattr(self.block, "needs_rng", False)

    def sharding_hints(self):
        # Pass the template block's tensor-parallel roles through, shifted
        # past the leading stack dim: 'col' still targets the last dim;
        # 'row' (input dim, dim 0 of the unstacked leaf) becomes 'row1'
        # (dim 1 behind the stack index). Strategies that don't know a role
        # fall back to their default placement.
        def shift(h):
            if isinstance(h, dict):
                return {k: shift(v) for k, v in h.items()}
            if h in ("expert", "pipe"):
                # These roles target dim 0 of their (unstacked) leaf; behind
                # the stack index they would shard the block-stack dim S.
                raise ValueError(
                    f"ScannedBlocks cannot stack blocks with {h!r}-role "
                    "params (MoE expert stacks / nested pipeline stages)"
                )
            return "row1" if h == "row" else h

        inner = shift(self.block.sharding_hints())
        return {"blocks": inner} if inner else {}

    def dtype_hints(self):
        # Stacked params mirror the template block's tree one level down,
        # so its explicit per-layer dtype overrides pass straight through.
        h = self.block.dtype_hints()
        return {"blocks": h} if h is not None and h != {} else {}

    def init(self, key, input_shape: Shape):
        shape = tuple(input_shape)
        params, state = init_stacked_blocks(
            self.block_fn, self.block, self.num_blocks, key, shape,
        )
        out_s = {"blocks": state} if jax.tree_util.tree_leaves(state) else {}
        return {"blocks": params}, out_s, shape

    def _overlap_gather(self):
        """Resolve the ambient strategy's gather at TRACE time (the
        ``current_strategy`` idiom — strategy scopes are entered around
        every jitted step body by ``Model._scoped``)."""
        if self.overlap == "off":
            return None
        from ..parallel.strategy import current_strategy
        strat = current_strategy()
        gather = strat.overlap_spec() if strat is not None else None
        if gather is None and self.overlap == "require":
            raise ValueError(
                "ScannedBlocks(overlap='require') needs an ambient "
                "strategy with an overlap_spec gather (the FSDP family); "
                f"got {type(strat).__name__ if strat else None}"
            )
        return gather

    def apply(self, params, state, x, *, train=False, rng=None):
        rngs = (
            jax.random.split(rng, self.num_blocks) if rng is not None else None
        )
        gather = self._overlap_gather()
        trace_record("overlap", {
            "overlap": gather is not None,
            "exposed_comm_fraction": (
                1.0 if gather is None else round(1.0 / self.num_blocks, 6)),
            "layers": self.num_blocks,
        })
        out, new_s = scan_stacked(
            self.block, params["blocks"], state.get("blocks", {}), x,
            train=train, rngs=rngs, overlap_gather=gather,
        )
        # Blocks that return no state (eval-mode BatchNorm, stateless
        # blocks) produce an empty ys tree; mirror Sequential's "omit when
        # empty" contract.
        if jax.tree_util.tree_leaves(new_s):
            return out, {"blocks": new_s}
        return out, {}

    # ---------------------------------------------------- incremental decode
    def init_cache(self, params, batch, max_len, dtype):
        return stacked_init_cache(
            self.block, self.num_blocks, params["blocks"], batch, max_len,
            dtype,
        )

    def decode(self, params, state, cache, x, *, pos):
        return stacked_decode(
            self.block, params["blocks"], state.get("blocks", {}), cache, x,
            pos=pos,
        )

    # Paged (block KV) serving: the per-layer pools stack with a leading
    # (S, ...) stage dim like everything else in this module, and each
    # hook scans the template block's paged step over the stack with the
    # block tables / per-slot position vectors closed over. The serving
    # engine's allocator and prefix store see block indices on axis 1
    # (the STACKED_POOL_KEY contract in serving/kv_cache.py).
    def init_paged_cache(self, params, num_blocks, block_size, dtype):
        return stacked_init_paged_cache(
            self.block, self.num_blocks, params["blocks"], num_blocks,
            block_size, dtype,
        )

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        return stacked_paged_decode(
            self.block, params["blocks"], state.get("blocks", {}), cache, x,
            block_tables=block_tables, positions=positions,
        )

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        return stacked_paged_verify(
            self.block, params["blocks"], state.get("blocks", {}), cache, x,
            block_tables=block_tables, positions=positions,
        )

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        return stacked_paged_prefill(
            self.block, params["blocks"], state.get("blocks", {}), cache, x,
            block_table=block_table, start=start,
        )
