"""Pipeline parallelism: GPipe-microbatched stage execution over 'pipe'.

Absent from the reference (single model replica per worker, SURVEY.md §2c
"Pipeline parallelism: NO"); built because the mesh promises `pipe` as a
composable axis (parallel.mesh.AXES) and the strategy hint machinery
anticipates a stacked-blocks layer.

TPU-first design:

- **Stacked stage parameters**: ``PipelinedBlocks`` holds S structurally
  identical blocks as ONE pytree whose leaves have a leading (S, ...) stage
  dimension — a single NamedSharding (dim 0 over 'pipe') places every stage's
  weights on its device; there is no per-stage program or weight exchange.
- **Schedule as data flow, not control flow**: the GPipe schedule is a
  ``lax.scan`` over M + n - 1 ticks inside one ``shard_map``. Each tick every
  device runs its resident stage(s) on the activation it holds and the
  activations hop one rank along the 'pipe' axis via ``lax.ppermute`` — a
  neighbor ICI transfer on a TPU torus. XLA sees one static program; no
  host-side scheduler exists (contrast GPipe/PipeDream's runtime schedulers).
- **Backward for free**: the schedule is reverse-mode differentiable
  (scan + ppermute + psum all have transposes), so ``jax.grad`` of the jitted
  train step yields the reverse pipeline schedule without any hand-written
  backward pass.
- Bubble fraction is the standard GPipe (n-1)/(M+n-1); raise
  ``num_microbatches`` on the strategy to amortize — or switch to
  ``schedule="interleaved"``: each rank holds ``v`` non-contiguous chunks of
  the stack (Megatron's virtual stages; Narayanan et al., 2021) and the tick
  scan circulates every microbatch ``v`` laps around the full ring, cutting
  the bubble to (n-1)/(vM+n-1) — the same n-1 idle ticks amortized over v
  laps of useful ones, each tick now 1/v of a GPipe stage's compute.

Single-device (no 'pipe' axis in the ambient strategy) the same layer runs
its blocks as a weight-stacked ``lax.scan`` — one trace of the block instead
of S inlined copies, which keeps compile time flat in depth.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec

from .core import Layer, Shape


# Trace-time record of the most recent pipelined apply on this thread:
# which schedule ran, over how many stages/microbatches/ticks, and the
# resulting bubble fraction. Model.fit's telemetry exit reads it
# (training/model.py) the same way it reads scan.last_overlap_trace —
# best-effort by design, like the threadlocal strategy scope it mirrors.
_pipeline_trace = threading.local()


def last_pipeline_trace() -> Optional[dict]:
    """``{"schedule", "interleave", "num_stages", "num_microbatches",
    "ticks", "bubble_fraction"}`` from the most recent pipelined apply
    traced on this thread, or None before any (including the sequential
    single-device path, which has no schedule to report)."""
    return getattr(_pipeline_trace, "record", None)


def _live_pipe_mesh(strategy):
    """(mesh, pipe_axis) when the ambient strategy carries a >1-rank pipe
    axis, else (None, None) — the single dispatch used by BOTH the training
    schedule and the ring decode, so they cannot diverge."""
    pipe_axis = getattr(strategy, "pipe_axis", None)
    mesh = getattr(strategy, "mesh", None)
    if (
        pipe_axis is None
        or mesh is None
        or pipe_axis not in mesh.axis_names
        or int(mesh.shape[pipe_axis]) == 1
    ):
        return None, None
    return mesh, pipe_axis


def _stage_spec(pipe_axis):
    return lambda a: PartitionSpec(pipe_axis, *((None,) * (a.ndim - 1)))


class PipelinedBlocks(Layer):
    """S structurally identical shape-preserving blocks, stacked for
    pipeline parallelism.

    ``block_fn()`` must return a fresh ``Layer`` with the same structure each
    call (e.g. ``lambda: nn.Sequential(transformer_block(...))``). Blocks
    must be shape-preserving (input shape == output shape) and stateless
    (BatchNorm-style running stats can't ride a microbatch schedule).

    Under a strategy with a 'pipe' mesh axis (``DataPipelineParallel``) the
    stacked params shard one-stage-per-rank and apply() runs the GPipe
    schedule; under any other strategy the same params run as a sequential
    ``lax.scan`` — identical numerics, which is what the parity tests assert.
    """

    # Incremental decode IS supported (same stacked-cache recipe as
    # ScannedBlocks): caches are stacked with a leading (S, ...) stage dim
    # like the params. Off a pipe mesh, decode() scans the template
    # block's cached one-token step over the full stack. On a LIVE 'pipe'
    # mesh it runs the memory-sharded ring decode instead: each rank keeps
    # only its (S/n)-block param/cache slices resident and the activation
    # hops rank-to-rank via ppermute (generation is inherently sequential
    # through the stack, so every rank executing each hop costs the same
    # total block-compute as the gather-everything form — but no rank ever
    # materializes the full weight stack, which is the reason PP exists).
    # decode_safe stays False so a template whose own decode would silently
    # be wrong still fails loudly inside the scan body.
    decode_safe = False

    def __init__(
        self,
        block_fn: Callable[[], Layer],
        num_blocks: int,
        *,
        schedule: str = "gpipe",
        interleave: int = 1,
        name: Optional[str] = None,
    ):
        """``schedule``: 'gpipe' (default) runs each rank's contiguous
        stage once per microbatch; 'interleaved' splits each rank's stage
        into ``interleave`` virtual chunks and circulates every microbatch
        that many laps around the ring (module docstring) — same numerics,
        smaller bubble, needs ``num_microbatches >= stages`` and
        ``num_blocks % (stages * interleave) == 0``."""
        super().__init__(name)
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if schedule not in ("gpipe", "interleaved"):
            raise ValueError(
                f"schedule must be 'gpipe' or 'interleaved', got {schedule!r}"
            )
        v = int(interleave)
        if schedule == "gpipe" and v != 1:
            raise ValueError(
                "interleave only applies to schedule='interleaved' "
                f"(got interleave={v} with schedule='gpipe')"
            )
        if schedule == "interleaved" and v < 2:
            raise ValueError(
                "schedule='interleaved' needs interleave >= 2 "
                f"(interleave=1 IS the GPipe schedule), got {v}"
            )
        self.num_blocks = int(num_blocks)
        self.schedule = schedule
        self.interleave = v
        self.block_fn = block_fn
        self.block = block_fn()  # template: defines structure + names

    def default_name(self) -> str:
        return "pipelined_blocks"

    @property
    def needs_rng(self) -> bool:
        return getattr(self.block, "needs_rng", False)

    def init(self, key, input_shape: Shape):
        from .scan import init_stacked_blocks

        shape = tuple(input_shape)
        params, _ = init_stacked_blocks(
            self.block_fn, self.block, self.num_blocks, key, shape,
            require_stateless=True, container="PipelinedBlocks",
        )
        return {"blocks": params}, {}, shape

    def sharding_hints(self):
        # Container-level role string: the whole stacked subtree shards its
        # leading (stage) dim over the 'pipe' mesh axis.
        return {"blocks": "pipe"}

    def dtype_hints(self):
        # Same pass-through as ScannedBlocks: stacked params mirror the
        # template block's tree one level down.
        h = self.block.dtype_hints()
        return {"blocks": h} if h is not None and h != {} else {}

    # ------------------------------------------------------------------ apply
    def _stage_rngs(self, rng):
        if rng is None:
            return None
        return jax.random.split(rng, self.num_blocks)

    def _scan_blocks(self, stacked, x, *, train, rngs):
        """Run a stack of block params over x: scan over the stage dim.
        Shared by the sequential path (whole stack) and each pipeline rank's
        stage (its local slice); the scan body itself lives in
        scan.scan_stacked so ScannedBlocks and this layer can't diverge."""
        from .scan import scan_stacked

        # Blocks are validated stateless at init: the state stack is empty.
        out, _ = scan_stacked(self.block, stacked, {}, x,
                              train=train, rngs=rngs)
        return out

    def apply(self, params, state, x, *, train=False, rng=None):
        from ..obs import spans as obs_spans
        from ..parallel.strategy import current_strategy

        stacked = params["blocks"]
        rngs = self._stage_rngs(rng)
        strategy = current_strategy()
        mesh, pipe_axis = _live_pipe_mesh(strategy)
        if mesh is None:
            return self._scan_blocks(stacked, x, train=train, rngs=rngs), {}

        n = int(mesh.shape[pipe_axis])
        v = self.interleave
        if self.num_blocks % (n * v):
            raise ValueError(
                f"{self.num_blocks} blocks not divisible by "
                f"{pipe_axis}={n} stages"
                + (f" x interleave={v} virtual chunks" if v > 1 else "")
            )
        # Batch rows may shard over several axes (CompositeParallel rows
        # over ('data','fsdp')); honor them all so the schedule's shard_map
        # doesn't silently all-gather the extra folds and recompute the
        # pipeline per-slice.
        row_axes = tuple(
            a for a in getattr(strategy, "row_axes", ())
            if a in mesh.axis_names
        ) or (getattr(strategy, "axis", "data"),)
        n_data = 1
        for a in row_axes:
            n_data *= int(mesh.shape.get(a, 1))
        m = int(getattr(strategy, "num_microbatches", n))
        b_global = x.shape[0]
        if b_global % (n_data * m):
            raise ValueError(
                f"batch {b_global} not divisible by data shards ({n_data}) "
                f"x microbatches ({m})"
            )
        if v > 1 and m < n:
            raise ValueError(
                f"interleaved schedule needs num_microbatches >= stages "
                f"(got M={m} < n={n}): a microbatch re-enters rank 0 for "
                f"its next lap M-n ticks after it left, which must not be "
                f"in the past"
            )
        b_local = b_global // n_data
        mb = b_local // m
        ticks = v * m + n - 1
        _pipeline_trace.record = {
            "schedule": self.schedule,
            "interleave": v,
            "num_stages": n,
            "num_microbatches": m,
            "ticks": ticks,
            "bubble_fraction": round((n - 1) / ticks, 6),
        }
        feat_none = (None,) * (x.ndim - 1)
        rows = row_axes if len(row_axes) > 1 else row_axes[0]
        x_spec = PartitionSpec(rows, *feat_none)
        if v > 1:
            # Static reindex for the virtual-stage layout: rank r's
            # contiguous pipe shard, read as v sub-chunks of cs blocks,
            # must hold original chunks j*n + r for laps j = 0..v-1 (each
            # lap advances the microbatch one chunk on every rank, and a
            # full ring pass advances it n chunks). The stacked leading
            # dim stays one pytree; only the block order changes, and the
            # perm is a compile-time constant, so XLA lays the shuffle
            # into the weights' placement rather than a per-tick gather.
            cs = self.num_blocks // (n * v)
            perm = np.concatenate([
                np.arange((j * n + r) * cs, (j * n + r + 1) * cs)
                for r in range(n) for j in range(v)
            ])
            stacked = jax.tree_util.tree_map(lambda l: l[perm], stacked)
            if rngs is not None:
                rngs = rngs[perm]
        p_specs = jax.tree_util.tree_map(_stage_spec(pipe_axis), stacked)
        in_specs = [p_specs, x_spec]
        args = [stacked, x]
        if rngs is not None:
            in_specs.append(PartitionSpec(pipe_axis))
            args.append(rngs)

        scan_blocks = self._scan_blocks

        def gpipe_fn(p_local, x_local, *maybe_rngs):
            r_local = maybe_rngs[0] if maybe_rngs else None
            rank = lax.axis_index(pipe_axis)
            mbs = x_local.reshape((m, mb) + x_local.shape[1:])
            shift = [(j, j + 1) for j in range(n - 1)]

            def tick(recv, t):
                # Rank 0 injects microbatch t (clamped past the end: those
                # ticks' outputs fall in the bubble and are discarded);
                # other ranks consume what arrived from rank-1 last tick.
                inj = lax.dynamic_index_in_dim(
                    mbs, jnp.minimum(t, m - 1), axis=0, keepdims=False
                )
                h = jnp.where(rank == 0, inj, recv)
                # Per-tick rng fold: each microbatch must draw fresh
                # dropout masks, not reuse the stage key M times.
                rngs_t = (
                    None if r_local is None
                    else jax.vmap(jax.random.fold_in, (0, None))(r_local, t)
                )
                y = scan_blocks(p_local, h, train=train, rngs=rngs_t)
                return lax.ppermute(y, pipe_axis, shift), y

            zeros = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
            _, ys = lax.scan(tick, zeros, jnp.arange(m + n - 1))
            # Last rank's ticks n-1 .. m+n-2 hold microbatch outputs 0..m-1.
            outs = ys[n - 1:].reshape((b_local,) + x_local.shape[1:])
            # Publish to every pipe rank (loss/head run replicated on pipe).
            return lax.psum(
                jnp.where(rank == n - 1, outs, jnp.zeros_like(outs)),
                pipe_axis,
            )

        def interleaved_fn(p_local, x_local, *maybe_rngs):
            # v laps over the FULL ring (rank n-1 wraps to rank 0). At
            # tick t rank r runs lap j = (t-r)//M on microbatch (t-r)%M
            # using its j-th resident chunk; a microbatch leaves rank n-1
            # at lap j and re-enters rank 0 for lap j+1 exactly M-n ticks
            # later, so rank 0 banks every wrap-around arrival in an
            # (M, mb, ...) buffer keyed by microbatch index (M >= n makes
            # the write land no later than the tick that reads it; ticks
            # outside a rank's active window compute on garbage that the
            # bubble discards, same as GPipe's clamped injections).
            r_local = maybe_rngs[0] if maybe_rngs else None
            rank = lax.axis_index(pipe_axis)
            mbs = x_local.reshape((m, mb) + x_local.shape[1:])
            ring = [(j, (j + 1) % n) for j in range(n)]

            def tick(carry, t):
                recv, buf = carry
                # Incoming recv at tick t is rank n-1's tick t-1 output:
                # microbatch (t-n) mod M, banked for its next lap.
                buf = lax.dynamic_update_index_in_dim(
                    buf, recv, jnp.mod(t - n, m), axis=0
                )
                u = t - rank
                lap = jnp.clip(u // m, 0, v - 1)
                mbi = jnp.mod(u, m)
                inj = lax.dynamic_index_in_dim(
                    mbs, mbi, axis=0, keepdims=False
                )
                re_entry = lax.dynamic_index_in_dim(
                    buf, mbi, axis=0, keepdims=False
                )
                h = jnp.where(
                    rank == 0, jnp.where(lap == 0, inj, re_entry), recv
                )
                chunk = jax.tree_util.tree_map(
                    lambda l: lax.dynamic_slice_in_dim(
                        l, lap * cs, cs, axis=0
                    ),
                    p_local,
                )
                rngs_t = (
                    None if r_local is None
                    else jax.vmap(jax.random.fold_in, (0, None))(
                        lax.dynamic_slice_in_dim(
                            r_local, lap * cs, cs, axis=0
                        ),
                        t,
                    )
                )
                y = scan_blocks(chunk, h, train=train, rngs=rngs_t)
                return (lax.ppermute(y, pipe_axis, ring), buf), y

            zeros = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
            buf0 = jnp.zeros((m, mb) + x_local.shape[1:], x_local.dtype)
            (_, _), ys = lax.scan(tick, (zeros, buf0), jnp.arange(ticks))
            # Rank n-1's final-lap ticks (v-1)M+n-1 .. vM+n-2 hold
            # microbatch outputs 0..M-1 in order.
            outs = ys[(v - 1) * m + n - 1:].reshape(
                (b_local,) + x_local.shape[1:]
            )
            return lax.psum(
                jnp.where(rank == n - 1, outs, jnp.zeros_like(outs)),
                pipe_axis,
            )

        local_fn = interleaved_fn if v > 1 else gpipe_fn
        with obs_spans.span("pipeline_schedule"):
            out = shard_map(
                local_fn,
                mesh=mesh,
                in_specs=tuple(in_specs),
                out_specs=x_spec,
                check_vma=False,
            )(*args)
        return out, {}

    # ---------------------------------------------------- incremental decode
    def init_cache(self, params, batch, max_len, dtype):
        from .scan import stacked_init_cache

        return stacked_init_cache(
            self.block, self.num_blocks, params["blocks"], batch, max_len,
            dtype,
        )

    # Paged (block KV) serving works on the sequential single-device path:
    # the pools stack with a leading (S, ...) stage dim (scan.py's
    # stacked-pool layout) and each hook scans the template block's paged
    # step over the stack. On a LIVE pipe mesh it stays a loud raise: the
    # serving engine's block allocator, prefix store, and copy-on-write
    # are host-side state over ONE pool address space, and a pipe-sharded
    # stack would give every rank a different pool — serve off the pipe
    # mesh (where PP's memory argument doesn't apply: decode holds one
    # token of activations, not a training batch).
    def _no_paged_on_pipe_mesh(self):
        from ..parallel.strategy import current_strategy

        mesh, pipe_axis = _live_pipe_mesh(current_strategy())
        if mesh is not None:
            raise NotImplementedError(
                "PipelinedBlocks paged serving is single-device only: the "
                "paged pool's allocator/prefix/copy-on-write state is "
                "host-side and assumes one pool address space, which a "
                f"{pipe_axis}-sharded stack would split across ranks — "
                "serve this model OFF the pipe mesh (the sequential path "
                "supports the full paged engine)"
            )

    def init_paged_cache(self, params, num_blocks, block_size, dtype):
        from .scan import stacked_init_paged_cache

        self._no_paged_on_pipe_mesh()
        return stacked_init_paged_cache(
            self.block, self.num_blocks, params["blocks"], num_blocks,
            block_size, dtype,
        )

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        from .scan import stacked_paged_decode

        self._no_paged_on_pipe_mesh()
        return stacked_paged_decode(
            self.block, params["blocks"], {}, cache, x,
            block_tables=block_tables, positions=positions,
        )

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        from .scan import stacked_paged_verify

        self._no_paged_on_pipe_mesh()
        return stacked_paged_verify(
            self.block, params["blocks"], {}, cache, x,
            block_tables=block_tables, positions=positions,
        )

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        from .scan import stacked_paged_prefill

        self._no_paged_on_pipe_mesh()
        return stacked_paged_prefill(
            self.block, params["blocks"], {}, cache, x,
            block_table=block_table, start=start,
        )

    def decode(self, params, state, cache, x, *, pos):
        from ..parallel.strategy import current_strategy
        from .scan import stacked_decode

        mesh, pipe_axis = _live_pipe_mesh(current_strategy())
        stacked = params["blocks"]
        if mesh is not None:
            # Loud failures for every config the ring schedule can't run:
            # silently taking the gather-everything path would materialize
            # the full stack on every device — the opposite of what a pipe
            # mesh promises.
            if self.num_blocks % int(mesh.shape[pipe_axis]):
                raise ValueError(
                    f"{self.num_blocks} blocks not divisible by "
                    f"{pipe_axis}={int(mesh.shape[pipe_axis])} stages"
                )
            if not jax.tree_util.tree_leaves(cache):
                raise ValueError(
                    "PipelinedBlocks.decode on a live pipe mesh needs a "
                    "per-block cache (the template block's init_cache "
                    "returned nothing) — a cacheless stack would scan the "
                    "pipe-sharded params and all-gather the full stack on "
                    "every rank; decode off the pipe mesh instead"
                )
        if mesh is None:
            return stacked_decode(self.block, stacked, {}, cache, x, pos=pos)

        # Memory-sharded ring decode (class comment): every rank holds its
        # local stage slice; all ranks start from the replicated token
        # activation, and after hop i rank i holds the TRUE activation —
        # so rank r's cache write is kept only at iteration r, and after n
        # hops the final output has wrapped around to rank 0.
        n = int(mesh.shape[pipe_axis])
        block = self.block

        p_specs = jax.tree_util.tree_map(_stage_spec(pipe_axis), stacked)
        c_specs = jax.tree_util.tree_map(
            _stage_spec(pipe_axis), cache["blocks"]
        )
        x_spec = PartitionSpec(*((None,) * x.ndim))

        def local_fn(p_local, c_local, h, pos):
            my = lax.axis_index(pipe_axis)
            perm = [(j, (j + 1) % n) for j in range(n)]

            def hop(carry, i):
                h, c = carry
                y, new_c = stacked_decode(
                    block, p_local, {}, {"blocks": c}, h, pos=pos
                )
                new_c = new_c["blocks"]
                keep = i == my
                c = jax.tree_util.tree_map(
                    lambda nl, ol: jnp.where(keep, nl, ol), new_c, c
                )
                return (lax.ppermute(y, pipe_axis, perm), c), None

            (h, c_local), _ = lax.scan(hop, (h, c_local), jnp.arange(n))
            out = lax.psum(
                jnp.where(my == 0, h, jnp.zeros_like(h)), pipe_axis
            )
            return out, c_local

        out, new_blocks = shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(p_specs, c_specs, x_spec, PartitionSpec()),
            out_specs=(x_spec, c_specs),
            check_vma=False,
        )(stacked, cache["blocks"], x, jnp.asarray(pos))
        return out, {"blocks": new_blocks}
