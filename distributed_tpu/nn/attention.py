"""Attention and positional embedding layers.

The reference has no attention anywhere (SURVEY.md §2c: inputs are 28x28
images); these layers exist so long-context/distributed training is shaped
into the core design (mesh axes 'seq'/'model' in parallel.mesh.AXES) rather
than bolted on. TPU notes:

- Scores/softmax compute in float32 regardless of activation dtype; the
  einsums lower to MXU matmuls.
- QKV projections are stored as 2D (D, heads*head_dim) kernels so Megatron
  TP is a plain PartitionSpec: q/k/v column-sharded over the 'model' axis
  (splitting heads), output projection row-sharded — XLA inserts the
  all-reduce after the row matmul.
- The causal mask is built from static shapes (no dynamic control flow), so
  the whole layer jits into one XLA program.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import initializers
from .core import Layer, Shape, child_scope, read_counters
from ..obs.registry import default_registry
from ..ops.index_scores import (
    block_index_scores, index_scores, kernel_fits, tile_counts)
from ..precision import resolve_dtype
from ..quant import _QMAX, QKEY, SKEY, dequantize, maybe_dequantize, shape_of


def _kv_block_size(pool) -> int:
    """Block size of one paged layer pool — plain K/V array or an int8
    ``{"q","scale"}`` quantized pair (quant.py's plain-dict idiom)."""
    return (pool[QKEY] if isinstance(pool, dict) else pool).shape[1]


def _kv_scatter(pool, blk, off, rows):
    """Scatter freshly-computed K/V ``rows`` (..., H, hd) into
    ``pool[blk, off]`` (index arrays share the rows' leading shape).

    Plain pools write the rows as-is (cast to the pool dtype). int8 pools
    quantize ON SCATTER, row-wise: unlike weight quantization (one static
    scale per output channel — ``quant.quantize_leaf``), KV rows are
    data-dependent per position, so each (position, head) row gets its own
    dynamic scale ``amax(|row|)/127`` stored alongside the int8 payload.
    All-zero rows get scale 1 so the dequant stays finite — and the trash
    block, which is never written by a live slot, dequantizes to exact
    zeros."""
    if not isinstance(pool, dict):
        return pool.at[blk, off].set(rows.astype(pool.dtype))
    r = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(r), axis=-1, keepdims=True)  # (..., H, 1)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0)
    q = jnp.clip(jnp.round(r / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return {
        QKEY: pool[QKEY].at[blk, off].set(q),
        SKEY: pool[SKEY].at[blk, off].set(scale),
    }


class MultiHeadAttention(Layer):
    """Multi-head self-attention over (B, T, D) inputs."""

    def __init__(
        self,
        num_heads: int,
        head_dim: Optional[int] = None,
        *,
        causal: bool = False,
        use_bias: bool = True,
        dtype=None,
        ring_axis: Optional[str] = "seq",
        flash="auto",
        name: Optional[str] = None,
    ):
        """``ring_axis``: when the ambient strategy's mesh has this axis with
        size > 1 (sequence parallelism), attention runs as ring attention
        over it (ops.ring_attention) — K/V rotate between sequence shards
        instead of being all-gathered. Irrelevant (dense path) otherwise;
        set None to force dense attention even under a seq mesh.

        ``flash``: True runs the Pallas flash-attention kernel
        (ops.flash_attention — O(T*D) HBM instead of the (T, T) score
        tensor); False keeps the dense einsum path; "auto" (default) uses
        flash on TPU for sequences >= 512. Under a sharded mesh the kernel
        runs per-shard via shard_map (parallel.auto_shard) so GSPMD never
        replicates it; ring attention still takes precedence under a seq
        mesh."""
        super().__init__(name)
        self.num_heads = int(num_heads)
        self.head_dim = head_dim
        self.causal = bool(causal)
        self.use_bias = use_bias
        self.dtype = dtype
        self.ring_axis = ring_axis
        self.flash = flash

    def init(self, key, input_shape: Shape):
        d = input_shape[-1]
        hd = self.head_dim or d // self.num_heads
        if self.head_dim is None and d % self.num_heads:
            raise ValueError(
                f"d_model {d} not divisible by num_heads {self.num_heads}"
            )
        inner = self.num_heads * hd
        keys = jax.random.split(key, 4)
        init = initializers.get("glorot_uniform")
        params = {
            "wq": init(keys[0], (d, inner), jnp.float32),
            "wk": init(keys[1], (d, inner), jnp.float32),
            "wv": init(keys[2], (d, inner), jnp.float32),
            "wo": init(keys[3], (inner, d), jnp.float32),
        }
        if self.use_bias:
            params.update(
                bq=jnp.zeros((inner,), jnp.float32),
                bk=jnp.zeros((inner,), jnp.float32),
                bv=jnp.zeros((inner,), jnp.float32),
                bo=jnp.zeros((d,), jnp.float32),
            )
        return params, {}, tuple(input_shape)

    def sharding_hints(self):
        hints = {"wq": "col", "wk": "col", "wv": "col", "wo": "row"}
        if self.use_bias:
            hints.update(bq="col", bk="col", bv="col")
        return hints

    def _ring_config(self):
        """(mesh, batch_axis, mode) when sequence-parallel attention should
        run ('ring' or 'ulysses' per the strategy), else None. Reads the
        ambient strategy at trace time (Model enters its strategy scope
        around step tracing)."""
        if self.ring_axis is None:
            return None
        from ..parallel.strategy import current_strategy

        strat = current_strategy()
        mesh = getattr(strat, "mesh", None)
        if mesh is None or self.ring_axis not in mesh.axis_names:
            return None
        if int(mesh.shape[self.ring_axis]) <= 1:
            return None
        batch_axis = getattr(strat, "axis", None)
        if batch_axis not in mesh.axis_names:
            batch_axis = None
        mode = getattr(strat, "seq_attention", "ring")
        return mesh, batch_axis, mode

    def _ulysses_attention(self, q, k, v, mesh, batch_axis):
        """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism: two
        sharding constraints reshard (B, T/s, H, d) -> (B, T, H/s, d) and
        back — GSPMD lowers each to one all-to-all over the seq axis — so
        every device runs full-sequence attention for its head slice. One
        collective pair per layer vs ring's n-1 ppermutes; requires
        num_heads divisible by the seq-axis size.

        Per head shard the attention runs the flash (blockwise) kernel via
        shard_map, so device memory is O(T*d) — at the long contexts
        Ulysses exists for, a dense per-shard (T, T) score matrix would
        reintroduce exactly the O(T^2) the seq axis removed. ``flash=False``
        on the layer keeps the dense path (debug/tiny-T escape hatch)."""
        import functools

        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops.flash_attention import flash_attention
        from ..parallel.auto_shard import shard_rows

        seq_axis = self.ring_axis
        n_seq = int(mesh.shape[seq_axis])
        h = self.num_heads
        if h % n_seq:
            raise ValueError(
                f"Ulysses attention shards heads over the {seq_axis!r} "
                f"axis: num_heads {h} not divisible by its size {n_seq}"
            )
        head_sh = NamedSharding(mesh, P(batch_axis, None, seq_axis, None))
        seq_sh = NamedSharding(mesh, P(batch_axis, seq_axis, None, None))
        wsc = jax.lax.with_sharding_constraint
        q, k, v = (wsc(a, head_sh) for a in (q, k, v))
        # Same gating as the main path (_use_flash): 'auto' takes the
        # blockwise kernel only at long T on a TPU backend — on CPU/GPU the
        # Pallas interpret/fallback path would be far slower than dense.
        if not self._use_flash(q.shape[1]):
            from ..ops.flash_attention import dense_attention

            ctx = dense_attention(q, k, v, self.causal)
        else:
            fn = functools.partial(flash_attention, causal=self.causal)
            spec = P(batch_axis, None, seq_axis, None)
            ctx = shard_rows(
                fn, (q, k, v), (spec, spec, spec), spec,
                allowed_axes={batch_axis, seq_axis},
            )
        return wsc(ctx, seq_sh)

    def _use_flash(self, t: int) -> bool:
        if self.flash is True:
            return True
        if self.flash == "auto":
            return t >= 512 and jax.default_backend() == "tpu"
        return False

    def _flash_call(self, q, k, v):
        """Flash attention, per-shard under the ambient mesh (batch on the
        strategy's data axis, heads on the Megatron 'model' axis)."""
        import functools

        from jax.sharding import PartitionSpec as P

        from ..ops.flash_attention import flash_attention
        from ..parallel.auto_shard import ambient_mesh, shard_rows

        fn = functools.partial(flash_attention, causal=self.causal)
        mesh, batch_axis, model_axis = ambient_mesh()
        if mesh is None:
            return fn(q, k, v)
        spec = P(batch_axis, None, model_axis, None)
        return shard_rows(fn, (q, k, v), (spec, spec, spec), spec)

    def _proj(self, params, x, w, b):
        # Weight-only int8 (quant.py): dequantize in-trace; compute dtype
        # handling below is unchanged.
        kernel = maybe_dequantize(params[w])
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            kernel = kernel.astype(dt)
        y = jnp.dot(x, kernel)
        if self.use_bias:
            y = y + params[b].astype(y.dtype)
        return y

    # ------------------------------------------------- incremental decode --
    decode_safe = True  # via the cached override below

    def init_cache(self, params, batch, max_len, dtype):
        inner = shape_of(params["wq"])[1]
        hd = inner // self.num_heads
        shape = (batch, max_len, self.num_heads, hd)
        cdtype = self.dtype or dtype
        return {
            "k": jnp.zeros(shape, cdtype),
            "v": jnp.zeros(shape, cdtype),
        }

    def decode(self, params, state, cache, x, *, pos):
        """One-token attention over the KV cache: x (B, 1, D), the new K/V
        row written at ``pos``, scores masked to positions <= pos."""
        if not self.causal:
            # Cached decode is causal by construction (future rows are
            # zeros); a bidirectional model was trained attending both ways
            # and would silently get different logits here.
            raise NotImplementedError(
                "incremental decode requires causal attention "
                "(MultiHeadAttention(causal=True)); bidirectional models "
                "have no autoregressive decode"
            )
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        b = x.shape[0]
        h = self.num_heads
        hd = shape_of(params["wq"])[1] // h
        q = self._proj(params, x, "wq", "bq").reshape(b, 1, h, hd)
        k = self._proj(params, x, "wk", "bk").reshape(b, 1, h, hd)
        v = self._proj(params, x, "wv", "bv").reshape(b, 1, h, hd)
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)
        )
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, ck, preferred_element_type=jnp.float32
        ) / jnp.sqrt(jnp.float32(hd))  # (B, H, 1, Tmax)
        t_max = ck.shape[1]
        visible = jnp.arange(t_max) <= pos
        scores = jnp.where(
            visible[None, None, None, :], scores, jnp.float32(-1e30)
        )
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", attn, cv).reshape(b, 1, h * hd)
        out = jnp.dot(ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype))
        if self.use_bias:
            out = out + params["bo"].astype(out.dtype)
        return out, {"k": ck, "v": cv}

    # ------------------------------------------- paged (block) KV cache --
    # Serving-engine cache layout (serving.Engine / docs/SERVING.md): one
    # pool of fixed-size blocks shared by every running sequence, indexed
    # through per-slot block tables — HBM is allocated per block on
    # demand instead of max_len per sequence, so heterogeneous lengths
    # share the pool (vLLM-style PagedAttention). Reads gather the slot's
    # blocks into a contiguous view and mask by the slot's position; the
    # gather is plain XLA (no custom kernel), which is exact everywhere
    # and leaves a Pallas gather-attention kernel as a later perf lever
    # (ROADMAP item 4).

    def init_paged_cache(self, params, num_blocks, block_size, dtype):
        inner = shape_of(params["wq"])[1]
        hd = inner // self.num_heads
        shape = (num_blocks, block_size, self.num_heads, hd)
        if dtype is not None and jnp.dtype(dtype) == jnp.dtype("int8"):
            # int8 KV: ~4x fewer pool bytes than f32 (scale adds 1/hd
            # overhead). Same {"q","scale"} plain-dict idiom as quantized
            # weights, but with per-(position, head) DYNAMIC scales
            # (_kv_scatter) — KV values are data-dependent per step, so a
            # static per-channel scale cannot serve them.
            return {
                "k": {QKEY: jnp.zeros(shape, jnp.int8),
                      SKEY: jnp.ones(shape[:-1] + (1,), jnp.float32)},
                "v": {QKEY: jnp.zeros(shape, jnp.int8),
                      SKEY: jnp.ones(shape[:-1] + (1,), jnp.float32)},
            }
        cdtype = self.dtype or dtype
        return {
            "k": jnp.zeros(shape, cdtype),
            "v": jnp.zeros(shape, cdtype),
        }

    def _paged_view(self, pool, block_tables, out_dtype=None, *,
                    visible=None):
        """Gather per-slot blocks into a contiguous (S, nb*bs, H, hd) view
        (logical position j of slot s lives at block_tables[s, j // bs],
        offset j % bs). Plain pools return their own dtype (``out_dtype``
        ignored — the f32/bf16 program is unchanged); int8 pools gather
        q + scale and dequantize IN-TRACE to ``out_dtype``.

        ``visible`` ((S, L) bool, L = nb*bs): rows the caller's causal
        mask can ever expose. On the int8 path masked rows are zeroed
        BEFORE the dequantize multiply (payload -> 0, scale -> 1), so
        trash-block / stale rows dequantize to exact zeros instead of
        ``garbage * scale`` — the reference view then agrees bit-for-bit
        with the fused kernel (ops.paged_attention), which never weights
        those rows, and the dequantize does no work the mask would
        discard. Plain pools ignore it (their masked rows are never
        multiplied un-masked either way)."""
        if isinstance(pool, dict):
            qv = self._paged_view(pool[QKEY], block_tables)
            sv = self._paged_view(pool[SKEY], block_tables)
            if visible is not None:
                vis = visible[:, :, None, None]
                qv = jnp.where(vis, qv, jnp.zeros_like(qv))
                sv = jnp.where(vis, sv, jnp.ones_like(sv))
            return dequantize({QKEY: qv, SKEY: sv}, out_dtype)
        gathered = pool[block_tables]  # (S, nb, bs, H, hd)
        s, nb, bs, h, hd = gathered.shape
        return gathered.reshape(s, nb * bs, h, hd)

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        """One-token attention for S independent slots at per-slot
        positions: x (S, 1, D); each slot's new K/V row is scattered into
        the pool block its position maps to, scores masked to that slot's
        positions <= positions[s]. Inactive slots point their whole block
        table at the engine's trash block, so their writes land harmlessly
        outside every live sequence."""
        if not self.causal:
            raise NotImplementedError(
                "incremental decode requires causal attention "
                "(MultiHeadAttention(causal=True)); bidirectional models "
                "have no autoregressive decode"
            )
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        s = x.shape[0]
        h = self.num_heads
        hd = shape_of(params["wq"])[1] // h
        bs = _kv_block_size(cache["k"])
        q = self._proj(params, x, "wq", "bq").reshape(s, 1, h, hd)
        k = self._proj(params, x, "wk", "bk").reshape(s, h, hd)
        v = self._proj(params, x, "wv", "bv").reshape(s, h, hd)
        blk = jnp.take_along_axis(
            block_tables, (positions // bs)[:, None], axis=1
        )[:, 0]  # (S,) pool block holding each slot's write position
        off = positions % bs
        ck = _kv_scatter(cache["k"], blk, off, k)
        cv = _kv_scatter(cache["v"], blk, off, v)
        from ..ops import paged_attention as paged_ops
        if paged_ops.current_decode_kernel() == paged_ops.FUSED:
            # Fused gather + attention: the block table rides into the
            # kernel as a scalar-prefetch operand; no (S, L, H, hd) view
            # is ever materialized. Scatter stays plain XLA above.
            ctx = paged_ops.paged_attention(
                q, ck, cv, block_tables, positions
            ).reshape(s, 1, h * hd)
        else:
            visible = (
                jnp.arange(block_tables.shape[1] * bs)[None]
                <= positions[:, None]
            )  # (S, L)
            view_k = self._paged_view(
                ck, block_tables, q.dtype, visible=visible
            )  # (S, L, H, hd)
            view_v = self._paged_view(
                cv, block_tables, q.dtype, visible=visible
            )
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, view_k,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(hd))  # (S, H, 1, L)
            scores = jnp.where(
                visible[:, None, None, :], scores, jnp.float32(-1e30)
            )
            attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            ctx = jnp.einsum(
                "bhqk,bkhd->bqhd", attn, view_v
            ).reshape(s, 1, h * hd)
        out = jnp.dot(ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype))
        if self.use_bias:
            out = out + params["bo"].astype(out.dtype)
        return out, {"k": ck, "v": cv}

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        """Speculative-verification attention: x (S, K, D) holds, per
        slot, K CANDIDATE tokens occupying consecutive absolute positions
        [positions[s], positions[s] + K). All K are scored in ONE
        fixed-shape dispatch — the K-wide generalization of paged_decode
        (K=1 degenerates to it): each candidate's K/V row is scattered at
        its own position and its scores are masked causally to
        positions <= its own, so column j's logits equal what K=1 decode
        would produce after accepting candidates 0..j-1. Rejected
        candidates leave stale rows behind; the engine masks them (every
        later read attends only below its own position, and the rows are
        overwritten before ever becoming visible). Non-speculating slots
        ride the trash block exactly as in decode."""
        if not self.causal:
            raise NotImplementedError(
                "incremental decode requires causal attention "
                "(MultiHeadAttention(causal=True)); bidirectional models "
                "have no autoregressive decode"
            )
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        s, kw, _ = x.shape
        h = self.num_heads
        hd = shape_of(params["wq"])[1] // h
        bs = _kv_block_size(cache["k"])
        q = self._proj(params, x, "wq", "bq").reshape(s, kw, h, hd)
        k = self._proj(params, x, "wk", "bk").reshape(s, kw, h, hd)
        v = self._proj(params, x, "wv", "bv").reshape(s, kw, h, hd)
        abs_pos = positions[:, None] + jnp.arange(kw)[None]  # (S, K)
        blk = jnp.take_along_axis(block_tables, abs_pos // bs, axis=1)
        off = abs_pos % bs  # (S, K)
        ck = _kv_scatter(cache["k"], blk, off, k)
        cv = _kv_scatter(cache["v"], blk, off, v)
        from ..ops import paged_attention as paged_ops
        if paged_ops.current_decode_kernel() == paged_ops.FUSED:
            # Same fused kernel as decode: candidate row k of slot s
            # masks itself to positions <= positions[s] + k in-kernel.
            ctx = paged_ops.paged_attention(
                q, ck, cv, block_tables, positions
            ).reshape(s, kw, h * hd)
            out = jnp.dot(
                ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype)
            )
            if self.use_bias:
                out = out + params["bo"].astype(out.dtype)
            return out, {"k": ck, "v": cv}
        ll = block_tables.shape[1] * bs
        # Per-slot union of the K candidates' causal windows — what any
        # row of this dispatch can ever expose (the view-level mask).
        row_vis = (
            jnp.arange(ll)[None, :] <= (positions + kw - 1)[:, None]
        )  # (S, L)
        view_k = self._paged_view(
            ck, block_tables, q.dtype, visible=row_vis
        )  # (S, L, H, hd)
        view_v = self._paged_view(
            cv, block_tables, q.dtype, visible=row_vis
        )
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, view_k,
            preferred_element_type=jnp.float32,
        ) / jnp.sqrt(jnp.float32(hd))  # (S, H, K, L)
        visible = (
            jnp.arange(view_k.shape[1])[None, None, :] <= abs_pos[:, :, None]
        )  # (S, K, L): candidate j attends through its own position
        scores = jnp.where(
            visible[:, None, :, :], scores, jnp.float32(-1e30)
        )
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", attn, view_v).reshape(s, kw,
                                                                  h * hd)
        out = jnp.dot(ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype))
        if self.use_bias:
            out = out + params["bo"].astype(out.dtype)
        return out, {"k": ck, "v": cv}

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        """Prompt-chunk prefill for one sequence: x (1, C, D) covers
        absolute positions [start, start+C). The whole chunk's K/V is
        computed in ONE parallel pass (this is the prefill/decode split —
        prompts never crawl through the one-token decode path), scattered
        into the sequence's blocks, and attention runs against the full
        cached prefix + chunk (so chunked prefill composes: chunk i
        attends to chunks < i through the pool)."""
        if not self.causal:
            raise NotImplementedError(
                "incremental decode requires causal attention "
                "(MultiHeadAttention(causal=True)); bidirectional models "
                "have no autoregressive decode"
            )
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        c = x.shape[1]
        h = self.num_heads
        hd = shape_of(params["wq"])[1] // h
        bs = _kv_block_size(cache["k"])
        q = self._proj(params, x, "wq", "bq").reshape(1, c, h, hd)
        k = self._proj(params, x, "wk", "bk").reshape(c, h, hd)
        v = self._proj(params, x, "wv", "bv").reshape(c, h, hd)
        abs_pos = start + jnp.arange(c)  # (C,)
        blk = block_table[abs_pos // bs]  # (C,)
        off = abs_pos % bs
        ck = _kv_scatter(cache["k"], blk, off, k)
        cv = _kv_scatter(cache["v"], blk, off, v)
        ll = block_table.shape[0] * bs
        chunk_vis = (jnp.arange(ll) <= start + c - 1)[None]  # (1, L)
        view_k = self._paged_view(
            ck, block_table[None], q.dtype, visible=chunk_vis
        )[0]
        view_v = self._paged_view(
            cv, block_table[None], q.dtype, visible=chunk_vis
        )[0]
        scores = jnp.einsum(
            "bqhd,khd->bhqk", q, view_k,
            preferred_element_type=jnp.float32,
        ) / jnp.sqrt(jnp.float32(hd))  # (1, H, C, L)
        visible = (
            jnp.arange(view_k.shape[0])[None, :] <= abs_pos[:, None]
        )  # (C, L): causal against the absolute position of each query
        scores = jnp.where(
            visible[None, None, :, :], scores, jnp.float32(-1e30)
        )
        attn = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        ctx = jnp.einsum("bhqk,khd->bqhd", attn, view_v).reshape(1, c,
                                                                 h * hd)
        out = jnp.dot(ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype))
        if self.use_bias:
            out = out + params["bo"].astype(out.dtype)
        return out, {"k": ck, "v": cv}

    def apply(self, params, state, x, *, train=False, rng=None):
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        b, t, _ = x.shape
        h = self.num_heads
        hd = shape_of(params["wq"])[1] // h  # robust if apply runs on a fresh instance
        q = self._proj(params, x, "wq", "bq").reshape(b, t, h, hd)
        k = self._proj(params, x, "wk", "bk").reshape(b, t, h, hd)
        v = self._proj(params, x, "wv", "bv").reshape(b, t, h, hd)
        ring = self._ring_config()
        if ring is not None:
            mesh, batch_axis, mode = ring
            if mode == "ulysses":
                ctx = self._ulysses_attention(q, k, v, mesh, batch_axis)
            else:
                from ..ops.ring_attention import ring_attention

                ctx = ring_attention(
                    q, k, v,
                    mesh=mesh,
                    seq_axis=self.ring_axis,
                    batch_axis=batch_axis,
                    causal=self.causal,
                )
        elif self._use_flash(t):
            ctx = self._flash_call(q, k, v)
        else:
            from ..ops.flash_attention import dense_attention

            ctx = dense_attention(q, k, v, self.causal)
        ctx = ctx.reshape(b, t, h * hd)
        out = jnp.dot(ctx, maybe_dequantize(params["wo"]).astype(ctx.dtype))
        if self.use_bias:
            out = out + params["bo"].astype(out.dtype)
        return out, {}


def rope_interleaved(x, theta: float):
    """Rotary position embedding on adjacent pairs (``rope_interleave``):
    dimensions (2i, 2i+1) of ``x`` (B, T, H, d) at position t turn by the
    angle t * theta^(-2i/d), from position 0. Float32 inside, ``x``'s dtype
    out. The pair swap (x[2i], x[2i+1]) -> (-x[2i+1], x[2i]) is a product
    with a signed permutation matrix, exact in any dtype, so the MXU does
    it and no lane-strided reshape exists."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1)[None, :, None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1)[None, :, None, :]
    even = jnp.arange(0, d, 2)
    swap = jnp.zeros((d, d), x.dtype)
    swap = swap.at[even + 1, even].set(-1).at[even, even + 1].set(1)
    rotated = x.astype(jnp.float32) * cos + jnp.dot(x, swap).astype(
        jnp.float32) * sin
    return rotated.astype(x.dtype)


class LatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2/V3's MLA, no query
    compression) over (B, T, D) inputs, for training and full forward
    passes:

        q = x Wq                       (T, H, nope + rope)
        [c | k_rope] = x Wkv_a         latent of ``kv_rank``, one rope key
        [k_nope | v] = RMSNorm(c) Wkv_b    (T, H, nope + v_dim)
        k = [k_nope | rope(k_rope)]    the one rope key shared by all heads
        out = softmax(q k^T / sqrt(nope + rope), causal) v  Wo

    with interleaved RoPE on the rope part of q and on k_rope and no
    position table. Queries and keys are wider than values (192 and 128 at
    the published sizes): the flash kernels take both widths as they are
    (``ops.flash_attention``). The scope, and the parameter key, starts with
    ``multi_head_attention``: ``benchmarks/scopes.py`` sorts attention by
    that prefix. No cached decode: a latent paged cache does not exist yet
    (ROADMAP.md)."""

    decode_safe = False

    def __init__(self, num_heads: int, *, kv_rank: int, nope_dim: int,
                 rope_dim: int, v_dim: int, rope_theta: float = 10000.0,
                 epsilon: float = 1e-6, causal: bool = True, dtype=None,
                 flash="auto", name: Optional[str] = None):
        super().__init__(name)
        self.num_heads = int(num_heads)
        self.kv_rank = int(kv_rank)
        self.nope_dim = int(nope_dim)
        self.rope_dim = int(rope_dim)
        self.v_dim = int(v_dim)
        self.rope_theta = float(rope_theta)
        self.epsilon = float(epsilon)
        self.causal = bool(causal)
        self.dtype = dtype
        self.flash = flash

    def default_name(self) -> str:
        return "multi_head_attention_latent"

    def init(self, key, input_shape: Shape):
        d, h = input_shape[-1], self.num_heads
        keys = jax.random.split(key, 4)
        init = initializers.get("glorot_uniform")
        params = {
            "wq": init(keys[0], (d, h * (self.nope_dim + self.rope_dim)),
                       jnp.float32),
            "wkv_a": init(keys[1], (d, self.kv_rank + self.rope_dim),
                          jnp.float32),
            "kv_norm": {"scale": jnp.ones((self.kv_rank,), jnp.float32)},
            "wkv_b": init(keys[2],
                          (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                          jnp.float32),
            "wo": init(keys[3], (h * self.v_dim, d), jnp.float32),
        }
        return params, {}, tuple(input_shape)

    def sharding_hints(self):
        return {"wq": "col", "wkv_b": "col", "wo": "row"}

    _use_flash = MultiHeadAttention._use_flash
    _flash_call = MultiHeadAttention._flash_call

    def apply(self, params, state, x, *, train=False, rng=None):
        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        b, t, _ = x.shape
        h, nope = self.num_heads, self.nope_dim
        proj = lambda a, w: jnp.dot(
            a, maybe_dequantize(params[w]).astype(a.dtype))
        q = proj(x, "wq").reshape(b, t, h, nope + self.rope_dim)
        latent = proj(x, "wkv_a")
        c, k_rope = latent[..., :self.kv_rank], latent[..., self.kv_rank:]
        with child_scope("kv_norm"):
            cf = c.astype(jnp.float32)
            ms = jnp.mean(jnp.square(cf), axis=-1, keepdims=True)
            c = (cf * jax.lax.rsqrt(ms + self.epsilon)
                 * params["kv_norm"]["scale"]).astype(c.dtype)
        kv = proj(c, "wkv_b").reshape(b, t, h, nope + self.v_dim)
        k_rope = rope_interleaved(k_rope[:, :, None, :], self.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], rope_interleaved(q[..., nope:], self.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (b, t, h, self.rope_dim))], axis=-1)
        v = kv[..., nope:]
        if self._use_flash(t):
            ctx = self._flash_call(q, k, v)
        else:
            from ..ops.flash_attention import dense_attention

            ctx = dense_attention(q, k, v, self.causal)
        return proj(ctx.reshape(b, t, h * self.v_dim), "wo"), {}


def rope_half(x, theta: float):
    """Rotary position embedding on the two halves of the last axis
    (``rotate_half``, Llama's and Qwen's): dimensions (i, i + d/2) of ``x``
    (B, T, H, d) at position t turn by the angle t * theta^(-2i/d), from
    position 0. Float32 inside, ``x``'s dtype out."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.tile(jnp.cos(angle), 2)[None, :, None, :]
    sin = jnp.tile(jnp.sin(angle), 2)[None, :, None, :]
    xf = x.astype(jnp.float32)
    turned = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], axis=-1)
    return (xf * cos + turned * sin).astype(x.dtype)


def yarn_inv_freq(dim: int, theta: float, *, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0, truncate: bool = True):
    """YaRN's inverse frequencies (Peng et al. 2023, arXiv:2309.00071) of a
    rotation over ``dim`` dimensions, (dim / 2,) float32, as Hugging Face
    ``transformers`` computes them (``modeling_rope_utils.py:
    _compute_yarn_parameters``): a pair that turns more than ``beta_fast``
    times over the ``original_max_position`` positions keeps its frequency
    theta^(-2i/dim), one that turns fewer than ``beta_slow`` times has it
    divided by ``factor`` (its positions interpolated), and between the
    two, by the pair's index, a linear ramp blends them. Static for a
    layer: NumPy."""
    def correction_dim(rotations):
        return dim * math.log(original_max_position / (
            rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = correction_dim(beta_fast), correction_dim(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # no singularity
    pos_freqs = np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1)  # the interpolated share
    return ((1.0 / (factor * pos_freqs)) * ramp
            + (1.0 / pos_freqs) * (1 - ramp)).astype(np.float32)


def rope_rotary(x, inv_freq, scale: float = 1.0):
    """``rope_half`` with given frequencies on the first ``2 *
    len(inv_freq)`` dimensions of the last axis of ``x`` (B, T, H, d), the
    rest passed through (a partial rotation), and cos and sin times
    ``scale`` (YaRN's attention factor: it scales the rotated dimensions
    alone). Float32 inside, ``x``'s dtype out."""
    t, r = x.shape[1], 2 * len(inv_freq)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None]
    cos = (jnp.tile(jnp.cos(angle), 2) * scale)[None, :, None, :]
    sin = (jnp.tile(jnp.sin(angle), 2) * scale)[None, :, None, :]
    xf = x.astype(jnp.float32)
    rot = xf[..., :r]
    turned = jnp.concatenate([-rot[..., r // 2:], rot[..., :r // 2]], axis=-1)
    return jnp.concatenate(
        [rot * cos + turned * sin, xf[..., r:]], axis=-1).astype(x.dtype)


def _rms(x, scale, epsilon):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + epsilon) * scale).astype(x.dtype)


# ------------------------------------------- learned selection of the keys --
def _mean_probs(q, k, lse, picked):
    """The main attention's probabilities over the selected keys, averaged
    over the heads: (n, T) float32 for a block of queries q (n, H, D), keys k
    (T, Hkv, D), the attention's own row statistic lse (n, H) (each row's
    log-sum-exp of its scaled scores over its selection) and ``picked`` (n,
    T) bool; zero off the selection. One exp(score - lse) a pair, no second
    softmax. A K/V head at a time, its query heads' rows stacked into one
    plain product whose rows end in the keys."""
    n, h, d = q.shape
    t, g = k.shape[0], k.shape[1]
    m = h // g
    total = jnp.zeros((n, t), jnp.float32)
    for kv in range(g):
        heads = slice(kv * m, (kv + 1) * m)
        rows = jnp.moveaxis(q[:, heads], 1, 0).reshape(m * n, d)
        s = jnp.dot(rows, k[:, kv].T, preferred_element_type=jnp.float32
                    ).reshape(m, n, t) / jnp.sqrt(jnp.float32(d))
        p = jnp.exp(s - lse[:, heads].T[:, :, None])
        total = total + jnp.sum(jnp.where(picked, p, 0.0), axis=0)
    return total / h


# Queries a block in which a selecting layer scores, selects and computes
# L_I: a (block, T) float32 score matrix an indexer head is all that is live
# (256 MB at 16 heads and T = 8192).
INDEX_BLOCK = 512


def _cut(a, block: int):
    """``a`` (T, ...) in blocks of queries, (T // n, n, ...): n is ``block``
    where that divides T, else their greatest common divisor."""
    n = math.gcd(a.shape[0], block)
    return a.reshape((a.shape[0] // n, n) + a.shape[1:])


def _select_sequence(qi, ki, w, *, topk, block):
    """The selection (T, T) int8 of one sequence: query t keeps the ``topk``
    keys s <= t with the largest index score, a block of ``block`` queries
    at a time (a (block, T) score matrix a head is all that is live)."""
    from ..ops.topk_select import topk_mask

    t = qi.shape[0]

    def rows_of(blk):
        qi_b, w_b, rows = blk
        causal = rows[:, None] >= jnp.arange(t)[None, :]
        scores = index_scores(qi_b, ki, w_b)
        with jax.named_scope("select"):
            return topk_mask(scores, topk, causal).astype(jnp.int8)

    return jax.lax.map(rows_of, tuple(
        _cut(a, block) for a in (qi, w, jnp.arange(t)))).reshape(t, t)


def select_keys(qi, ki, w, *, topk: int, block: int = INDEX_BLOCK):
    """The learned selection of a batch, (B, T, T) int8, non-zero where query
    t keeps key s: the ``topk`` keys s <= t with the largest index score
    (``index_scores``; every key s <= t while there are no more than
    ``topk``), for the indexer's queries qi (B, T, J, d), key ki (B, T, d)
    and head weights w (B, T, J). No gradient."""
    qi, ki, w = jax.lax.stop_gradient((qi, ki, w))
    one = functools.partial(_select_sequence, topk=topk, block=block)
    return jax.lax.map(lambda a: one(*a), (qi, ki, w))


def _index_loss_sequence(qi, ki, w, q, k, lse, selection, *, block, kernel):
    """(sum over one sequence's queries of the KL, its gradient in (qi, ki,
    w)), a block of queries at a time: ``index_loss``."""
    t, j, d = qi.shape
    n = math.gcd(t, block)
    itemsize = jnp.dtype(qi.dtype).itemsize
    # The scores' gradient recomputes them in a kernel where their shapes
    # tile; else autodiff's, which keeps the (n, J, T) products.
    kernel = kernel and kernel_fits(n, j, d, t, itemsize)
    if kernel:  # static for a shape, published at trace time as counts
        gauge = default_registry().gauge
        for name, count in zip(("square", "computed"),
                               tile_counts(t, n, itemsize)):
            gauge(f"index.tiles_{name}", count)

    def step(d_ki, blk):
        qi_b, w_b, q_b, lse_b, picked, row0 = blk
        picked = picked != 0

        def kl_sum(qi_b, w_b, ki):
            scores = (block_index_scores(qi_b, ki, w_b, row0) if kernel
                      else index_scores(qi_b, ki, w_b))
            target = _mean_probs(q_b, k, lse_b, picked)
            log_index = jax.nn.log_softmax(
                jnp.where(picked, scores, jnp.float32(-1e30)), axis=-1)
            return jnp.sum(jnp.where(
                picked, jax.scipy.special.xlogy(target, target)
                - target * log_index, 0.0))

        kl, (d_qi, d_w, d_ki_b) = jax.value_and_grad(kl_sum, (0, 1, 2))(
            qi_b, w_b, ki)
        return d_ki + d_ki_b.astype(jnp.float32), (kl, d_qi, d_w)

    d_ki, (kl, d_qi, d_w) = jax.lax.scan(
        step, jnp.zeros(ki.shape, jnp.float32), tuple(
            _cut(a, block) for a in (qi, w, q, lse.T, selection)) + (
                jnp.arange(0, t, n),))
    return jnp.sum(kl), (d_qi.reshape(qi.shape), d_ki, d_w.reshape(w.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def index_loss(qi, ki, w, q, k, lse, selection, block=INDEX_BLOCK,
               kernel=False):
    """``L_I``, the loss that trains the indexer: the mean over a batch's
    queries of KL(the main attention's probabilities over the query's
    selection, averaged over its heads || the softmax of the query's index
    scores over its selection) (DeepSeek-V3.2-Exp's sparse training stage),
    for the indexer's qi (B, T, J, d), ki (B, T, d) and w (B, T, J), the
    main attention's q (B, T, H, D), k (B, T, Hkv, D) and row statistic lse
    (B, H, T), and the ``selection`` (B, T, T). Its gradient reaches qi, ki
    and w only: the selection and the main attention's side are constants.
    The gradient is taken where the scores are, in the forward pass, a block
    of queries at a time, so no (T, T) matrix is kept for the backward
    pass. ``kernel``: whether the caller runs its attention in the flash
    kernels; the scores' own gradient then recomputes them a key tile at a
    time in one (``ops/index_scores.py``), where their shapes tile."""
    return _index_loss_fwd(qi, ki, w, q, k, lse, selection, block, kernel)[0]


def _index_loss_fwd(qi, ki, w, q, k, lse, selection, block, kernel):
    one = functools.partial(_index_loss_sequence, block=block, kernel=kernel)
    kl, grads = jax.lax.map(lambda a: one(*a),
                            (qi, ki, w, q, k, lse, selection))
    queries = qi.shape[0] * qi.shape[1]
    scaled = tuple((g / queries).astype(a.dtype)
                   for g, a in zip(grads, (qi, ki, w)))
    return jnp.sum(kl) / queries, (scaled, q, k, lse)


def _index_loss_bwd(block, kernel, res, ct):
    grads, q, k, lse = res
    d_qi, d_ki, d_w = ((g * ct).astype(g.dtype) for g in grads)
    return (d_qi, d_ki, d_w, jnp.zeros_like(q), jnp.zeros_like(k),
            jnp.zeros_like(lse), None)


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)

# Names of a selecting GroupedQueryAttention's counters in its state.
_SELECT_COUNTERS = ("steps", "queries", "causal_pairs", "selected_pairs",
                    "blocks_total", "blocks_computed")
_WINDOW_COUNTERS = ("steps", "queries", "causal_pairs", "window_pairs",
                    "walked_pairs")


class GroupedQueryAttention(Layer):
    """Causal grouped-query self-attention over (B, T, D) inputs with RoPE
    and per-head RMSNorm on queries and keys, for training and full forward
    passes; optionally a learned selection of the keys each query sees
    (DeepSeek-V3.2-Exp's lightning indexer), a sliding window, a partial or
    YaRN-scaled rotation and a head-wise output gate:

        q = rope(RMSNorm_hd(x Wq))      (T, H, hd)
        k = rope(RMSNorm_hd(x Wk))      (T, Hkv, hd)     v = x Wv
        out = softmax(q k^T / sqrt(hd), over the keys seen) v  Wo

    Query head h reads K/V head h // (H / Hkv); RoPE is the half-split
    rotation at ``rope_theta``, from position 0; no bias. Three families'
    layer: Qwen3-MoE's with an indexer (Keye-VL-2.0: ``index_topk``,
    ``index_heads``, ``index_dim``, ``record_selection``), LFM2-MoE's
    attention layers (no optional argument) and Laguna's (``window`` in the
    sliding layers; ``rotary_dim`` and ``rope_scaling`` in the full ones;
    ``gate`` in both).

    Where the flash path is on and a head is 128 wide, exactly one lane
    tile, the norm and the rotation of q and of k are one kernel pass each
    on the projection's own (B, T, H x 128) layout
    (``ops/head_norm_rope.py``: ``dtpu_head_norm_rope`` and its backward,
    which keeps the projection alone), under the scopes ``q_norm`` and
    ``k_norm``, which then hold the rotation too: on a (B, T, H, 128) view
    XLA:TPU relayouts the projection in float32 around both (root PERF.md,
    PR 39). Any other shape, the dense path and the indexer run ``_rms`` and
    ``rope_half`` / ``rope_rotary``, the definition the kernels are held to
    (rounded to the input's dtype after the norm and after the rotation,
    both ways). The trace-time counters ``attn.qk_prep_fused`` and
    ``attn.qk_prep_xla`` count the calls of either path, q and k each. On
    the same condition the gate is one kernel pass each way on the (B, T, H
    x 128) layout the flash kernels return and ``Wo`` reads
    (``ops/head_gate.py``: ``dtpu_head_gate`` and its backward, under the
    scope ``gate``); elsewhere it is the plain lines below, which the
    kernels are held to. ``attn.gate_fused`` and ``attn.gate_xla`` count a
    gated layer's calls of either path.

    ``window``: query t sees the keys t - window < s <= t (the flash
    kernels' ``window``; ``dtpu_flash_*_swa``). ``rotary_dim``: the first
    that many of a head's dimensions are rotated and the rest passed
    through. ``rope_scaling``: a dict with ``rope_type`` ``"yarn"`` and
    YaRN's ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow`` and ``attention_factor`` (default 0.1 ln(factor) + 1): the
    frequencies of ``yarn_inv_freq`` and cos and sin times the factor.
    ``gate``: one gate a head and token from the layer's input, on the
    head's output before ``Wo``:

        out = (softmax(...) v * sigmoid(x Wg)[..., None]) Wo     Wg (D, H)

    With ``index_topk`` the layer carries an indexer of ``index_heads``
    heads of ``index_dim`` and one key head, and query t sees the
    ``index_topk`` keys s <= t with the largest index score (every key s <= t
    while there are no more than that):

        qI = rope(x WqI)   (T, J, dI)       kI = rope(LayerNorm(x WkI))  (T, dI)
        w = x Ww / sqrt(J dI)               I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])

    The selection has no gradient: the model's loss flows through the keys
    selected as through a constant mask. The indexer learns from ``L_I``,
    the KL between the main attention's probabilities and the index scores'
    softmax over the selection (``index_loss``), with the block's input a
    constant too, so ``L_I`` moves ``indexer``'s leaves and nothing else. A
    train step hands ``L_I`` to the objective through the state key
    ``aux_loss`` (``training/model.py`` adds every such key to the loss).
    The index scores are computed twice a train step, a block of
    ``INDEX_BLOCK`` queries at a time: once to select (``select_keys``),
    and, after the attention whose row statistic the probabilities are
    recomputed from, once more with their gradient (``index_loss``).

    Device scopes under the layer's own (which starts with
    ``multi_head_attention``, so ``benchmarks/scopes.py`` sorts all of it
    under attention; ``multi_head_attention_swa`` with a window, else
    ``multi_head_attention_gqa``): ``indexer`` (its projections, the scores,
    ``L_I`` and its gradient) and, inside it, ``select``; ``gate`` (the
    gate's projection, its sigmoid and its product with the heads'
    outputs). A windowed layer counts in its state, cumulative over train
    steps (``window_counters``): ``steps``, ``queries``, ``causal_pairs``
    (pairs s <= t), ``window_pairs`` (those inside the window) and
    ``walked_pairs``, the pairs of the sub-tiles the windowed kernels
    computed (``flash_attention.subtile_counts``; 0 on the dense path).
    A selecting layer's counters, cumulative over
    train steps, in the layer's state (``select_counters``): ``steps``,
    ``queries``, ``causal_pairs`` (pairs s <= t), ``selected_pairs``, and the
    flash kernels' grid blocks at or below the diagonal, ``blocks_total``,
    with those that held a selected pair and were walked,
    ``blocks_computed`` (both 0 on the dense path). ``record_selection`` adds
    ``selection`` to the state: the selection of the first example of the
    last train step, bit-packed along the keys, (T, T / 8) uint8, for
    whoever compares the layer with another implementation: a selection is
    discrete, and such a comparison has to start from the same keys.

    The scope, and the parameter key, starts with ``multi_head_attention``.
    No cached decode (ROADMAP.md: an indexer cache does not exist yet)."""

    decode_safe = False

    def __init__(self, num_heads: int, num_kv_heads: int, head_dim: int, *,
                 rope_theta: float = 10000.0, epsilon: float = 1e-6,
                 index_topk: Optional[int] = None, index_heads: int = 16,
                 index_dim: int = 64,
                 record_selection: bool = False,
                 window: Optional[int] = None,
                 rotary_dim: Optional[int] = None,
                 rope_scaling: Optional[dict] = None, gate: bool = False,
                 dtype=None, flash="auto",
                 name: Optional[str] = None):
        super().__init__(name)
        self.num_heads = int(num_heads)
        self.num_kv_heads = int(num_kv_heads)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {num_heads} is no multiple of num_kv_heads "
                f"{num_kv_heads}")
        self.head_dim = int(head_dim)
        self.rope_theta = float(rope_theta)
        self.epsilon = float(epsilon)
        self.index_topk = int(index_topk) if index_topk else None
        self.index_heads = int(index_heads)
        self.index_dim = int(index_dim)
        self.record_selection = bool(record_selection) and bool(index_topk)
        self.window = int(window) if window else None
        if self.window and self.index_topk:
            raise ValueError("a layer takes a window or an indexer, not both")
        self.rotary_dim = int(rotary_dim or head_dim)
        if not 0 < self.rotary_dim <= self.head_dim or self.rotary_dim % 2:
            raise ValueError(
                f"rotary_dim {rotary_dim} is no even width within the "
                f"head's {head_dim}")
        # The rotation's frequencies and the factor on cos and sin, where
        # they are not ``rope_half``'s own (None: that function as it is).
        self.rotation = None
        if rope_scaling and rope_scaling.get("rope_type") != "default":
            if rope_scaling.get("rope_type") != "yarn":
                raise ValueError(
                    f"rope_scaling of type {rope_scaling.get('rope_type')!r}"
                    "; 'yarn' and 'default' are what the layer computes")
            factor = float(rope_scaling["factor"])
            scale = rope_scaling.get("attention_factor")
            self.rotation = (yarn_inv_freq(
                self.rotary_dim, self.rope_theta, factor=factor,
                original_max_position=int(
                    rope_scaling["original_max_position_embeddings"]),
                beta_fast=float(rope_scaling.get("beta_fast") or 32),
                beta_slow=float(rope_scaling.get("beta_slow") or 1),
                truncate=bool(rope_scaling.get("truncate", True))),
                float(scale) if scale else (
                    0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0))
        elif self.rotary_dim < self.head_dim:
            r = self.rotary_dim
            self.rotation = (1.0 / np.float32(self.rope_theta) ** (
                np.arange(0, r, 2, dtype=np.float32) / np.float32(r)), 1.0)
        self.gate = bool(gate)
        self.dtype = dtype
        self.flash = flash

    def default_name(self) -> str:
        return ("multi_head_attention_swa" if self.window
                else "multi_head_attention_gqa")

    def _rope(self, x):
        if self.rotation is None:
            return rope_half(x, self.rope_theta)
        return rope_rotary(x, *self.rotation)

    def _rotation(self):
        """``_rope``'s frequencies and factor, for ``head_norm_rope``:
        ``rope_half``'s own, computed as it computes them, where the layer
        has no others."""
        if self.rotation is not None:
            return self.rotation
        d = self.head_dim
        return 1.0 / (self.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)), 1.0

    def init(self, key, input_shape: Shape):
        t, d = input_shape[-2], input_shape[-1]
        h, g, hd = self.num_heads, self.num_kv_heads, self.head_dim
        keys = jax.random.split(key, 8 if self.gate else 7)
        init = initializers.get("glorot_uniform")
        ones = lambda n: {"scale": jnp.ones((n,), jnp.float32)}
        params = {
            "wq": init(keys[0], (d, h * hd), jnp.float32),
            "wk": init(keys[1], (d, g * hd), jnp.float32),
            "wv": init(keys[2], (d, g * hd), jnp.float32),
            "wo": init(keys[3], (h * hd, d), jnp.float32),
            "q_norm": ones(hd), "k_norm": ones(hd),
        }
        state = {}
        if self.gate:
            params["wg"] = init(keys[7], (d, h), jnp.float32)
        if self.window:
            state = {c: jnp.float32(0.0) for c in _WINDOW_COUNTERS}
        if self.index_topk:
            j, di = self.index_heads, self.index_dim
            params["indexer"] = {
                "wq": init(keys[4], (d, j * di), jnp.float32),
                "wk": init(keys[5], (d, di), jnp.float32),
                "k_norm": {"scale": jnp.ones((di,), jnp.float32),
                           "bias": jnp.zeros((di,), jnp.float32)},
                "ww": init(keys[6], (d, j), jnp.float32),
            }
            state = {"aux_loss": jnp.float32(0.0)}
            state.update({c: jnp.float32(0.0) for c in _SELECT_COUNTERS})
            if self.record_selection:
                state["selection"] = jnp.zeros((t, -(-t // 8)), jnp.uint8)
        return params, state, tuple(input_shape)

    def sharding_hints(self):
        hints = {"wq": "col", "wk": "col", "wv": "col", "wo": "row"}
        return dict(hints, wg="col") if self.gate else hints

    _use_flash = MultiHeadAttention._use_flash

    def _indexer(self, params, x):
        """The indexer's queries, key and head weights of the block's input
        (a constant: the indexer's loss moves the indexer alone)."""
        dt = x.dtype
        b, t, _ = x.shape
        j, di = self.index_heads, self.index_dim
        x = jax.lax.stop_gradient(x)
        proj = lambda w: jnp.dot(x, maybe_dequantize(params[w]).astype(dt))
        qi = rope_half(proj("wq").reshape(b, t, j, di), self.rope_theta)
        with child_scope("k_norm"):
            kf = proj("wk").astype(jnp.float32)
            kf = kf - jnp.mean(kf, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(kf), axis=-1, keepdims=True)
            ki = (kf * jax.lax.rsqrt(var + self.epsilon)
                  * params["k_norm"]["scale"] + params["k_norm"]["bias"]
                  ).astype(dt)
        ki = rope_half(ki[:, :, None, :], self.rope_theta)[:, :, 0]
        w = proj("ww").astype(jnp.float32) / jnp.sqrt(jnp.float32(j * di))
        return qi, ki, w

    def apply(self, params, state, x, *, train=False, rng=None):
        from ..ops import flash_attention as fa
        from ..parallel.auto_shard import ambient_mesh

        dt = resolve_dtype(self.dtype)
        if dt is not None:
            x = x.astype(dt)
        b, t, _ = x.shape
        h, g, hd = self.num_heads, self.num_kv_heads, self.head_dim
        proj = lambda a, w: jnp.dot(
            a, maybe_dequantize(params[w]).astype(a.dtype))
        flash = self._use_flash(t) and ambient_mesh()[0] is None
        # A head is one lane tile: what takes a selection in Mosaic, what
        # norms and rotates q and k on the projections' own layout, and what
        # gates the heads' outputs on the flash kernels' own.
        kernels = flash and hd == 128
        count = default_registry().counter
        if kernels:
            from ..ops.head_norm_rope import head_norm_rope

            heads = lambda a: a.reshape(b, t, -1, hd)
            q, k, v = proj(x, "wq"), proj(x, "wk"), heads(proj(x, "wv"))
            prep = functools.partial(head_norm_rope, epsilon=self.epsilon)
            rotation = self._rotation()
            with child_scope("q_norm"):
                q = heads(prep(q, params["q_norm"]["scale"], rotation))
            with child_scope("k_norm"):
                k = heads(prep(k, params["k_norm"]["scale"], rotation))
            count("attn.qk_prep_fused", 2)
        else:
            q = proj(x, "wq").reshape(b, t, h, hd)
            k = proj(x, "wk").reshape(b, t, g, hd)
            v = proj(x, "wv").reshape(b, t, g, hd)
            with child_scope("q_norm"):
                q = _rms(q, params["q_norm"]["scale"], self.epsilon)
            with child_scope("k_norm"):
                k = _rms(k, params["k_norm"]["scale"], self.epsilon)
            q, k = self._rope(q), self._rope(k)
            count("attn.qk_prep_xla", 2)
        selection = None
        blocks = (jnp.float32(0.0), 0)
        if self.index_topk:
            with child_scope("indexer"):
                index = self._indexer(params["indexer"], x)
                selection = select_keys(*index, topk=self.index_topk,
                                        block=INDEX_BLOCK)
        walked = 0  # pairs of the sub-tiles the windowed kernels compute
        if self.window:
            if flash:
                ctx = fa.flash_attention(q, k, v, causal=True,
                                         window=self.window)
                walked = fa.walked_pairs(
                    t, h, hd, jnp.dtype(q.dtype).itemsize, self.window)
            else:
                ctx = fa.dense_attention(q, k, v, True, window=self.window)
        elif selection is None:
            ctx = (fa.flash_attention(q, k, v, causal=True) if flash
                   else fa.dense_attention(q, k, v, True))
        elif kernels:
            flags, total = fa.selection_blocks(selection, *fa.resolve_blocks(
                t, jnp.dtype(q.dtype).itemsize))
            blocks = (jnp.sum(flags).astype(jnp.float32), b * total)
            ctx, lse = fa.flash_attention(
                q, k, v, causal=True, selection=selection,
                selection_flags=flags, return_lse=True)
        else:
            ctx, lse = fa.dense_attention(q, k, v, True, selection,
                                          return_lse=True)
        if self.gate:
            with child_scope("gate"):
                z = proj(x, "wg")
                if kernels:
                    from ..ops.head_gate import head_gate

                    ctx = head_gate(ctx.reshape(b, t, h * hd), z)
                    count("attn.gate_fused")
                else:
                    g = jax.nn.sigmoid(z.astype(jnp.float32))
                    ctx = (ctx.astype(jnp.float32) * g[..., None]).astype(
                        ctx.dtype)
                    count("attn.gate_xla")
        out = proj(ctx.reshape(b, t, h * hd), "wo")
        if train and self.window:
            w = min(self.window, t)
            return out, dict(
                steps=state["steps"] + 1.0,
                queries=state["queries"] + float(b * t),
                causal_pairs=state["causal_pairs"] + float(
                    b * t * (t + 1) // 2),
                window_pairs=state["window_pairs"] + float(
                    b * (w * (w + 1) // 2 + (t - w) * w)),
                walked_pairs=state["walked_pairs"] + float(b * walked))
        if not (train and self.index_topk):
            return out, {}
        with child_scope("indexer"):
            loss = index_loss(*index, *jax.lax.stop_gradient((q, k)), lse,
                              selection, INDEX_BLOCK, kernels)
            new_state = dict(
                state, aux_loss=loss,
                steps=state["steps"] + 1.0,
                queries=state["queries"] + float(b * t),
                causal_pairs=state["causal_pairs"] + float(
                    b * t * (t + 1) // 2),
                selected_pairs=state["selected_pairs"] + jnp.sum(
                    selection, dtype=jnp.float32),
                blocks_total=state["blocks_total"] + float(blocks[1]),
                blocks_computed=state["blocks_computed"] + blocks[0])
            if self.record_selection:
                new_state["selection"] = jnp.packbits(
                    selection[0].astype(bool), axis=-1)
        return out, new_state


def window_counters(state) -> dict:
    """``{layer path: {counter: value}}`` of every windowed
    ``GroupedQueryAttention`` in a model's ``state`` tree
    (``core.read_counters``)."""
    return read_counters(state, _WINDOW_COUNTERS)


def select_counters(state) -> dict:
    """``{layer path: {counter: value}}`` of every selecting
    ``GroupedQueryAttention`` in a model's ``state`` tree
    (``core.read_counters``)."""
    return read_counters(state, _SELECT_COUNTERS)


class PositionalEmbedding(Layer):
    """Learned absolute positions, added to (B, T, D) activations."""

    def __init__(self, max_len: int, name: Optional[str] = None):
        super().__init__(name)
        self.max_len = int(max_len)

    def init(self, key, input_shape: Shape):
        t, d = input_shape
        if t > self.max_len:
            raise ValueError(
                f"sequence length {t} exceeds max_len {self.max_len}"
            )
        table = initializers.normal(0.02)(key, (self.max_len, d), jnp.float32)
        return {"table": table}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None):
        t = x.shape[1]
        table = maybe_dequantize(params["table"])
        return x + table[:t][None].astype(x.dtype), {}

    decode_safe = True  # positional rows picked by ``pos``, not x.shape

    def init_cache(self, params, batch, max_len, dtype):
        if max_len > self.max_len:
            raise ValueError(
                f"generation length {max_len} exceeds positional table "
                f"max_len {self.max_len}"
            )
        return {}

    def decode(self, params, state, cache, x, *, pos):
        row = jax.lax.dynamic_slice_in_dim(
            maybe_dequantize(params["table"]), pos, 1, axis=0
        )  # (1, D)
        return x + row[None].astype(x.dtype), cache

    def paged_decode(self, params, state, cache, x, *, block_tables,
                     positions):
        # Per-SLOT positions: slot s reads table row positions[s] — the
        # vectorized form of decode()'s single dynamic row.
        rows = jnp.take(
            maybe_dequantize(params["table"]), positions, axis=0
        )  # (S, D)
        return x + rows[:, None].astype(x.dtype), cache

    def paged_verify(self, params, state, cache, x, *, block_tables,
                     positions):
        # Slot s's K candidates sit at positions[s] + 0..K-1.
        kw = x.shape[1]
        abs_pos = positions[:, None] + jnp.arange(kw)[None]  # (S, K)
        rows = jnp.take(
            maybe_dequantize(params["table"]), abs_pos, axis=0
        )  # (S, K, D)
        return x + rows.astype(x.dtype), cache

    def paged_prefill(self, params, state, cache, x, *, block_table, start):
        c = x.shape[1]
        rows = jax.lax.dynamic_slice_in_dim(
            maybe_dequantize(params["table"]), start, c, axis=0
        )  # (C, D)
        return x + rows[None].astype(x.dtype), cache
