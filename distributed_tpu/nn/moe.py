"""Mixture-of-Experts layer with expert parallelism.

Not in the reference (dense CNN only — SURVEY.md §2c "Expert parallelism
(EP / MoE): NO"); built so the 'expert' mesh axis (parallel.mesh.AXES) is a
working capability, not a reserved name.

TPU-first design choices:
- **Dense dispatch** (Shazeer-style einsum with one-hot combine tensors):
  no sorting, no dynamic shapes, no scatter — everything is static-shape
  einsums that tile onto the MXU and jit into one XLA program.
- **Capacity factor**: each expert processes a fixed ``capacity`` tokens per
  batch; overflow tokens are dropped from that expert (their combine weight
  is zero, so they pass through the residual unchanged in a transformer
  block). Static capacity is what makes the computation shape-static.
- **Expert parallelism**: expert weight stacks are (E, din, dout); the
  sharding hint 'expert' splits dim 0 across the 'expert' mesh axis, and
  GSPMD turns the dispatch/combine einsums into all-to-alls over ICI.
- Router computes in float32; a load-balancing auxiliary loss (Switch
  Transformer's fraction*probability form) is returned in state under
  ``"aux_loss"`` so training can add it to the objective.

``DroplessMoE`` below is the other formulation, DeepSeek-V3's: sigmoid
scores, no capacity and no dropped pair, the (token, choice) pairs sorted by
expert into one buffer and multiplied by ``ops.grouped_matmul``, and a layer
that holds a share of the experts (``experts_held``) while routing over all
of them. ``MoE`` stays for what it alone does: the GSPMD all-to-all over the
'expert' mesh axis, cached decode, the auxiliary loss.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import core, initializers
from .core import Layer, Shape, child_scope
from .layers import GatedMLP
from ..ops import grouped_matmul as gmm, moe_rows
from ..quant import maybe_dequantize
from ..precision import resolve_dtype


class MoE(Layer):
    """Token-choice top-k MoE over (B, T, D) or (B, D) inputs.

    Output shape == input shape (experts are D -> hidden -> D MLPs).
    """

    def __init__(
        self,
        num_experts: int,
        hidden_dim: int,
        *,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        group_size: int = 1024,
        activation: str = "gelu",
        aux_loss_weight: float = 0.01,
        dtype=None,
        name: Optional[str] = None,
    ):
        """``group_size``: tokens are routed within fixed-size groups (the
        Mesh-TF/Switch formulation) so the dispatch/combine one-hots are
        O(tokens * group * k), linear in batch tokens — global routing would
        be quadratic. Capacity is per group."""
        super().__init__(name)
        if top_k < 1 or top_k > num_experts:
            raise ValueError(
                f"top_k must be in [1, num_experts={num_experts}], got {top_k}"
            )
        self.num_experts = int(num_experts)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.group_size = int(group_size)
        self.activation = activation
        self.aux_loss_weight = float(aux_loss_weight)
        self.dtype = dtype

    def default_name(self) -> str:
        return "moe"  # the camel-case splitter would produce "mo_e"

    def init(self, key, input_shape: Shape):
        d = input_shape[-1]
        e, h = self.num_experts, self.hidden_dim
        k_router, k_in, k_out = jax.random.split(key, 3)
        glorot = initializers.get("glorot_uniform")
        params = {
            "router": glorot(k_router, (d, e), jnp.float32),
            "w_in": glorot(k_in, (e, d, h), jnp.float32),
            "b_in": jnp.zeros((e, h), jnp.float32),
            "w_out": glorot(k_out, (e, h, d), jnp.float32),
            "b_out": jnp.zeros((e, d), jnp.float32),
        }
        # aux_loss lives in state from init so the state STRUCTURE never
        # changes between a fresh model and one that has stepped (checkpoint
        # restore compares structures).
        return params, {"aux_loss": jnp.float32(0.0)}, tuple(input_shape)

    def sharding_hints(self):
        # dim 0 (the expert stack) splits across the 'expert' mesh axis.
        return {
            "w_in": "expert",
            "b_in": "expert",
            "w_out": "expert",
            "b_out": "expert",
        }

    def _group_size(self, n_tokens: int) -> int:
        # Groups are always full-width: awkward token counts (primes, odd
        # batch*seq products) are PADDED up to a group boundary rather than
        # shrinking the group — a tiny group would collapse capacity to ~1
        # and silently drop most routing choices.
        return min(self.group_size, n_tokens)

    def _capacity(self, group: int) -> int:
        c = int(self.capacity_factor * self.top_k * group
                / self.num_experts) or 1
        return min(c, group)

    def _route(self, tokens_f32, router):
        """Shared routing math for apply() and decode(): softmax router
        probs -> top-k choice -> renormalized gates. tokens_f32 is
        (..., d) float32; returns (probs, gate_vals, gate_idx)."""
        logits = jnp.einsum(
            "...d,de->...e", tokens_f32, router,
            preferred_element_type=jnp.float32,
        )
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )
        return probs, gate_vals, gate_idx

    def apply(self, params, state, x, *, train=False, rng=None):
        from . import activations

        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        b, t, d = x.shape
        n = b * t
        e, k = self.num_experts, self.top_k
        g = self._group_size(n)
        ng = -(-n // g)  # number of routing groups (ceil)
        n_pad = ng * g
        cap = self._capacity(g)
        act = activations.get(self.activation)

        flat = x.reshape(n, d)
        if n_pad != n:
            flat = jnp.concatenate(
                [flat, jnp.zeros((n_pad - n, d), flat.dtype)], axis=0
            )
        tokens = flat.reshape(ng, g, d)
        # (G, g) validity mask; pad tokens are excluded from dispatch (they
        # consume no capacity) and from the aux loss statistics.
        token_valid = (jnp.arange(n_pad) < n).astype(jnp.float32)
        # Evaluation pads its final BATCH too (training/model.py keeps the
        # step shape static): the eval step publishes per-example validity
        # weights, and those rows must not route. For eval's own pads
        # (always appended AFTER real rows, so cumsum dispatch priority
        # already favors the real ones) the effect is on the load-balance
        # aux statistics, which were biased exactly on the models whose
        # eval loss is watched (VERDICT r4 weak #6); for zero-weighted
        # rows in arbitrary positions the exclusion also keeps them from
        # consuming expert capacity ahead of later valid rows.
        sample_w = core.current_sample_weights()
        if sample_w is not None:
            # Binarize: the dispatch position math (cumsum over one-hot
            # choices) requires 0/1 validity — a fractional weight would
            # make slot positions non-integral and alias buffer slots.
            per_tok = jnp.broadcast_to(
                (sample_w > 0).astype(jnp.float32)[:, None], (b, t)
            ).reshape(n)
            if n_pad != n:
                per_tok = jnp.concatenate(
                    [per_tok, jnp.zeros((n_pad - n,), jnp.float32)]
                )
            token_valid = token_valid * per_tok
        valid = token_valid.reshape(ng, g)
        n_valid = jnp.maximum(jnp.sum(valid), 1.0)
        # Router probs + top-k choice + renormalized gates (shared with
        # decode()). probs: (G, g, e); gate_vals/gate_idx: (G, g, k).
        probs, gate_vals, gate_idx = self._route(
            tokens.astype(jnp.float32), maybe_dequantize(params["router"])
        )

        # Position of each (token, choice) in its expert's per-group buffer;
        # tokens beyond capacity are dropped (combine weight zeroed).
        choice_onehot = (
            jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)
            * valid[:, :, None, None]
        )  # (G,g,k,e)
        pos = (
            jnp.cumsum(choice_onehot.reshape(ng, g * k, e), axis=1) - 1.0
        ).reshape(ng, g, k, e)
        within = pos < cap
        dispatch_w = choice_onehot * within  # (G, g, k, e)
        pos_onehot = jax.nn.one_hot(
            (pos * choice_onehot).sum(-1).astype(jnp.int32), cap,
            dtype=jnp.float32,
        )  # (G, g, k, cap)
        # dispatch[G, n, e, c] = 1 iff group-G token n sits in slot c of
        # expert e's buffer for that group.
        dispatch = jnp.einsum("Gnke,Gnkc->Gnec", dispatch_w, pos_onehot)
        combine = jnp.einsum("Gnk,Gnke,Gnkc->Gnec", gate_vals, dispatch_w,
                             pos_onehot)

        # Expert buffers: (G, e, cap, d) -> MLP -> back. All MXU einsums.
        compute_dtype = resolve_dtype(self.dtype) or tokens.dtype
        buf = jnp.einsum(
            "Gnec,Gnd->Gecd", dispatch.astype(compute_dtype),
            tokens.astype(compute_dtype),
        )
        hid = act(
            jnp.einsum("Gecd,edh->Gech", buf,
                       maybe_dequantize(params["w_in"]).astype(compute_dtype))
            + params["b_in"][None, :, None].astype(compute_dtype)
        )
        out_buf = (
            jnp.einsum("Gech,ehd->Gecd", hid,
                       maybe_dequantize(params["w_out"]).astype(compute_dtype))
            + params["b_out"][None, :, None].astype(compute_dtype)
        )
        out = jnp.einsum(
            "Gnec,Gecd->Gnd", combine.astype(compute_dtype), out_buf
        )

        # Switch-style load-balance loss: E * sum_e fraction_e * prob_e,
        # averaged over *valid* tokens only (batch-pad rows excluded when
        # the eval step publishes sample weights).
        frac = jnp.sum(choice_onehot[:, :, 0], axis=(0, 1)) / n_valid
        mean_prob = (
            jnp.sum(probs * valid[:, :, None], axis=(0, 1)) / n_valid
        )
        aux = self.aux_loss_weight * e * jnp.sum(frac * mean_prob)

        out = out.reshape(n_pad, d)[:n].reshape(b, t, d).astype(x.dtype)
        if squeeze:
            out = out[:, 0]
        return out, {"aux_loss": aux}

    # ------------------------------------------------- incremental decode --
    # apply() mixes positions through group capacity (tokens compete for
    # expert slots), so the inherited default decode would be silently
    # wrong. This override routes each token droplessly: capacity never
    # binds for one token at inference, which matches apply() exactly
    # whenever apply() dropped nothing, and is the standard serving
    # behavior when it did.
    decode_safe = True

    def decode(self, params, state, cache, x, *, pos):
        from . import activations

        act = activations.get(self.activation)
        b, t, d = x.shape  # t == 1
        e, k = self.num_experts, self.top_k
        flat = x.reshape(b * t, d)
        _, gate_vals, gate_idx = self._route(
            flat.astype(jnp.float32), maybe_dequantize(params["router"])
        )  # (N, k)
        # Per-expert combine weight: sum of the gates that chose it.
        weight = jnp.einsum(
            "nk,nke->ne", gate_vals,
            jax.nn.one_hot(gate_idx, e, dtype=jnp.float32),
        )  # (N, e)
        compute_dtype = resolve_dtype(self.dtype) or x.dtype
        h = act(
            jnp.einsum("nd,edh->neh", flat.astype(compute_dtype),
                       maybe_dequantize(params["w_in"]).astype(compute_dtype))
            + params["b_in"][None].astype(compute_dtype)
        )
        out_e = (
            jnp.einsum("neh,ehd->ned", h,
                       maybe_dequantize(params["w_out"]).astype(compute_dtype))
            + params["b_out"][None].astype(compute_dtype)
        )
        out = jnp.einsum(
            "ne,ned->nd", weight.astype(compute_dtype), out_e
        )
        return out.reshape(b, t, d).astype(x.dtype), cache


# ------------------------------------------------------ dropless experts --
# The rows go to the experts' buffer and back by ``ops.moe_rows``'s two
# walks over the tiles in use. Pairs are laid out choice-major, pair
# j * n + i being token i's j-th choice. ``walk`` is what both walks follow:
# ``(row_pair, rows_in_tile, tiles_used)``, the pair of each buffer row, the
# valid rows of each tile and the tiles in use.
@jax.custom_vjp
def _dispatch(flat, walk):
    """Rows of ``flat`` (n, d) into the experts' buffer (M, d): a valid row
    of a tile in use holds its pair's token, a padded one zeros; the tiles
    not in use are not written (nobody reads them: the grouped matmuls
    follow ``tiles_used`` too). Its transpose is the other walk: token i's
    gradient is the f32 sum of the buffer rows of its pairs."""
    return moe_rows.gather_rows(flat, *walk)


def _dispatch_fwd(flat, walk):
    return _dispatch(flat, walk), (walk, flat.shape[0])


def _dispatch_bwd(res, d_buf):
    walk, n = res
    return moe_rows.sum_rows(d_buf, *walk, n), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out_buf, gates, walk):
    """``y[i] = sum_j gates[j, i] * out_buf[row of pair (j, i)]`` over the
    pairs held, in float32: each valid buffer row, times its pair's gate,
    added to its token. ``gates`` (k, n) float32; a pair whose expert is
    not held has no row and adds nothing. Backward, one walk: each buffer
    row takes its token's dy times its gate, and its dot with that dy is
    its gate's gradient."""
    return moe_rows.sum_rows(out_buf, *walk, gates.shape[1],
                             pair_scale=gates)


def _combine_fwd(out_buf, gates, walk):
    return _combine(out_buf, gates, walk), (out_buf, gates, walk)


def _combine_bwd(res, dy):
    out_buf, gates, walk = res
    d_out, d_gates = moe_rows.gather_rows(
        dy, *walk, pair_scale=gates, dot_with=out_buf)
    return d_out, d_gates, None


_combine.defvjp(_combine_fwd, _combine_bwd)

# Names of a DroplessMoE's counters in its state (kind "moe").
_COUNTERS = ("steps", "pairs", "held_rows", "load_max_sum", "tiles_used",
             "buffer_tiles")


class DroplessMoE(Layer):
    """DeepSeek-V3's expert layer over (..., D) inputs, for the share of the
    experts this chip holds.

    Routing is over all ``num_experts``: scores ``s = sigmoid(x Wr)`` in
    float32; a token's ``top_k`` experts are the largest of ``s + b``; their
    gates are ``s`` (without ``b``) over the sum of the ``top_k``, times
    ``routed_scaling``. ``b`` is noaux_tc's per-expert selection bias, a
    buffer in the layer's state under ``router_bias`` with no gradient:
    the balancing of the family (DeepSeek-V3, arXiv:2412.19437, section
    2.1.2). Every train step ends by moving it ``bias_update_rate`` down for
    each expert that took more pairs than the mean expert in that step's
    batch and as much up for each that took fewer (the paper trains with
    0.001, the default here; 0 freezes it). No auxiliary loss.
    ``scoring="softmax"`` is Qwen3-MoE's router (``norm_topk_prob``) in the
    same place: scores are the softmax over all ``num_experts`` in float32,
    a token's experts the ``top_k`` largest, no selection bias
    (``bias_update_rate`` is ignored and the buffer stays at zeros), gates
    as above.

    The layer holds experts ``[expert_offset, expert_offset +
    experts_held)`` (all of them by default) and returns the part of the
    result they give,

        y = sum over a token's chosen experts held here of gate * expert(x)
            + shared(x)

    ``shared`` one gated MLP of ``shared_hidden_dim`` on every token (none
    at 0). What the experts held elsewhere add is left out; nothing stands
    in for them, and on one chip no exchange runs. Experts are bias-free
    gated SiLU MLPs (``GatedMLP``'s mathematics) of ``hidden_dim``.

    No capacity and no dropped pair: the (token, choice) pairs of held
    experts are sorted by expert (a counting sort: a pair's rank in its
    expert's group is a running count) into one buffer of static shape,
    sized for every pair landing here, each group starting on a tile
    boundary (``ops.grouped_matmul.group_layout``). The experts' gated MLP
    runs over the groups' tiles in use (``ops.grouped_matmul.
    grouped_gated_mlp``: three grouped matmuls forward and six backward,
    the activation, its backward and the sum of the buffer's two gradients
    in their epilogues), and the rows go back weighted by their gates.
    Dispatch, combine and both their transposes follow the tiles in use too
    (``ops.moe_rows``): into the buffer, each valid row of a tile in use
    fetches its token's row (dispatch; combine's backward, which also takes
    each row's dot with dy for its gate's gradient); out of it, each valid
    row is added in float32 to its token (combine, times the pair's gate;
    dispatch's backward). A pair whose expert is not held has no row, costs
    nothing and adds exactly zero; the tiles not in use are neither written
    nor read nor stepped over by anything between dispatch and combine, in
    either pass, so what the layer moves and computes follows the share it
    holds (``moe.buffer_used_pct`` of the buffer), not the buffer's static
    worst case. What the layer still does for every expert held, whatever
    its rows: the weights' casts to the compute dtype.

    Device scopes under the layer's own (``moe``): ``route`` (router, top-k,
    sort, the row walks both ways), ``experts`` (the weights' casts and the
    nine grouped matmuls) and ``shared``. Counters, cumulative over
    train steps, in the layer's state and so read with no device sync
    inside a step loop (``core.counters``' "moe"): ``steps``, ``pairs``
    (tokens x ``top_k``), ``held_rows`` (pairs whose expert is held here),
    ``load_max_sum`` (the busiest of all ``num_experts`` experts' pairs,
    summed over steps), ``tiles_used`` (tiles of the buffer the step went
    over) and ``buffer_tiles`` (tiles of its static worst case: their ratio
    is the gauge ``moe.buffer_used_pct``). ``record_choice`` adds an output
    for whoever compares the layer with another implementation: ``choice``
    in the state, the experts the last train step chose for the first
    example of its batch,
    (..., top_k) in the shape of one example. Routing is discrete, so such a
    comparison has to start from the same experts before it can see rounding.
    """

    def __init__(self, num_experts: int, hidden_dim: int, *, top_k: int,
                 experts_held: Optional[int] = None, expert_offset: int = 0,
                 shared_hidden_dim: int = 0, routed_scaling: float = 1.0,
                 bias_update_rate: float = 1e-3, record_choice: bool = False,
                 scoring: str = "sigmoid", dtype=None,
                 name: Optional[str] = None):
        super().__init__(name)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(
                f"scoring must be 'sigmoid' or 'softmax', got {scoring!r}")
        self.scoring = scoring
        self.num_experts = int(num_experts)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.experts_held = int(
            num_experts if experts_held is None else experts_held)
        self.expert_offset = int(expert_offset)
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k must be in [1, num_experts={num_experts}], got {top_k}")
        if (self.experts_held < 1 or self.expert_offset < 0
                or self.expert_offset + self.experts_held > self.num_experts):
            raise ValueError(
                f"experts [{expert_offset}, {expert_offset} + {experts_held})"
                f" are not among the {num_experts} routed over")
        self.routed_scaling = float(routed_scaling)
        # Softmax scoring has no selection bias: the buffer stays at zeros.
        self.bias_update_rate = (float(bias_update_rate)
                                 if scoring == "sigmoid" else 0.0)
        self.record_choice = bool(record_choice)
        self.dtype = dtype
        self.shared = (GatedMLP(shared_hidden_dim, dtype=dtype)
                       if shared_hidden_dim else None)

    def default_name(self) -> str:
        return "moe"

    def init(self, key, input_shape: Shape):
        d = input_shape[-1]
        g, h = self.experts_held, self.hidden_dim
        k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 5)
        glorot = initializers.get("glorot_uniform")
        params = {
            "router": glorot(k_router, (d, self.num_experts), jnp.float32),
            "w_gate": glorot(k_gate, (g, d, h), jnp.float32),
            "w_up": glorot(k_up, (g, d, h), jnp.float32),
            "w_down": glorot(k_down, (g, h, d), jnp.float32),
        }
        if self.shared is not None:
            params["shared"] = self.shared.init(k_shared, input_shape)[0]
        state = {"router_bias": jnp.zeros((self.num_experts,), jnp.float32)}
        state.update({c: jnp.float32(0.0) for c in _COUNTERS})
        if self.record_choice:
            state["choice"] = jnp.zeros(
                tuple(input_shape[:-1]) + (self.top_k,), jnp.int32)
        return params, state, tuple(input_shape)

    def sharding_hints(self):
        hints = {"w_gate": "expert", "w_up": "expert", "w_down": "expert"}
        if self.shared is not None:
            hints["shared"] = self.shared.sharding_hints()
        return hints

    def route(self, tokens_f32, router, bias):
        """``(expert index, gate)``, each (n, top_k): see the class."""
        logits = jnp.dot(tokens_f32, router,
                         precision=jax.lax.Precision.HIGHEST)
        if self.scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            bias = 0.0
        else:
            scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias, self.top_k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        gates = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        return idx, gates * self.routed_scaling

    def apply(self, params, state, x, *, train=False, rng=None):
        dt = resolve_dtype(self.dtype) or x.dtype
        d = x.shape[-1]
        flat = x.reshape(-1, d).astype(dt)
        n, k, g = flat.shape[0], self.top_k, self.experts_held
        rows = gmm.buffer_rows(n * k, g)
        with jax.named_scope("route"):
            idx, gates = self.route(
                flat.astype(jnp.float32), maybe_dequantize(params["router"]),
                state["router_bias"])
            # (k, n), choice-major: pair j * n + i is token i's j-th choice.
            local = idx.T - self.expert_offset
            held = jnp.logical_and(local >= 0, local < g)
            group = jnp.where(held, local, g).reshape(-1)
            # Counting sort by expert: a pair's rank is how many earlier
            # pairs chose its expert.
            onehot = (group[:, None] == jnp.arange(g)[None]).astype(jnp.int32)
            rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                           axis=1)
            sizes = jnp.sum(onehot, axis=0)
            row_starts, tile_group, tiles_used = gmm.group_layout(
                sizes, rows // gmm.TILE_M)
            start = jnp.take(row_starts, jnp.minimum(group, g - 1))
            dest = jnp.where(held.reshape(-1), start + rank, rows)
            # The inverse, pair of a buffer row: one scatter of n*k ints
            # (pairs not held fall outside the buffer and are dropped).
            src_pair = jnp.full((rows,), n * k, jnp.int32).at[dest].set(
                jnp.arange(n * k, dtype=jnp.int32), mode="drop")
            walk = (src_pair,
                    moe_rows.tile_rows(sizes, row_starts, tile_group,
                                       tiles_used),
                    tiles_used)
            buf = _dispatch(flat, walk)
        with jax.named_scope("experts"):
            weight = lambda name: maybe_dequantize(params[name]).astype(dt)
            out_buf = gmm.grouped_gated_mlp(
                buf, weight("w_gate"), weight("w_up"), weight("w_down"),
                tile_group, tiles_used)
        with jax.named_scope("route"):
            y = _combine(out_buf, jnp.where(held, gates.T, 0.0), walk)
        if self.shared is not None:
            with child_scope("shared"):
                y = y + self.shared.apply(params["shared"], {}, flat)[0]
        y = y.reshape(x.shape).astype(x.dtype)
        if not train:
            return y, {}
        with jax.named_scope("route"):
            loads = jnp.sum(
                idx.reshape(-1)[:, None] == jnp.arange(self.num_experts)[None],
                axis=0)
            # noaux_tc: overloaded experts down a notch, underloaded up.
            bias = state["router_bias"] + self.bias_update_rate * jnp.sign(
                n * k / self.num_experts - loads.astype(jnp.float32))
            new_state = dict(
                state, router_bias=bias,
                steps=state["steps"] + 1.0,
                pairs=state["pairs"] + float(n * k),
                held_rows=state["held_rows"] + jnp.sum(sizes).astype(
                    jnp.float32),
                load_max_sum=state["load_max_sum"] + jnp.max(loads).astype(
                    jnp.float32),
                tiles_used=state["tiles_used"] + tiles_used[0].astype(
                    jnp.float32),
                buffer_tiles=state["buffer_tiles"] + float(
                    rows // gmm.TILE_M),
            )
            if self.record_choice:
                new_state["choice"] = idx.reshape(x.shape[:-1] + (k,))[0]
        return y, new_state


def _gauges(layers):
    # The layers' sums, and the tiles the row walks and the grouped matmuls
    # went over, of the tiles of the buffers' static worst case.
    total = lambda name: sum(c[name] for c in layers.values())
    return {"moe.pairs": total("pairs"), "moe.held_rows": total("held_rows"),
            "moe.buffer_used_pct": 100.0 * total("tiles_used") / max(
                total("buffer_tiles"), 1.0)}


core.counts("moe", _COUNTERS, _gauges)
