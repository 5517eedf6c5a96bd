"""Decoder-only Transformer language model.

Not present in the reference (no attention of any kind, SURVEY.md §2c); this
is the model family that exercises the framework's long-context/TP design:
pre-LN blocks built from the same Residual/Sequential primitives as ResNet,
MultiHeadAttention + MLP carrying Megatron tensor-parallel sharding hints
(q/k/v + MLP-in column-sharded over the 'model' mesh axis, projections
row-sharded), so ``DataTensorParallel`` distributes it with zero
model-side changes. Pairs with the Pallas fused cross-entropy for the
large-vocab LM head.
"""

from __future__ import annotations

from typing import Optional

from .. import nn
from ..obs.registry import default_registry


def transformer_block(
    d_model: int,
    num_heads: int,
    d_ff: int,
    *,
    causal: bool = True,
    moe_experts: int = 0,
    flash="auto",
    dtype=None,
) -> list:
    """Pre-LN block as two Residuals: [LN -> MHA] + [LN -> MLP-or-MoE].

    ``moe_experts > 0`` swaps the dense MLP for an nn.MoE with that many
    experts (expert-parallel under DataExpertParallel). ``flash`` passes
    through to MultiHeadAttention (True/False/'auto')."""
    attn = nn.Residual(
        nn.Sequential(
            [
                nn.LayerNorm(),
                nn.MultiHeadAttention(num_heads, causal=causal, flash=flash,
                                      dtype=dtype),
            ],
            name="main",
        )
    )
    if moe_experts:
        ffn_layers = [nn.LayerNorm(), nn.MoE(moe_experts, d_ff, dtype=dtype)]
    else:
        # Flat layer list (not nested in a named container): the param tree
        # paths residual_N/main/{dense,dense_1} are a checkpoint format.
        ffn_layers = [
            nn.LayerNorm(),
            nn.Dense(d_ff, activation="gelu", shard="col", dtype=dtype),
            nn.Dense(d_model, shard="row", dtype=dtype),
        ]
    mlp = nn.Residual(nn.Sequential(ffn_layers, name="main"))
    return [attn, mlp]


def transformer_lm(
    vocab_size: int,
    *,
    num_layers: int = 2,
    d_model: int = 128,
    num_heads: int = 4,
    d_ff: Optional[int] = None,
    max_len: int = 512,
    causal: bool = True,
    moe_experts: int = 0,
    moe_every: int = 2,
    pipeline: bool = False,
    pipeline_schedule: str = "gpipe",
    pipeline_interleave: int = 1,
    scan: bool = False,
    scan_overlap: str = "auto",
    remat: bool = False,
    remat_policy=None,
    flash="auto",
    dtype=None,
) -> nn.Sequential:
    """Token-in, logits-out LM: (B, T) int32 -> (B, T, vocab).

    Train with ``loss="sparse_categorical_crossentropy"`` (or the fused
    ``"pallas_sparse_categorical_crossentropy"``) on next-token labels.
    ``moe_experts > 0`` makes every ``moe_every``-th block's FFN a MoE.
    ``pipeline=True`` stacks the blocks in an ``nn.PipelinedBlocks`` so they
    pipeline over the 'pipe' mesh axis under ``DataPipelineParallel`` (and
    run as a weight-stacked scan otherwise); incompatible with MoE blocks
    (aux-loss state can't ride the microbatch schedule).
    ``pipeline_schedule``/``pipeline_interleave`` forward
    ``nn.PipelinedBlocks(schedule=, interleave=)`` — ``"interleaved"``
    with ``interleave=v`` gives each pipe rank ``v`` non-contiguous stage
    chunks, shrinking the bubble from (n-1)/(M+n-1) to (n-1)/(vM+n-1).
    ``scan=True`` stacks them in an ``nn.ScannedBlocks`` — one lax.scan over
    weight-stacked blocks, keeping static op count and compile time
    depth-independent; generation works through stacked KV caches
    (ScannedBlocks.decode scans the cached one-token step over the stack).
    ``scan_overlap`` forwards ``ScannedBlocks(overlap=)`` ('auto' | 'off' |
    'require'): under an FSDP-family strategy the scan prefetches layer
    i+1's parameter all-gather behind layer i's compute.
    ``remat=True`` wraps every attention/FFN residual in ``nn.Remat`` —
    backward recomputes block activations instead of holding them in HBM
    (identical numerics and checkpoint paths, O(1)-blocks activation
    memory). ``remat_policy`` forwards a ``jax.checkpoint_policies`` entry
    (e.g. ``dots_with_no_batch_dims_saveable`` keeps matmul outputs and
    recomputes only the elementwise chains).
    """
    d_ff = d_ff or 4 * d_model
    layers = [
        nn.Embedding(vocab_size, d_model, dtype=dtype),
        nn.PositionalEmbedding(max_len),
    ]
    if pipeline or scan:
        if moe_experts:
            raise ValueError(
                "pipeline/scan block stacking does not support MoE blocks"
            )
        if pipeline and scan:
            raise ValueError("pipeline and scan are mutually exclusive")

        def make_block():
            block = nn.Sequential(
                transformer_block(
                    d_model, num_heads, d_ff, causal=causal, flash=flash,
                    dtype=dtype,
                )
            )
            return nn.Remat(block, policy=remat_policy) if remat else block

        if pipeline:
            layers.append(nn.PipelinedBlocks(
                make_block, num_layers,
                schedule=pipeline_schedule, interleave=pipeline_interleave,
            ))
        else:
            layers.append(nn.ScannedBlocks(
                make_block, num_layers, overlap=scan_overlap,
            ))
    else:
        for i in range(num_layers):
            moe = moe_experts if (moe_experts and i % moe_every == moe_every - 1) else 0
            block = transformer_block(
                d_model, num_heads, d_ff, causal=causal, moe_experts=moe,
                flash=flash, dtype=dtype,
            )
            if remat:
                block = [nn.Remat(residual, policy=remat_policy)
                         for residual in block]
            layers += block
    layers += [nn.LayerNorm(), nn.Dense(vocab_size, dtype=dtype)]
    return nn.Sequential(layers, name="transformer_lm")


def deepseek_v3_lm(
    vocab_size: int,
    *,
    num_layers: int,
    d_model: int,
    num_heads: int,
    kv_rank: int,
    nope_dim: int,
    rope_dim: int,
    v_dim: int,
    d_ff: int,
    num_experts: int,
    top_k: int,
    moe_hidden: int,
    shared_experts: int = 0,
    first_dense: int = 1,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    routed_scaling: float = 1.0,
    bias_update_rate: float = 1e-3,
    record_choice: bool = False,
    rope_theta: float = 10000.0,
    epsilon: float = 1e-6,
    flash="auto",
    dtype=None,
) -> nn.Sequential:
    """The DeepSeek-V3 block as a token-in, logits-out LM (kanana-2-30b-a3b
    is one; ``model_type: deepseek_v3``, no query compression): pre-RMSNorm
    residual blocks of ``nn.LatentAttention`` and a gated SiLU MLP. The
    first ``first_dense`` layers' MLP is dense of width ``d_ff``; the rest
    are ``nn.DroplessMoE`` over ``num_experts`` experts of ``moe_hidden``,
    ``top_k`` a token, with one shared gated MLP of ``shared_experts *
    moe_hidden``. ``experts_held`` and ``expert_offset`` give this chip's
    share of every expert layer under expert parallelism: the experts it
    holds and computes, while routing stays over all ``num_experts``.
    ``bias_update_rate`` is the step of noaux_tc's balancing of the selection
    bias (0 freezes it) and ``record_choice`` keeps each expert layer's last
    choices in its state (``nn.DroplessMoE``). A final RMSNorm and an
    untied, bias-free head; no position table (RoPE is inside the
    attention)."""
    blocks = []
    for i in range(num_layers):
        attn = nn.LatentAttention(
            num_heads, kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim,
            v_dim=v_dim, rope_theta=rope_theta, epsilon=epsilon, flash=flash,
            dtype=dtype)
        if i < first_dense:
            ffn = nn.GatedMLP(d_ff, dtype=dtype)
        else:
            ffn = nn.DroplessMoE(
                num_experts, moe_hidden, top_k=top_k,
                experts_held=experts_held, expert_offset=expert_offset,
                shared_hidden_dim=shared_experts * moe_hidden,
                routed_scaling=routed_scaling,
                bias_update_rate=bias_update_rate,
                record_choice=record_choice, dtype=dtype)
        blocks.append((attn, ffn))
    return _rms_norm_lm("deepseek_v3_lm", vocab_size, d_model, blocks,
                        epsilon, dtype)


def _rms_norm_lm(name, vocab_size, d_model, blocks, epsilon, dtype,
                 embedding_std=0.02, tie_embeddings=False):
    """Token-in, logits-out LM of pre-RMSNorm residual blocks: an embedding,
    for each (mixer, ffn) of ``blocks`` two residuals ``RMSNorm -> layer``,
    a final RMSNorm and a bias-free head: untied, a ``Dense`` with its own
    kernel, or with ``tie_embeddings`` the embedding's own table
    (``nn.TiedSequential``: one leaf, read at both ends). The parameter
    paths (which are the device scopes) read ``residual``, ``residual_1``,
    ...: the token mixer (attention or ``nn.ShortConv``) at even indices,
    the MLP or expert layer at odd ones; the head is ``dense`` either way.
    What was assembled is published by kind as the gauges
    ``model.layers_{conv,attention,dense,experts}``."""
    layers = [nn.Embedding(vocab_size, d_model, dtype=dtype,
                           stddev=embedding_std)]
    for mixer, ffn in blocks:
        layers += [
            nn.Residual(nn.Sequential([nn.RMSNorm(epsilon), mixer],
                                      name="main")),
            nn.Residual(nn.Sequential([nn.RMSNorm(epsilon), ffn],
                                      name="main")),
        ]
    convs = sum(isinstance(m, nn.ShortConv) for m, _ in blocks)
    dense = sum(isinstance(f, nn.GatedMLP) for _, f in blocks)
    for kind, n in (("conv", convs), ("attention", len(blocks) - convs),
                    ("dense", dense), ("experts", len(blocks) - dense)):
        default_registry().gauge(f"model.layers_{kind}", n)
    layers.append(nn.RMSNorm(epsilon))
    if tie_embeddings:
        return nn.TiedSequential(
            layers + [nn.TiedHead(vocab_size, dtype=dtype)], name=name)
    return nn.Sequential(
        layers + [nn.Dense(vocab_size, use_bias=False, dtype=dtype)],
        name=name)


def qwen3_moe_lm(
    vocab_size: int,
    *,
    num_layers: int,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    num_experts: int,
    top_k: int,
    moe_hidden: int,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    index_topk: Optional[int] = None,
    index_heads: int = 16,
    index_dim: int = 64,
    record_choice: bool = False,
    rope_theta: float = 10000.0,
    epsilon: float = 1e-6,
    embedding_std: float = 0.02,
    flash="auto",
    dtype=None,
) -> nn.Sequential:
    """The Qwen3-MoE block as a token-in, logits-out LM, with or without a
    learned selection of keys (Keye-VL-2.0-30B-A3B's language model is one
    with: ``sa_config``): pre-RMSNorm residual blocks of
    ``nn.GroupedQueryAttention`` (``num_heads`` query heads over
    ``num_kv_heads`` K/V heads of ``head_dim``, RoPE, per-head RMSNorm on q
    and k) and ``nn.DroplessMoE`` with softmax scoring over ``num_experts``
    experts of ``moe_hidden``, ``top_k`` a token, gates normalised over the
    chosen, no shared expert and no dense layer. ``index_topk`` gives every
    attention layer its indexer (``index_heads`` heads of ``index_dim``, one
    key head), whose loss joins the objective through the layers' state
    (``nn.GroupedQueryAttention``). ``experts_held`` / ``expert_offset`` are
    this chip's share of every expert layer, as in ``deepseek_v3_lm``, with
    which the block assembly is shared; ``record_choice`` keeps each expert
    layer's last choices and each attention layer's last selection in its
    state. A final RMSNorm and an untied, bias-free head; no position
    table. ``embedding_std`` is the embedding's initial scale: every layer
    here adds to the residual stream what attention averaged over thousands
    of keys or what the few experts held computed, so under the library's
    normal(0.02) a freshly initialised stack's tokens are all but one
    vector after the first layer, and every token picks the same experts
    (root PERF.md, PR 32)."""
    blocks = [(
        nn.GroupedQueryAttention(
            num_heads, num_kv_heads, head_dim, rope_theta=rope_theta,
            epsilon=epsilon, index_topk=index_topk, index_heads=index_heads,
            index_dim=index_dim,
            record_selection=record_choice, flash=flash, dtype=dtype),
        nn.DroplessMoE(
            num_experts, moe_hidden, top_k=top_k, experts_held=experts_held,
            expert_offset=expert_offset, scoring="softmax",
            record_choice=record_choice, dtype=dtype),
    ) for _ in range(num_layers)]
    return _rms_norm_lm("qwen3_moe_lm", vocab_size, d_model, blocks, epsilon,
                        dtype, embedding_std)


LAYER_TYPES = ("conv", "full_attention")


def lfm2_moe_lm(
    vocab_size: int,
    *,
    layer_types,
    num_dense_layers: int,
    d_model: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    d_ff: int,
    num_experts: int,
    top_k: int,
    moe_hidden: int,
    conv_kernel: int = 3,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    routed_scaling: float = 1.0,
    bias_update_rate: float = 1e-3,
    record_choice: bool = False,
    rope_theta: float = 1000000.0,
    epsilon: float = 1e-5,
    tie_embeddings: bool = True,
    flash="auto",
    dtype=None,
) -> nn.Sequential:
    """LFM2-MoE's block as a token-in, logits-out LM (LFM2-8B-A1B is one;
    ``model_type: lfm2_moe``): a stack whose layers are not all alike.
    ``layer_types`` names each layer's token mixer, ``"conv"``
    (``nn.ShortConv`` with ``conv_kernel`` taps) or ``"full_attention"``
    (``nn.GroupedQueryAttention``: ``num_heads`` query heads over
    ``num_kv_heads`` K/V heads of ``head_dim``, RoPE, per-head RMSNorm on q
    and k, no indexer), and independently of it the first
    ``num_dense_layers`` layers carry a gated SiLU MLP of ``d_ff`` and the
    rest ``nn.DroplessMoE``: sigmoid scores over ``num_experts`` experts of
    ``moe_hidden``, ``top_k`` a token chosen with the selection bias
    (``use_expert_bias``; moved by ``bias_update_rate`` a step), gates
    normalised over the chosen (their sum + 1e-6, the published constant)
    times ``routed_scaling``, no shared expert.
    ``experts_held`` / ``expert_offset`` are this chip's share of every
    expert layer and ``record_choice`` keeps each expert layer's last
    choices in its state, as in ``deepseek_v3_lm``, with which the block
    assembly is shared. Pre-RMSNorm residuals, a final RMSNorm, and with
    ``tie_embeddings`` (the published model's) the logits are over the
    embedding's own table. Training and full forward passes only."""
    layer_types = tuple(layer_types)
    unknown = sorted(set(layer_types) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(
            f"layer_types may hold {LAYER_TYPES}, got {unknown}")
    blocks = []
    for i, kind in enumerate(layer_types):
        if kind == "conv":
            mixer = nn.ShortConv(conv_kernel, dtype=dtype)
        else:
            mixer = nn.GroupedQueryAttention(
                num_heads, num_kv_heads, head_dim, rope_theta=rope_theta,
                epsilon=epsilon, flash=flash, dtype=dtype)
        if i < num_dense_layers:
            ffn = nn.GatedMLP(d_ff, dtype=dtype)
        else:
            ffn = nn.DroplessMoE(
                num_experts, moe_hidden, top_k=top_k,
                experts_held=experts_held, expert_offset=expert_offset,
                routed_scaling=routed_scaling,
                bias_update_rate=bias_update_rate,
                record_choice=record_choice, dtype=dtype)
        blocks.append((mixer, ffn))
    return _rms_norm_lm("lfm2_moe_lm", vocab_size, d_model, blocks, epsilon,
                        dtype, tie_embeddings=tie_embeddings)


ATTENTION_TYPES = ("full_attention", "sliding_attention")
MLP_TYPES = ("dense", "sparse")


def laguna_lm(
    vocab_size: int,
    *,
    layer_types,
    mlp_layer_types,
    num_heads_per_layer,
    d_model: int,
    num_kv_heads: int,
    head_dim: int,
    d_ff: int,
    num_experts: int,
    top_k: int,
    moe_hidden: int,
    shared_hidden: int,
    sliding_window: int,
    rope_parameters: dict,
    experts_held: Optional[int] = None,
    expert_offset: int = 0,
    routed_scaling: float = 1.0,
    bias_update_rate: float = 0.0,
    record_choice: bool = False,
    gate: bool = True,
    epsilon: float = 1e-6,
    embedding_std: float = 0.02,
    flash="auto",
    dtype=None,
) -> nn.Sequential:
    """Laguna's block as a token-in, logits-out LM (poolside's Laguna-XS.2
    is one; ``model_type: laguna``): a stack whose attention layers are of
    two kinds with unlike head counts. ``layer_types`` names each layer's
    attention, ``"sliding_attention"`` (query t sees the last
    ``sliding_window`` keys) or ``"full_attention"``;
    ``num_heads_per_layer`` gives each layer's query heads over
    ``num_kv_heads`` K/V heads of ``head_dim``; ``rope_parameters`` holds,
    for each of the two kinds, its ``rope_theta``, its
    ``partial_rotary_factor`` (the share of a head's dimensions rotated, the
    first ones) and its ``rope_type`` with, under ``"yarn"``, YaRN's
    parameters (``nn.GroupedQueryAttention``'s ``rope_scaling``). Every
    attention layer norms q and k a head and, with ``gate``, gates each
    head's output from the block's normed input. ``mlp_layer_types`` names
    each layer's MLP independently: ``"dense"``, a gated SiLU MLP of
    ``d_ff``, or ``"sparse"``, ``nn.DroplessMoE``: sigmoid scores over
    ``num_experts`` experts of ``moe_hidden``, ``top_k`` a token, gates
    normalised over the chosen times ``routed_scaling``, plus one shared
    gated MLP of ``shared_hidden`` on every token; ``bias_update_rate`` 0
    leaves the selection bias at zeros (no balancing is published).
    ``experts_held`` / ``expert_offset`` are this chip's share of every
    expert layer and ``record_choice`` keeps each expert layer's last
    choices in its state, as in ``deepseek_v3_lm``, with which the block
    assembly is shared. Pre-RMSNorm residuals, a final RMSNorm and an
    untied, bias-free head; ``embedding_std`` as in ``qwen3_moe_lm``.
    Training and full forward passes only."""
    layer_types, mlp_layer_types = tuple(layer_types), tuple(mlp_layer_types)
    heads = tuple(num_heads_per_layer)
    unknown = sorted(set(layer_types) - set(ATTENTION_TYPES)) + sorted(
        set(mlp_layer_types) - set(MLP_TYPES))
    if unknown:
        raise ValueError(
            f"layer_types may hold {ATTENTION_TYPES} and mlp_layer_types "
            f"{MLP_TYPES}, got {unknown}")
    if not len(layer_types) == len(mlp_layer_types) == len(heads):
        raise ValueError(
            "layer_types, mlp_layer_types and num_heads_per_layer name "
            f"{len(layer_types)}, {len(mlp_layer_types)} and {len(heads)} "
            "layers")
    blocks = []
    for kind, mlp, h in zip(layer_types, mlp_layer_types, heads):
        rope = rope_parameters[kind]
        mixer = nn.GroupedQueryAttention(
            h, num_kv_heads, head_dim,
            rope_theta=float(rope["rope_theta"]), epsilon=epsilon,
            window=sliding_window if kind == "sliding_attention" else None,
            rotary_dim=int(head_dim * rope.get("partial_rotary_factor", 1)),
            rope_scaling=rope, gate=gate, flash=flash, dtype=dtype)
        if mlp == "dense":
            ffn = nn.GatedMLP(d_ff, dtype=dtype)
        else:
            ffn = nn.DroplessMoE(
                num_experts, moe_hidden, top_k=top_k,
                experts_held=experts_held, expert_offset=expert_offset,
                shared_hidden_dim=shared_hidden,
                routed_scaling=routed_scaling,
                bias_update_rate=bias_update_rate,
                record_choice=record_choice, dtype=dtype)
        blocks.append((mixer, ffn))
    default_registry().gauge("model.layers_sliding",
                             layer_types.count("sliding_attention"))
    return _rms_norm_lm("laguna_lm", vocab_size, d_model, blocks, epsilon,
                        dtype, embedding_std)
