"""The Keye-VL-2.0 family's language model (``model_type: KeyeVL2``; the
Qwen3-MoE block with a learned top-k selection of keys) as the program runs
it: builds the system's model for a configuration file through the program's
public entry point, names the system's own leaves for the plain reference
(no second copy of the weights exists), and gives the train driver what it
needs to know of the family: the reference's static arguments, the
learning-rate schedule, the step's operations and the first-step limits."""

from __future__ import annotations

from benchmarks import flops_keye_vl2 as flops
from benchmarks.families.deepseek_v3 import (  # noqa: F401  (the driver's)
    compute_dtype, learning_rate, router_experts, vocab_rows)

# What the first step may show against the reference, and why. The system
# multiplies in bfloat16 from float32 masters and keeps a bfloat16 residual
# stream; the reference computes in float32 at "highest". Two choices are
# discrete: a token's eighth and ninth expert, and a query's 2048th and
# 2049th key, swap where their scores lie within rounding of each other, and
# one swapped row moves a gradient by more than all rounding does. So the
# reference is held to the program's own experts and keys (the layers are
# built with ``record_choice``), each kind of swap is counted and limited on
# its own, and what is left to compare is rounding. Each limit is written
# beside its readings (my chip runs, PR 32; PERF.md section 6): the largest
# the program read over thirty seeds, and [in brackets] the controls
# handed to this same comparison in the program's place
# (``scripts/moe_wrong_models.py --cell keye-vl2-30b.train.dsa8k``, seeds
# 2147483821 and, int8 again, 2147483822): the reference with every weight matmul in int8,
# the precision below the configuration's bfloat16; the reference with the
# selection ignored; the reference selecting half the keys.
LOSS_TOL = 1e-3          # |loss - reference's|, L_I included: at most
#                          2.6e-4 [int8 2.2e-4, 9e-5; no selection 0.728]
GRAD_NORM_RTOL = 2e-3    # global gradient norm, relative: at most 5.5e-4
#                          [int8 4.3e-4, 2.1e-4; no selection 0.136]
# |reference's gradient - program's| / |reference's|, the worst leaf of each
# group of ``reference.GROUPS``, the held experts an expert at a time.
# Outside the indexer: attention 0.0113-0.0150, experts 0.0098-0.0112,
# router 0.0101-0.0112, the rest 0.0096-0.0108 [int8 at its two seeds:
# attention 0.0258 and 0.0257, experts 0.0239 and 0.0251, router 0.0233 and
# 0.0252, the rest 0.0185 and 0.0199; no selection 0.153-0.529; the float32
# reference itself 7e-7]. This is the limit that tells the precisions
# apart, and the two lie a factor of 1.7 apart, no more: eight bits of
# mantissa against seven. The limit leaves the program's largest reading
# 1.33 times of room and int8's largest 1.29; int8 passes every other limit.
GRAD_DIFF_RTOL = 2e-2
# The indexer's leaves on their own: L_I's gradient in a score is softmax(I)
# - pbar, the difference of two numbers near 1/2048, so rounding in the
# bfloat16 index scores reads four to seven times larger there than anywhere
# else: 0.0409-0.0779 [int8 0.0764, no wider than bfloat16; no selection
# 0.665; the float32 reference itself 4e-5].
INDEX_GRAD_DIFF_RTOL = 2e-1
# Share of an expert layer's (token, choice) pairs that name an expert the
# reference would not choose for that token: at most 0.37% in the first layer
# to 0.57% in the fourth [int8 0.14-0.65%; no selection 3.2%, 4.5%, 5.2%,
# 5.6%: every layer fails].
FLIPPED_PAIRS_SHARE = 2e-2
# Share of a layer's selected (query, key) pairs whose key the reference's
# own index scores would not select for that query: 0.204-0.208% in the
# first layer to 0.290-0.296% in the fourth, every seed alike [int8
# 0.56-0.60%; no selection 0 in the first layer, whose scores its input
# alone decides, then 2.5%, 3.5%, 4.3%]. And the other way round, the share
# of the reference's own selected pairs that the program left out
# (``missed_keys_share``): the reference is held to the program's keys, so a
# selection that keeps too few (a threshold off by a bit, a block's tail
# dropped) agrees with it in loss and gradients and flips nothing, and shows
# here alone. Both selections keep min(t + 1, topk) keys a query but for
# ties, so a correct program reads the two shares alike (within 2e-7, three
# pairs of 14.68M, in every layer of fifteen seeds) and the limit is one
# [half the keys selected: 0 flipped, gradients within 7e-7, 46.43% missed
# in every layer].
FLIPPED_KEYS_SHARE = 1.2e-2
# The checks of ``first_step_checks`` that a correct run passes.
FIRST_STEP_CHECKS = ("loss_agrees", "grad_norm_agrees",
                     "grad_differences_agree", "routing_agrees",
                     "selection_agrees")


def first_step_checks(loss: float, grad_norm: float, compared: dict,
                      pairs: int) -> dict:
    """A first step's ``loss`` and global gradient norm, and the reference's
    ``compare`` of it (fetched), each reading beside its limit. ``pairs`` is
    an expert layer's (token, choice) pairs. Where the choices could not be
    handed over (several sequences a batch: ``flipped`` is None), swapped
    rows are in the differences, which are then not judged."""
    ref_loss, ref_gnorm = float(compared["loss"]), float(
        compared["grad_norm"])
    diffs = {k: float(v) for k, v in compared["grad_differences"].items()}
    flips = compared["flipped"] or {k: [] for k in (
        "experts", "keys", "selected", "missed_keys", "own_selected")}
    forced = compared["flipped"] is not None
    experts = [int(n) / pairs for n in flips["experts"]]
    keys = [int(n) / max(int(s), 1)
            for n, s in zip(flips["keys"], flips["selected"])]
    missed = [int(n) / max(int(s), 1)
              for n, s in zip(flips["missed_keys"], flips["own_selected"])]
    return {
        "first_loss": loss, "reference_loss": ref_loss,
        "reference_index_loss": float(compared["index_loss"]),
        "grad_norm": grad_norm, "reference_grad_norm": ref_gnorm,
        "loss_tol": LOSS_TOL, "grad_norm_rtol": GRAD_NORM_RTOL,
        "loss_agrees": abs(loss - ref_loss) < LOSS_TOL,
        "grad_norm_agrees": abs(grad_norm - ref_gnorm)
        < GRAD_NORM_RTOL * ref_gnorm,
        "grad_differences": diffs, "grad_diff_rtol": GRAD_DIFF_RTOL,
        "index_grad_diff_rtol": INDEX_GRAD_DIFF_RTOL,
        "grad_differences_agree": not forced or all(
            v < (INDEX_GRAD_DIFF_RTOL if k == "indexer" else GRAD_DIFF_RTOL)
            for k, v in diffs.items()),
        "flipped_pairs_share": experts,
        "flipped_pairs_limit": FLIPPED_PAIRS_SHARE,
        "routing_agrees": all(v < FLIPPED_PAIRS_SHARE for v in experts),
        "flipped_keys_share": keys, "missed_keys_share": missed,
        "flipped_keys_limit": FLIPPED_KEYS_SHARE,
        "selection_agrees": all(
            v < FLIPPED_KEYS_SHARE for v in keys + missed),
    }


def build_module(config: dict):
    """``models.qwen3_moe_lm`` at the configuration's sizes and share."""
    import distributed_tpu as dtpu

    sa = config["sa_config"]
    return dtpu.models.qwen3_moe_lm(
        vocab_rows(config),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        num_experts=router_experts(config),
        experts_held=config["num_experts"],
        expert_offset=config["deployment"]["expert_offset"],
        top_k=config["num_experts_per_tok"],
        moe_hidden=config["moe_intermediate_size"],
        index_topk=sa["topk"],
        index_heads=sa["indexer_num_heads"],
        index_dim=sa["indexer_head_dim"],
        record_choice=True,
        rope_theta=float(config["rope_theta"]),
        epsilon=config["rms_norm_eps"],
        embedding_std=config["assumed"].get("embedding_init_std", 0.02),
        dtype=compute_dtype(config))


def reference_kwargs(config: dict) -> dict:
    """The reference's static arguments (``kw``)."""
    sa = config["sa_config"]
    return {
        "n_head": config["num_attention_heads"],
        "n_kv": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "expert_offset": config["deployment"]["expert_offset"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"],
        "q_block": sa["q_chunk_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return flops.train_flops_per_token(
        config, vocab_rows(config), seq_len, router_experts(config))


def _block_name(i: int) -> str:
    return "residual" if i == 0 else f"residual_{i}"


def reference_params(params: dict, state: dict, config: dict) -> dict:
    """The system's leaves under the reference's names. ``qwen3_moe_lm``
    names its blocks ``residual``, ``residual_1``, ...: attention at even
    indices, the expert layer at odd ones."""
    blocks = []
    for layer in range(config["num_hidden_layers"]):
        attn = params[_block_name(2 * layer)]["main"]
        ffn = params[_block_name(2 * layer + 1)]["main"]
        gqa, moe = attn["multi_head_attention_gqa"], ffn["moe"]
        ix = gqa["indexer"]
        blocks.append({
            "norm1": attn["rms_norm"]["scale"],
            "norm2": ffn["rms_norm"]["scale"],
            "wq": gqa["wq"], "wk": gqa["wk"], "wv": gqa["wv"],
            "wo": gqa["wo"], "q_norm": gqa["q_norm"]["scale"],
            "k_norm": gqa["k_norm"]["scale"],
            "indexer": {"wq": ix["wq"], "wk": ix["wk"], "ww": ix["ww"],
                        "k_norm_scale": ix["k_norm"]["scale"],
                        "k_norm_bias": ix["k_norm"]["bias"]},
            "router": moe["router"],
            "experts": {"gate": moe["w_gate"], "up": moe["w_up"],
                        "down": moe["w_down"]},
        })
    return {
        "wte": params["embedding"]["table"],
        "blocks": blocks,
        "norm_f": params["rms_norm"]["scale"],
        "head_w": params["dense"]["kernel"],
    }


def choices(state: dict, config: dict) -> dict:
    """What each layer chose for the first sequence of the last train step,
    from the layers' state: ``experts`` [(T, top_k) a layer] and ``keys``,
    the selections, [(T, T / 8) uint8 bit-packed a layer]."""
    layers = range(config["num_hidden_layers"])
    return {
        "experts": [state[_block_name(2 * i + 1)]["main"]["moe"]["choice"]
                    for i in layers],
        "keys": [state[_block_name(2 * i)]["main"][
            "multi_head_attention_gqa"]["selection"] for i in layers],
    }


def telemetry(model, config: dict) -> dict:
    """What the family's per-layer readers need of a finished fit: the
    expert layers' and the selecting attention layers' counters
    (``last_fit_telemetry``; a program without them gives none) and the
    shapes the kernels' costs are computed from."""
    fit = model.last_fit_telemetry or {}
    return {
        "moe_counters": fit.get("moe") or {},
        "select_counters": fit.get("select") or {},
        "experts_held": config["num_experts"],
        "router_experts": router_experts(config),
    }
