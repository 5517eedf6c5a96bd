"""The Laguna family (``model_type: laguna``; Laguna-XS.2) as the program
runs it: builds the system's model for a configuration file through the
program's public entry point, names the system's own leaves for the plain
reference (no second copy of the weights exists), and gives the train driver
what it needs to know of the family: the reference's static arguments, the
learning-rate schedule, the step's operations and the first-step limits."""

from __future__ import annotations

from benchmarks import flops_laguna as flops, harness
from benchmarks.families.deepseek_v3 import (  # noqa: F401  (the driver's)
    _gated, compute_dtype, learning_rate, router_experts, vocab_rows)
from benchmarks.families.lfm2_moe import _block_name

# What the first step may show against the reference, and why. The system
# multiplies in bfloat16 from float32 masters and keeps a bfloat16 residual
# stream; the reference computes in float32 at "highest". Routing is
# discrete, so the reference is held to the program's own choices of experts
# (the expert layers are built with ``record_choice``), the swaps are counted
# and limited on their own, and what is left to compare is rounding. Each
# limit is written beside its readings (my chip runs, PR 38; PERF.md section
# 6): the largest the program read over the builder's seeds, and [in
# brackets] the controls handed to this same comparison in the program's
# place (``scripts/moe_wrong_models.py --cell laguna-xs2.train.swa8k``): the
# reference with every weight matmul in int8 (the precision below the
# configuration's bfloat16), with full causal attention in the sliding
# layers, with no gate, and with all 128 dimensions rotated unscaled in the
# full layers.
# Seeds: the program's, 2147484401-07 and, from the committed files alone,
# 2147484450-56 (fourteen); int8 at 2147484401-02, the three others at
# 2147484401.
# |loss - reference's|: at most 3.1e-4 [int8 2.6e-4 and 7.5e-4; full causal
# 3.2e-3; no gate 3.1e-3; plain rope 3.9e-3]. The precision hardly moves the
# loss: int8 reads inside the limit, as in kanana's and LFM2's cells. This
# one guards the loss against a wrong model (the three others fail it), not
# against a lower precision.
LOSS_TOL = 1e-3
# Global gradient norm, relative: 1.7e-5 to 4.2e-4 [int8 1.30e-3 and 1.55e-3;
# full causal 0.038; no gate 0.33; plain rope 0.22]: 1.9 times of room over
# the program's largest reading, 1.6 under int8's smallest.
GRAD_NORM_RTOL = 8e-4
# |reference's gradient - program's| / |reference's|, the worst leaf of each
# group of ``reference.GROUPS``, the held experts an expert at a time:
# ``full_attention`` 0.0630-0.0816 (one seed of the fourteen over 0.073) and
# ``sliding_attention`` 0.0563-0.0665 worst, then ``router`` 0.0537-0.0598,
# ``other`` 0.0402-0.0439, ``shared`` 0.0409-0.0422, ``dense_mlp``
# 0.0394-0.0406, ``experts`` 0.0390-0.0407 [int8
# at its two seeds: dense_mlp 0.1357 and 0.1343, experts 0.1389 and 0.1351,
# other 0.1447 and 0.1432, shared 0.1459 and 0.1432, router 0.1887 and
# 0.1960, sliding_attention 0.2218 and 0.2053, full_attention 0.2257 and
# 0.2692: every group over the limit at both seeds; full causal 0.71-1.02 in
# every group; no gate 1.01-2.96; plain rope 1.01-1.77]. The limit leaves the
# program's largest reading 1.29 times of room and int8's smallest 1.28. The
# program reads 1.6 times what LFM2's and kanana's cells read under their
# 0.06 (0.024-0.037), in every group, the ones without a new mechanism too
# (layer 0's MLP 0.040 where LFM2's reads 0.024), and int8 reads 1.4 times
# theirs: five attention layers of 48 and 64 heads round more on the way
# back than one, or than latent attention's 32. By leaf (seed 2147484401, in
# a program of its own, a scratch script of PR 38's chip runs): the
# q and k norms' scales of 128 numbers worst (0.047-0.061), then wq and wk of
# the first sliding layer (0.048), the routers (0.044-0.050), the gates' Wg
# 0.041-0.042, every other leaf 0.039-0.041.
GRAD_DIFF_RTOL = 1.05e-1
# Share of an expert layer's (token, choice) pairs that name an expert the
# reference would not choose for that token: 1.51-1.67% in the first expert
# layer, 2.08-2.23%, 2.50-2.73% and 2.93-3.12% in the fourth, each layer's
# input carrying the rounding of the layers before it; eight of 256 experts
# a token lie closer together than LFM2's four of 32 (0.8-1.6%) [int8
# 4.93-5.15%, 6.76-6.98%, 8.30-8.39%, 9.34-9.48%: all four fail it at both
# seeds; full causal 21-43%; no gate 62-87%; plain rope 87-93%].
FLIPPED_PAIRS_SHARE = 4e-2
# The checks of ``first_step_checks`` that a correct run passes.
FIRST_STEP_CHECKS = ("loss_agrees", "grad_norm_agrees",
                     "grad_differences_agree", "routing_agrees")
KINDS = {"full_attention": "multi_head_attention_gqa",
         "sliding_attention": "multi_head_attention_swa"}


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
    forced = compared["flipped"] is not None
    flipped = [int(n) / pairs for n in compared["flipped"] or []]
    return {
        "first_loss": loss, "reference_loss": ref_loss,
        "grad_norm": grad_norm, "reference_grad_norm": ref_gnorm,
        "loss_tol": LOSS_TOL, "grad_norm_rtol": GRAD_NORM_RTOL,
        "loss_agrees": abs(loss - ref_loss) < LOSS_TOL,
        "grad_norm_agrees": abs(grad_norm - ref_gnorm)
        < GRAD_NORM_RTOL * ref_gnorm,
        "grad_differences": diffs, "grad_diff_rtol": GRAD_DIFF_RTOL,
        "grad_differences_agree": not forced or all(
            v < GRAD_DIFF_RTOL for v in diffs.values()),
        "flipped_pairs_share": flipped,
        "flipped_pairs_limit": FLIPPED_PAIRS_SHARE,
        "routing_agrees": all(v < FLIPPED_PAIRS_SHARE for v in flipped),
    }


def build_module(config: dict):
    """``models.laguna_lm`` at the configuration's sizes and share. A program
    without that builder cannot run the family: it fails here, at once."""
    import distributed_tpu as dtpu

    if not hasattr(dtpu.models, "laguna_lm"):
        raise harness.BenchmarkError(
            "this program has no models.laguna_lm: it cannot run the "
            f"configuration {config['name']!r}")
    assumed = config["assumed"]
    return dtpu.models.laguna_lm(
        vocab_rows(config),
        layer_types=config["layer_types"],
        mlp_layer_types=config["mlp_layer_types"],
        num_heads_per_layer=config["num_attention_heads_per_layer"],
        d_model=config["hidden_size"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        num_experts=router_experts(config),
        experts_held=config["num_experts"],
        expert_offset=config["deployment"]["expert_offset"],
        top_k=config["num_experts_per_tok"],
        moe_hidden=config["moe_intermediate_size"],
        shared_hidden=config["shared_expert_intermediate_size"],
        sliding_window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        routed_scaling=float(config["moe_routed_scaling_factor"]),
        bias_update_rate=assumed["router_bias_update_rate"],
        record_choice=True,
        gate=bool(config["gating"]),
        epsilon=config["rms_norm_eps"],
        embedding_std=assumed["embedding_std"],
        dtype=compute_dtype(config))


def reference_kwargs(config: dict) -> dict:
    """The reference's static arguments (``kw``)."""
    hd = config["head_dim"]
    full = config["rope_parameters"]["full_attention"]
    sliding = config["rope_parameters"]["sliding_attention"]
    return {
        "n_kv": config["num_key_value_heads"], "head_dim": hd,
        "eps": float(config["rms_norm_eps"]),
        "window": config["sliding_window"],
        "theta_sliding": float(sliding["rope_theta"]),
        "rotary_sliding": int(hd * sliding["partial_rotary_factor"]),
        "theta_full": float(full["rope_theta"]),
        "rotary_full": int(hd * full["partial_rotary_factor"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original": int(full["original_max_position_embeddings"]),
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config["moe_routed_scaling_factor"]),
        "expert_offset": config["deployment"]["expert_offset"],
        "q_block": config["assumed"]["reference_q_block"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return flops.train_flops_per_token(
        config, vocab_rows(config), seq_len, router_experts(config))


def reference_params(params: dict, state: dict, config: dict) -> dict:
    """The system's leaves under the reference's names. ``laguna_lm`` names
    its blocks ``residual``, ``residual_1``, ...: attention at even indices
    (``multi_head_attention_swa`` in a sliding layer,
    ``multi_head_attention_gqa`` in a full one: the reference takes the
    first under ``swa`` and the second under ``attn``), the MLP or the
    expert layer at odd ones; the selection bias is the expert layer's
    buffer in ``state``."""
    blocks = []
    for layer, kind in enumerate(config["layer_types"]):
        mixer = params[_block_name(2 * layer)]["main"]
        ffn = params[_block_name(2 * layer + 1)]["main"]
        gqa = mixer[KINDS[kind]]
        block = {
            "norm1": mixer["rms_norm"]["scale"],
            "norm2": ffn["rms_norm"]["scale"],
            "swa" if kind == "sliding_attention" else "attn": {
                "wq": gqa["wq"], "wk": gqa["wk"], "wv": gqa["wv"],
                "wo": gqa["wo"], "wg": gqa["wg"],
                "q_norm": gqa["q_norm"]["scale"],
                "k_norm": gqa["k_norm"]["scale"]},
        }
        if "gated_mlp" in ffn:
            block["mlp"] = _gated(ffn["gated_mlp"])
        else:
            moe = ffn["moe"]
            block.update(
                router=moe["router"],
                router_bias=state[_block_name(2 * layer + 1)]["main"]["moe"][
                    "router_bias"],
                experts={"gate": moe["w_gate"], "up": moe["w_up"],
                         "down": moe["w_down"]},
                shared=_gated(moe["shared"]))
        blocks.append(block)
    return {"wte": params["embedding"]["table"], "blocks": blocks,
            "norm_f": params["rms_norm"]["scale"],
            "head_w": params["dense"]["kernel"]}


def choices(state: dict, config: dict) -> list:
    """The experts each expert layer chose for the first sequence of the
    last train step, [(T, top_k) a layer], from the layers' state."""
    return [state[_block_name(2 * layer + 1)]["main"]["moe"]["choice"]
            for layer, kind in enumerate(config["mlp_layer_types"])
            if kind == "sparse"]


def telemetry(model, config: dict) -> dict:
    """What the family's per-layer readers need of a finished fit: the
    expert layers' and the windowed attention layers' counters
    (``last_fit_telemetry``; a program without them gives none) and the
    shapes the kernels' costs are computed from."""
    fit = model.last_fit_telemetry or {}
    return {
        "moe_counters": fit.get("moe") or {},
        "window_counters": fit.get("window") or {},
        "experts_held": config["num_experts"],
        "router_experts": router_experts(config),
    }
