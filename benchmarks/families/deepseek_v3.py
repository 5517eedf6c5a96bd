"""The DeepSeek-V3 family (``model_type: deepseek_v3``; kanana-2-30b-a3b) as
the program runs it: builds the system's model for a configuration file
through the program's public entry point, names the system's own leaves for
the plain reference (no second copy of the weights exists), and gives the
train driver what it needs to know of the family: the reference's static
arguments, the learning-rate schedule, the step's operations and the
first-step tolerances."""

from __future__ import annotations

from benchmarks import flops_deepseek_v3 as flops

# What the first step may show against the reference, and why. The system
# multiplies in bfloat16 from float32 masters and keeps a bfloat16 residual
# stream; the reference computes in float32 at "highest". Routing is
# discrete: on bfloat16 activations a token's sixth and seventh expert swap
# where their scores lie within rounding of each other, and one swapped row
# moves an expert's weight gradient by more than all rounding does. So the
# reference is held to the program's own choices (the expert layers are
# built with ``record_choice``), the swaps are counted and limited on their
# own, and what is left to compare is rounding. Each limit is written beside
# its two readings (my chip runs, PR 28; PERF.md section 6): the largest the
# program read over the builder's seeds, and [in brackets] the control, the
# reference with every weight matmul in int8 (the precision below the
# configuration's bfloat16) handed to this same comparison in the program's
# place (``scripts/moe_wrong_models.py --variants int8``).
LOSS_TOL = 1e-3          # |loss - reference's|: at most 1.9e-4 [2.9e-4]
GRAD_NORM_RTOL = 2e-3    # global gradient norm, relative: 3.8e-4 [1.0e-3]
# |reference's gradient - program's| / |reference's|, the worst leaf of each
# group of ``reference.GROUPS`` (the held experts an expert at a time, so
# that one wrong matrix among 64 x 3 is not averaged away): 0.024-0.037 over
# thirteen seeds, the router's leaves worst (0.034-0.037), then attention's
# (0.031-0.033) [0.094-0.36: layer 0's MLP least, experts 0.34, router 0.36].
# This is the limit that tells precisions apart; the two above only have to
# hold. It also catches gates left unnormalised, pairs dropped over a
# capacity and a missing shared expert (the script's other wrong models).
# Not the routed experts alone in int8: they read below what bfloat16
# everywhere reads, and no comparison with a float32 reference can tell
# that from the configuration's own precision (PERF.md).
GRAD_DIFF_RTOL = 6e-2
# Share of an expert layer's (token, choice) pairs that name an expert the
# reference would not choose for that token: at most 1.0% in the first expert
# layer to 2.1% in the fourth, each layer's input carrying the rounding of
# the layers before it [3.5%, 4.9%, 6.3%, 6.8%: the last three fail it].
FLIPPED_PAIRS_SHARE = 4e-2
# The checks of ``first_step_checks`` that a correct run passes.
FIRST_STEP_CHECKS = ("loss_agrees", "grad_norm_agrees",
                     "grad_differences_agree", "routing_agrees")


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


def vocab_rows(config: dict) -> int:
    return int(config["assumed"]["vocab_rows_held"])


def router_experts(config: dict) -> int:
    return int(config["deployment"]["router_experts"])


def compute_dtype(config: dict):
    import jax.numpy as jnp

    return jnp.dtype(config.get("compute_dtype", "float32"))


def build_module(config: dict):
    """``models.deepseek_v3_lm`` at the configuration's sizes and share."""
    import distributed_tpu as dtpu

    return dtpu.models.deepseek_v3_lm(
        vocab_rows(config),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"],
        d_ff=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        num_experts=router_experts(config),
        experts_held=config["n_routed_experts"],
        expert_offset=config["deployment"]["expert_offset"],
        top_k=config["num_experts_per_tok"],
        moe_hidden=config["moe_intermediate_size"],
        shared_experts=config["n_shared_experts"],
        routed_scaling=config["routed_scaling_factor"],
        bias_update_rate=config["assumed"]["router_bias_update_rate"],
        record_choice=True,
        rope_theta=float(config["rope_theta"]),
        epsilon=config["rms_norm_eps"],
        dtype=compute_dtype(config))


def learning_rate(config: dict, peak: float):
    """The optimizer's learning rate: the family's linear warm-up from 0 to
    ``peak`` (the traffic's) over ``assumed.lr_warmup_steps`` optimizer
    steps, ``peak`` from then on; ``peak`` itself where none is stated."""
    import optax

    steps = int(config["assumed"].get("lr_warmup_steps", 0))
    return optax.linear_schedule(0.0, peak, steps) if steps else peak


def reference_kwargs(config: dict) -> dict:
    """The reference's static arguments (``kw``)."""
    return {
        "n_head": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "kv_rank": config["kv_lora_rank"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "top_k": config["num_experts_per_tok"],
        "scaling": float(config["routed_scaling_factor"]),
        "expert_offset": config["deployment"]["expert_offset"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    return flops.train_flops_per_token(
        config, vocab_rows(config), seq_len, router_experts(config))


def _gated(p: dict) -> dict:
    return {"gate": p["dense"]["kernel"], "up": p["dense_1"]["kernel"],
            "down": p["dense_2"]["kernel"]}


def reference_params(params: dict, state: dict, config: dict) -> dict:
    """The system's leaves under the reference's names.
    ``deepseek_v3_lm`` names its blocks ``residual``, ``residual_1``, ...:
    attention at even indices, the MLP or the expert layer at odd ones; the
    selection bias is the expert layer's buffer in ``state`` (hand over the
    state a step started from: the step updates the bias when it ends)."""
    def name(i):
        return "residual" if i == 0 else f"residual_{i}"

    blocks = []
    for layer in range(config["num_hidden_layers"]):
        attn = params[name(2 * layer)]["main"]
        ffn = params[name(2 * layer + 1)]["main"]
        mla = attn["multi_head_attention_latent"]
        block = {
            "norm1": attn["rms_norm"]["scale"],
            "norm2": ffn["rms_norm"]["scale"],
            "wq": mla["wq"], "wkv_a": mla["wkv_a"],
            "kv_norm": mla["kv_norm"]["scale"], "wkv_b": mla["wkv_b"],
            "wo": mla["wo"],
        }
        if "gated_mlp" in ffn:
            block["mlp"] = _gated(ffn["gated_mlp"])
        else:
            moe = ffn["moe"]
            block.update(
                router=moe["router"],
                router_bias=state[name(2 * layer + 1)]["main"]["moe"][
                    "router_bias"],
                experts={"gate": moe["w_gate"], "up": moe["w_up"],
                         "down": moe["w_down"]})
            if "shared" in moe:
                block["shared"] = _gated(moe["shared"])
        blocks.append(block)
    return {
        "wte": params["embedding"]["table"],
        "blocks": blocks,
        "norm_f": params["rms_norm"]["scale"],
        "head_w": params["dense"]["kernel"],
    }


def choices(state: dict, config: dict) -> list:
    """The experts each expert layer chose for the first sequence of the
    last train step, [(T, top_k) a layer], from the layers' state."""
    first = config["first_k_dense_replace"]
    return [state[f"residual_{2 * layer + 1}"]["main"]["moe"]["choice"]
            for layer in range(first, config["num_hidden_layers"])]


def telemetry(model, config: dict) -> dict:
    """What the family's per-layer readers need of a finished fit: the
    expert layers' counters (``last_fit_telemetry["moe"]``; a program
    without them gives none) and the shapes the kernels' costs are computed
    from."""
    return {
        "moe_counters": (model.last_fit_telemetry or {}).get("moe") or {},
        "experts_held": config["n_routed_experts"],
        "router_experts": router_experts(config),
    }
