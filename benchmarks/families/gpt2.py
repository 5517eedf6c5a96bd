"""The GPT-2 family as the program runs it: builds the system's model for a
configuration file through the program's public entry points, and names the
system's own parameter leaves for the plain reference (no second copy of the
weights exists)."""

from __future__ import annotations


def vocab_rows(config: dict) -> int:
    return int(config.get("assumed", {}).get("vocab_rows_held",
                                             config["vocab_size"]))


def layer_norm_epsilon(config: dict) -> float:
    """The epsilon the program runs with (departure (c) of the config)."""
    return float(config.get("assumed", {}).get(
        "layer_norm_epsilon_run", config["layer_norm_epsilon"]))


def compute_dtype(config: dict):
    import jax.numpy as jnp

    return jnp.dtype(config.get("compute_dtype", "float32"))


def build_module(config: dict):
    """``models.transformer_lm`` at the configuration's sizes."""
    import distributed_tpu as dtpu

    return dtpu.models.transformer_lm(
        vocab_rows(config), num_layers=config["n_layer"],
        d_model=config["n_embd"], num_heads=config["n_head"],
        d_ff=config["n_inner"], max_len=config["n_positions"],
        dtype=compute_dtype(config))


def reference_params(params: dict, config: dict) -> dict:
    """The system's leaves under the reference's names. ``transformer_lm``
    names its blocks ``residual``, ``residual_1``, ...: attention at even
    indices, the MLP at odd ones."""
    def res(i):
        return params["residual" if i == 0 else f"residual_{i}"]["main"]

    blocks = []
    for layer in range(config["n_layer"]):
        attn, mlp = res(2 * layer), res(2 * layer + 1)
        mha = attn["multi_head_attention"]
        blocks.append({
            "ln1": attn["layer_norm"], "ln2": mlp["layer_norm"],
            "wq": mha["wq"], "wk": mha["wk"], "wv": mha["wv"],
            "wo": mha["wo"], "bq": mha["bq"], "bk": mha["bk"],
            "bv": mha["bv"], "bo": mha["bo"],
            "w1": mlp["dense"]["kernel"], "b1": mlp["dense"]["bias"],
            "w2": mlp["dense_1"]["kernel"], "b2": mlp["dense_1"]["bias"],
        })
    return {
        "wte": params["embedding"]["table"],
        "wpe": params["positional_embedding"]["table"],
        "blocks": blocks,
        "lnf": params["layer_norm"],
        "head_w": params["dense"]["kernel"],
        "head_b": params["dense"]["bias"],
    }
