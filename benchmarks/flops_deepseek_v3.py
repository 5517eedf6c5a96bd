"""Operations and bytes of the DeepSeek-V3 block's work on one chip's share,
from shapes and counted rows alone (``flops.py``'s rules: a matrix
multiplication of (m, k) by (k, n) is 2*m*k*n operations, causal attention
counts one half of the T x T square, a backward pass is two forward ones;
norms, RoPE, activations, routing, Adam and anything recomputed are not
counted). Whatever implements the work, these count the same.
"""

from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """Weights of one latent-attention layer (no query compression)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (d * h * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                            router_experts: int) -> float:
    """Forward operations per token of the work done here: the dense and
    shared products on every token; the routed experts' on the mean share of
    a token's pairs that lands on the experts held (``num_experts_per_tok *
    held / routed over``); causal attention at one half, its score products
    ``qk`` deep and its value products ``v_head_dim`` deep."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense_layers = cfg["first_k_dense_replace"]
    moe_layers = layers - dense_layers
    held = cfg["n_routed_experts"]
    dense = (layers * attention_params(cfg)
             + dense_layers * 3 * d * cfg["intermediate_size"]
             + moe_layers * (cfg["n_shared_experts"] * expert_params(cfg)
                             + d * router_experts)
             + vocab_rows * d)
    routed = (moe_layers * expert_params(cfg) * cfg["num_experts_per_tok"]
              * held / router_experts)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attention = layers * 0.5 * 2.0 * seq_len * cfg["num_attention_heads"] * (
        qk + cfg["v_head_dim"])
    return 2.0 * (dense + routed) + attention


def train_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                          router_experts: int) -> float:
    """Forward plus backward (twice the forward): what ``step_mfu_pct``
    divides by."""
    return 3.0 * forward_flops_per_token(cfg, vocab_rows, seq_len,
                                         router_experts)


# --------------------------------------------------------------- kernels --
# Products of each flash kernel at latent attention's widths: (deep qk,
# deep v). fwd S | PV; dq S, dQ | dP; dkv S, dK | dP, dV.
MLA_FLASH_MATMULS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}


def mla_flash_cost(kernel: str, rows: int, seq_len: int, heads: int,
                   qk_dim: int, v_dim: int, dtype_bytes: int = 2):
    """(operations, bytes) of one causal flash-attention kernel call over
    ``rows`` sequences with ``qk_dim``-wide queries and keys and
    ``v_dim``-wide values. Bytes: q, k (and dq, dk) at ``qk_dim``; v, o (and
    do, dv) at ``v_dim``; each crosses HBM once."""
    square = 2.0 * rows * heads * seq_len * seq_len * 0.5
    n_qk, n_v = MLA_FLASH_MATMULS[kernel]
    ops = square * (n_qk * qk_dim + n_v * v_dim)
    wide, narrow = {"fwd": (2, 2), "dq": (3, 2), "dkv": (4, 3)}[kernel]
    nbytes = rows * seq_len * heads * dtype_bytes * (
        wide * qk_dim + narrow * v_dim)
    return ops, float(nbytes)


def grouped_matmul_cost(rows_held: float, experts_held: int, d_model: int,
                        hidden: int, dtype_bytes: int = 2):
    """(operations, bytes) of the nine grouped products of one expert layer
    over ``rows_held`` rows (three forward: gate, up, down; six backward: d
    lhs and d rhs of each): 2 x rows x d_model x hidden each. Bytes: a
    product reads or writes the held experts' matrix once and its two row
    operands once."""
    ops = 9 * 2.0 * rows_held * d_model * hidden
    weights = experts_held * d_model * hidden * dtype_bytes
    row_bytes = rows_held * (d_model + hidden) * dtype_bytes
    return ops, 9.0 * (weights + row_bytes)
