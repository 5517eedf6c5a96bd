"""Operations of LFM2-MoE's block on one chip's share, from shapes
alone (``flops.py``'s rules: a matrix multiplication of (m, k) by (k, n) is
2*m*k*n operations, causal attention counts one half of the T x T square, a
backward pass is two forward ones; norms, RoPE, activations, routing, Adam
and anything recomputed are not counted). By the mathematics, whatever
implements it:

- a ``conv`` layer's two products, (D, 3D) in and (D, D) out; its taps and
  gates are K + 2 multiply-adds a channel and token, O(tokens x width) as
  the norms are, and not counted;
- a ``full_attention`` layer's four projections (K and V as wide as the K/V
  heads) and its two products over the causal half;
- the leading dense layers' gated MLP; in the others the router and the
  routed experts at the mean share of a token's pairs that lands on the
  experts held;
- the head's product over the rows held, once: the tied table's other use is
  a gather.
"""

from __future__ import annotations


def conv_params(cfg: dict) -> int:
    return 4 * cfg["hidden_size"] ** 2


def attention_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    hd = cfg["assumed"]["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def forward_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                            router_experts: int) -> float:
    d = cfg["hidden_size"]
    kinds = cfg["layer_types"]
    convs = sum(k == "conv" for k in kinds)
    attns = len(kinds) - convs
    dense_layers = cfg["num_dense_layers"]
    moe_layers = len(kinds) - dense_layers
    dense = (convs * conv_params(cfg) + attns * attention_params(cfg)
             + dense_layers * 3 * d * cfg["intermediate_size"]
             + moe_layers * d * router_experts + vocab_rows * d)
    routed = (moe_layers * expert_params(cfg) * cfg["num_experts_per_tok"]
              * cfg["num_experts"] / router_experts)
    attention = attns * 0.5 * 4.0 * seq_len * (
        cfg["num_attention_heads"] * cfg["assumed"]["head_dim"])
    return 2.0 * (dense + routed) + attention


def train_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                          router_experts: int) -> float:
    """Forward plus backward (twice the forward): what ``step_mfu_pct``
    divides by."""
    return 3.0 * forward_flops_per_token(cfg, vocab_rows, seq_len,
                                         router_experts)
