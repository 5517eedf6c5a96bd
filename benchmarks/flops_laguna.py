"""Operations and bytes of Laguna's block on one chip's share, from shapes
alone (``flops.py``'s rules: a matrix multiplication of (m, k) by (k, n) is
2*m*k*n operations, a backward pass is two forward ones; norms, RoPE, the
gate's sigmoid and product, activations, routing, Adam and anything
recomputed are not counted). By the mathematics, whatever implements it:

- an attention layer's five projections at its own head count (q and the
  output as wide as its query heads, K and V as the K/V heads, the gate one
  column a head) and its two products over the pairs its queries see: the
  causal half in a ``full_attention`` layer, the pairs inside the window in
  a ``sliding_attention`` one (query t sees min(t + 1, window) keys);
- a ``dense`` layer's gated MLP; in a ``sparse`` one the router, the shared
  expert on every token and the routed experts at the mean share of a
  token's pairs that lands on the experts held;
- the untied head's product over the rows held; the embedding is a gather.
"""

from __future__ import annotations

from benchmarks.flops import FLASH_MATMULS


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def window_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one sequence with t - window < s <= t."""
    w = min(seq_len, window)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_params(cfg: dict, heads: int) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (heads + cfg["num_key_value_heads"]) + d * heads


def expert_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def layers_of(cfg: dict, kind: str):
    """Query heads of each layer of ``kind``."""
    return [h for k, h in zip(cfg["layer_types"],
                              cfg["num_attention_heads_per_layer"])
            if k == kind]


def forward_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                            router_experts: int) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads = cfg["num_attention_heads_per_layer"]
    sparse = cfg["mlp_layer_types"].count("sparse")
    dense = (sum(attention_params(cfg, h) for h in heads)
             + cfg["mlp_layer_types"].count("dense") * expert_params(
                 cfg, cfg["intermediate_size"])
             + sparse * (d * router_experts + expert_params(
                 cfg, cfg["shared_expert_intermediate_size"]))
             + vocab_rows * d)
    routed = (sparse * expert_params(cfg, cfg["moe_intermediate_size"])
              * cfg["num_experts_per_tok"] * cfg["num_experts"]
              / router_experts)
    seen = {"full_attention": causal_pairs(seq_len) / seq_len,
            "sliding_attention": window_pairs(
                seq_len, cfg["sliding_window"]) / seq_len}
    attention = sum(2 * 2.0 * seen[k] * h * hd      # q k^T and p v
                    for k, h in zip(cfg["layer_types"], heads))
    return 2.0 * (dense + routed) + attention


def train_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                          router_experts: int) -> float:
    """Forward plus backward (twice the forward): what ``step_mfu_pct``
    divides by."""
    return 3.0 * forward_flops_per_token(cfg, vocab_rows, seq_len,
                                         router_experts)


# --------------------------------------------------------------- kernels --
def gqa_flash_cost(kernel: str, rows: int, pairs: int, seq_len: int,
                   heads: int, kv_heads: int, head_dim: int,
                   dtype_bytes: int = 2):
    """(operations, bytes) of one flash-attention kernel call over ``rows``
    sequences whose queries see ``pairs`` (query, key) pairs a sequence
    (``causal_pairs`` or ``window_pairs``): the products over those pairs (a
    walk that computes whole sub-tiles and masks them does more; that is its
    distance from this). Bytes: q, o, do, dq as wide as the query heads, k,
    v, dk, dv as the K/V heads, each across HBM once (K and V once a
    group)."""
    ops = 2.0 * rows * pairs * heads * head_dim * FLASH_MATMULS[kernel]
    wide, narrow = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4)}[kernel]
    nbytes = rows * seq_len * head_dim * dtype_bytes * (
        wide * heads + narrow * kv_heads)
    return ops, float(nbytes)
