"""Operations and bytes of Keye-VL-2.0's language block on one chip's share,
from shapes alone (``flops.py``'s rules: a matrix multiplication of (m, k) by
(k, n) is 2*m*k*n operations, a backward pass is two forward ones; norms,
RoPE, activations, routing, the selection itself, Adam and anything
recomputed are not counted). By the mathematics, whatever implements it:

- the main attention's two products over the *selected* pairs (query t keeps
  ``topk`` of its t + 1 keys once t + 1 > topk), not over the causal half;
- the indexer's score products over the *causal* pairs (every key before a
  query is scored before any is dropped), forward and backward; its
  projections' backward is one product, their input being a constant;
- the main attention's probabilities over the selected pairs once more,
  forward only: the target of the indexer's loss (one q k^T);
- the routed experts at the mean share of a token's pairs that lands on the
  experts held.
"""

from __future__ import annotations

from benchmarks.flops import FLASH_MATMULS


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs of one sequence a causal top-``topk`` selection
    keeps: sum over t of min(t + 1, topk)."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + max(seq_len - topk, 0) * topk


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def indexer_params(cfg: dict) -> int:
    sa, d = cfg["sa_config"], cfg["hidden_size"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return d * (j * di + sa["indexer_num_kv_heads"] * di + j)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int,
                          router_experts: int) -> float:
    """Forward plus backward operations per token of a train step: what
    ``step_mfu_pct`` divides by."""
    sa, layers = cfg["sa_config"], cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    dense = (layers * (attention_params(cfg)
                       + cfg["hidden_size"] * router_experts)
             + vocab_rows * cfg["hidden_size"])
    routed = (layers * expert_params(cfg) * cfg["num_experts_per_tok"]
              * cfg["num_experts"] / router_experts)
    kept = selected_pairs(seq_len, sa["topk"]) / seq_len
    attention = layers * 2 * 2.0 * kept * h * hd       # q k^T and p v
    index = layers * 2.0 * causal_pairs(seq_len) / seq_len * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"])
    target = layers * 2.0 * kept * h * hd              # q k^T once more
    return (3.0 * (2.0 * (dense + routed) + attention + index)
            + 2.0 * 2.0 * layers * indexer_params(cfg) + target)


# --------------------------------------------------------------- kernels --
def dsa_flash_cost(kernel: str, rows: int, seq_len: int, heads: int,
                   kv_heads: int, head_dim: int, topk: int,
                   dtype_bytes: int = 2):
    """(operations, bytes) of one flash-attention kernel call over ``rows``
    sequences whose queries see their selected keys only: the products over
    the selected pairs (a walk that computes whole blocks and masks them
    does more; that is its distance from this). Bytes: q, o, do, dq as wide
    as the query heads, k, v, dk, dv as the K/V heads, each across HBM once
    (K and V once a group); the selection's own bytes are the
    implementation's and not counted."""
    ops = 2.0 * rows * selected_pairs(seq_len, topk) * heads * head_dim * (
        FLASH_MATMULS[kernel])  # fwd S, PV; dq S, dP, dQ; dkv S, dP, dV, dK
    wide, narrow = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4)}[kernel]
    nbytes = rows * seq_len * head_dim * dtype_bytes * (
        wide * heads + narrow * kv_heads)
    return ops, float(nbytes)
