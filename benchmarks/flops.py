"""Operations and bytes a cell's work requires, from shapes alone.

Counted once here so that no PR that claims a gain can move the yardstick.
A matrix multiplication of (m, k) by (k, n) is 2*m*k*n operations. Causal
attention needs half of the T x T score matrix, so its two (forward) matrix
multiplications count at one half. The backward pass of a matrix
multiplication is two of them. Not counted: Adam's update, layer norms,
GELU, softmax and biases (all O(parameters) or O(tokens * width), under 1%
at these widths), and anything recomputed.
"""

from __future__ import annotations


def param_count(cfg: dict, vocab_rows: int) -> int:
    """Parameters of the GPT-2-shaped LM as the program holds it: the head
    is untied and biased."""
    d, ff, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    block = 4 * d * d + 4 * d + 2 * d * ff + ff + d + 4 * d
    head = vocab_rows * d + vocab_rows
    return (vocab_rows * d + cfg["n_positions"] * d + layers * block + 2 * d
            + head)


def matmul_params(cfg: dict, vocab_rows: int) -> int:
    """Weights that multiply every token: the blocks' and the head's."""
    d, ff, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    return layers * (4 * d * d + 2 * d * ff) + vocab_rows * d


def forward_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int
                            ) -> float:
    """Forward operations per token at sequence length ``seq_len``, causal
    attention at one half."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    dense = 2.0 * matmul_params(cfg, vocab_rows)
    # QK^T and PV: 2 * (2 * T * d) per token over a full square; half of it
    # under a causal mask.
    attention = layers * 0.5 * 4.0 * seq_len * d
    return dense + attention


def train_flops_per_token(cfg: dict, vocab_rows: int, seq_len: int) -> float:
    """Forward plus backward (twice the forward): what model-FLOPs
    utilisation divides by."""
    return 3.0 * forward_flops_per_token(cfg, vocab_rows, seq_len)


# --------------------------------------------------------------- kernels --
# Matrix multiplications of (T, hd) by (hd, T) shape each flash kernel has
# to do given its inputs: fwd S and PV; dq S, dP and dQ; dkv S, dP, dV, dK.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_cost(kernel: str, rows: int, seq_len: int, heads: int,
               head_dim: int, dtype_bytes: int = 2):
    """(operations, bytes) of one causal flash-attention kernel call over
    ``rows`` sequences. Bytes: each of q, k, v, o (and do, dq, dk, dv in
    the backward kernels) crosses HBM once."""
    per_matmul = 2.0 * rows * heads * seq_len * seq_len * head_dim * 0.5
    tensor = rows * seq_len * heads * head_dim * dtype_bytes
    tensors = {"fwd": 4, "dq": 6, "dkv": 7}[kernel]
    return FLASH_MATMULS[kernel] * per_matmul, float(tensors * tensor)


def xent_cost(kernel: str, rows: int, classes: int, dtype_bytes: int = 2):
    """(operations, bytes) of one fused softmax cross-entropy call over
    ``rows`` x ``classes`` logits: the forward reads the logits once; the
    backward reads them and writes their gradient."""
    elements = float(rows) * classes
    if kernel == "fwd":
        return 4.0 * elements, elements * dtype_bytes
    return 4.0 * elements, 2.0 * elements * dtype_bytes


def least_seconds(ops: float, nbytes: float, peaks: dict):
    """(least seconds, which bound) on one chip."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def decode_step_bytes(cfg: dict, vocab_rows: int, live_kv_rows: float,
                      dtype_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every matmul weight once in the
    compute dtype, and the K and V rows of the live contexts in every
    layer."""
    weights = matmul_params(cfg, vocab_rows) * dtype_bytes
    kv = 2.0 * cfg["n_layer"] * cfg["n_embd"] * dtype_bytes * live_kv_rows
    return weights + kv
