"""Roofline share of the plain causal flash-attention kernels under grouped
queries at 128-wide heads, read in place with no selection
(``dtpu_flash_{fwd,dq,dkv}_packed``: Laguna's full layers, 48 query heads
over 8 K/V heads): the least time the chip could take for the traced calls
(the causal half, 2 + 3 + 4 products, K and V read once a group:
``flops_laguna.gqa_flash_cost`` on ``causal_pairs``) over their device time
in the trace. ``gqa_flash_roofline.py`` reads LFM2's keys (64-wide heads, K
and V repeated)."""

from benchmarks import scopes_swa


def read(ctx):
    return scopes_swa.flash_roofline(ctx, "full_attention", "_packed")
