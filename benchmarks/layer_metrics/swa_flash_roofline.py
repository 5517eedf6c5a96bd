"""Roofline share of the windowed flash-attention kernels
(``dtpu_flash_{fwd,dq,dkv}_swa``, the sliding layers'): the least time the
chip could take for the traced calls counted on the pairs *inside the
window* (2 + 3 + 4 products 128 deep, K and V read once a group:
``flops_laguna.gqa_flash_cost`` on ``window_pairs``) over their device time
in the trace. A walk that computed the whole causal triangle would read at
most window over causal pairs of what the plain kernels read (12.1% at T =
8192, window 512); what is left below the plain kernels' share is the
sub-tiles the band's edges cross, computed whole and masked."""

from benchmarks import scopes_swa


def read(ctx):
    return scopes_swa.flash_roofline(ctx, "sliding_attention", "_swa")
