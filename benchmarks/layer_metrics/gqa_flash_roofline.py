"""Roofline share of the causal flash-attention kernels under grouped-query
attention with no selection (``dtpu_flash_{fwd,dq,dkv}*`` at the query
heads' count and width, from the keys the LFM2-MoE family's configuration
carries: ``flash_roofline.py`` reads GPT-2's): the least time the chip
could take for the traced calls (``flops.flash_cost``: the causal half, 2 +
3 + 4 products) over their device time in the trace."""

from benchmarks import flops, trace as trace_lib

KINDS = {"dtpu_flash_fwd": "fwd", "dtpu_flash_dq": "dq",
         "dtpu_flash_dkv": "dkv"}


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    cfg, t = ctx.config, ctx.telemetry
    if (dev is None or ctx.peaks is None or "layer_types" not in cfg
            or "full_attention" not in cfg["layer_types"]):
        return None
    return trace_lib.roofline_pct(dev, {
        needle: flops.flash_cost(kind, t["rows_per_chip"], t["seq_len"],
                                 cfg["num_attention_heads"],
                                 cfg["assumed"]["head_dim"])
        for needle, kind in KINDS.items()}, ctx.peaks)
