"""Roofline share of the flash-attention kernels in the train step: the
least time the chip could take for the traced calls (by shape and the peaks
table; each kernel's bound is the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over their device time in the trace."""

from benchmarks import flops, trace as trace_lib

KINDS = {"dtpu_flash_fwd": "fwd", "dtpu_flash_dq": "dq",
         "dtpu_flash_dkv": "dkv"}


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    if dev is None or ctx.peaks is None:
        return None
    cfg, t = ctx.config, ctx.telemetry
    head_dim = cfg["n_embd"] // cfg["n_head"]
    return trace_lib.roofline_pct(dev, {
        needle: flops.flash_cost(kind, t["rows_per_chip"], t["seq_len"],
                                 cfg["n_head"], head_dim)
        for needle, kind in KINDS.items()}, ctx.peaks)
