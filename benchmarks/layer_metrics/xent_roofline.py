"""Roofline share of the fused cross-entropy kernels in the train step
(memory-bound: the forward reads the logits once, the backward reads them
and writes their gradient)."""

from benchmarks import flops, trace as trace_lib

KINDS = {"dtpu_xent_fwd": "fwd", "dtpu_xent_bwd": "bwd"}


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    if dev is None or ctx.peaks is None:
        return None
    t = ctx.telemetry
    rows = t["rows_per_chip"] * t["seq_len"]
    return trace_lib.roofline_pct(dev, {
        needle: flops.xent_cost(kind, rows, t["vocab_rows"])
        for needle, kind in KINDS.items()}, ctx.peaks)
