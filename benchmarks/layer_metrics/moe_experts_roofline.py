"""Roofline share of the grouped-matmul kernels (``dtpu_gmm*``) in the train
step: the least time the chip could take for the traced calls over their
device time. A call's work is one of the nine products of an expert layer
over the rows the layer held (``flops_deepseek_v3.grouped_matmul_cost``); the
rows are the program's own count, its mean over the run's layer-steps
(``moe.held_rows``), so whatever implements the products is held to the
same work."""

from benchmarks import flops, flops_deepseek_v3, scopes_moe, trace as trace_lib


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    totals = scopes_moe.counter_totals(ctx)
    if dev is None or ctx.peaks is None or totals is None:
        return None
    events = trace_lib.matching(dev, "dtpu_gmm")
    measured = sum(e.seconds for e in events)
    if measured <= 0:
        return None
    cfg = ctx.config
    ops, nbytes = flops_deepseek_v3.grouped_matmul_cost(
        totals["held_rows"] / totals["steps"], ctx.telemetry["experts_held"],
        cfg["hidden_size"], cfg["moe_intermediate_size"])
    least = flops.least_seconds(ops / 9.0, nbytes / 9.0, ctx.peaks)[0]
    return 100.0 * len(events) * least / measured
