"""Roofline share of the decode program: the bytes a decode step has to
read (every matmul weight once in the compute dtype, and the K and V rows
of the live contexts in every layer) over the median device time
of the programs launched inside the engine's ``decode`` span x peak HBM bandwidth. Memory-bound at these batch sizes."""

from benchmarks import harness, trace as trace_lib


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    if dev is None or ctx.peaks is None:
        return None
    runs = trace_lib.module_runs_in(
        dev, ctx.trace.host_spans.get("decode", []))
    if not runs:
        return None
    seconds = harness.median(trace_lib.run_busy_seconds(dev, runs))
    least = ctx.telemetry["decode_step_bytes"] / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds if seconds > 0 else None
