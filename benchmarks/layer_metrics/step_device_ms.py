"""Device time of one train step: for each execution of the train program on
device 0 in the trace, the union of the operations inside it; the median."""

from benchmarks import harness, trace as trace_lib


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    if dev is None:
        return None
    runs = trace_lib.module_runs(dev)
    if not runs:
        return None
    return 1e3 * harness.median(trace_lib.run_busy_seconds(dev, runs))
