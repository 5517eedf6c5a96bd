"""Share of the train step in which a collective runs on device 0 and no
other operation does (all-gather, reduce-scatter, all-reduce and their
asynchronous halves, by operation name)."""

from benchmarks import trace as trace_lib


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    if dev is None:
        return None
    runs = trace_lib.module_runs(dev)
    whole = sum(r.seconds for r in runs)
    if whole <= 0:
        return None
    exposed = sum(trace_lib.exposed_collective_seconds(dev, (r.start, r.end))
                  for r in runs)
    return 100.0 * exposed / whole
