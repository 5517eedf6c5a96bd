"""Share of the traced stretch of an engine pass in which no operation ran
on the device (1 - busy union / window)."""

from benchmarks import trace as trace_lib


def read(ctx):
    share = trace_lib.idle_share(ctx.trace) if ctx.trace else None
    return None if share is None else 100.0 * share
