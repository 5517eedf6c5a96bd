"""Seconds the program spent tracing Python functions to jaxprs in the
set-up window: the union of the compile ledger's ``trace`` intervals, so a
``jit`` traced inside another's trace is counted once. Of the programs that
a span of the program asked for: what the caller compiles between them (the
benchmark's reference) is not in it (``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.stage_s(setup, "trace")
