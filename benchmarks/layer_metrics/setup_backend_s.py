"""Seconds inside JAX's backend stage in the set-up window: XLA's compile
on a miss of the persistent cache, the cache's read on a hit. The union of
the compile ledger's ``backend`` intervals, of the programs that a span of
the program asked for (``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.stage_s(setup, "backend")
