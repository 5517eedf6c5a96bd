"""Seconds the program spent lowering jaxprs to MLIR modules in the set-up
window (Pallas bodies become Mosaic here): the union of the compile ledger's
``lower`` intervals, of the programs that a span of the program asked for
(``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.stage_s(setup, "lower")
