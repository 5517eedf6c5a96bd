"""Seconds inside the program's own top-level spans, in the set-up window,
that no child span and no record of the compile ledger covers: what the
program's instrumentation still cannot name. The gaps between top-level
spans are the caller's and are not counted
(``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.unseen_s(setup)
