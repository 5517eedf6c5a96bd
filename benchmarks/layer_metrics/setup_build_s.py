"""Seconds inside the program's ``build`` span (``Model.build``: the
module's ``init``, the placement of parameters and state, the optimizer's
state) in the set-up window (``benchmarks/setup_timeline.py``). Its children
and the programs they compiled are named in the timeline's dump, not here."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.span_s(setup, "build")
