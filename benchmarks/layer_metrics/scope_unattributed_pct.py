"""Share of a train step's operation seconds that carry no scope of the program
(XLA's own copies, what the partitioner names after nothing): the check on the
five ``*_device_ms`` metrics, which can only account for what is named. Median
over the traced steps (``benchmarks/scopes.py``)."""

from benchmarks import scopes


def read(ctx):
    return scopes.unattributed_pct(ctx)
