"""Device time a train step spends in the optimizer: median over the traced steps of
the seconds of the operations under the ``optimizer`` scope (``tx.update``,
``apply_updates`` and the strategy's output constraints; ``benchmarks/scopes.py``)."""

from benchmarks import scopes


def read(ctx):
    return scopes.group_ms(ctx, "optimizer")
