"""Device time a train step spends routing in the expert layers: median over
the traced steps of the seconds under the ``moe*/route`` scopes (router,
top-k, the sort by expert, the gather into the experts' buffer and the
weighted sum back), forward and backward (``benchmarks/scopes_moe.py``)."""

from benchmarks import scopes_moe


def read(ctx):
    return scopes_moe.scope_ms(ctx, "route")
