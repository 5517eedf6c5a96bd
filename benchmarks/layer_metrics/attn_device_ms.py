"""Device time a train step spends in attention: median over the traced steps of the
seconds of the operations whose scope holds a ``multi_head_attention`` component
(``benchmarks/scopes.py``), forward and backward, kernels and projections."""

from benchmarks import scopes


def read(ctx):
    return scopes.group_ms(ctx, "attention")
