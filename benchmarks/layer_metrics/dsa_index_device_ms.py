"""Device time a train step spends in the attention layers' indexers: median
over the traced steps of the seconds under the ``indexer`` scopes (the
indexer's projections, the index scores, the main attention's probabilities
recomputed for L_I, L_I and its gradient), the row-wise selection inside
them taken out (``benchmarks/scopes_dsa.py``)."""

from benchmarks import scopes_dsa


def read(ctx):
    return scopes_dsa.scope_ms(ctx, "indexer")
