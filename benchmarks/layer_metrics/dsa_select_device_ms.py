"""Device time a train step spends selecting keys: median over the traced
steps of the seconds under the ``select`` scopes (each query's ``topk``
largest index scores, as a mask: ``ops/topk_select.py``), all layers
(``benchmarks/scopes_dsa.py``)."""

from benchmarks import scopes_dsa


def read(ctx):
    return scopes_dsa.scope_ms(ctx, "select")
