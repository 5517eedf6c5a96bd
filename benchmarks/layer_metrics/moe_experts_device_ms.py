"""Device time a train step spends in the held experts: median over the
traced steps of the seconds under the ``moe*/experts`` scopes (the grouped
matmul kernels, the activation between them, the weights' casts), forward
and backward (``benchmarks/scopes_moe.py``)."""

from benchmarks import scopes_moe


def read(ctx):
    return scopes_moe.scope_ms(ctx, "experts")
