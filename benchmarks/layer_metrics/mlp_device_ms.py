"""Device time a train step spends in the MLPs: median over the traced steps of the
seconds of the operations under a ``dense*`` or ``moe*`` scope inside a
``residual`` block (``benchmarks/scopes.py``), forward and backward."""

from benchmarks import scopes


def read(ctx):
    return scopes.group_ms(ctx, "mlp")
