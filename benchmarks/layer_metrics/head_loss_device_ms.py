"""Device time a train step spends in the vocabulary head and the loss: median over
the traced steps of the seconds of the operations under the top-level ``dense``
scope or the ``loss`` scope (``benchmarks/scopes.py``), forward and backward; the
xent kernels fall here."""

from benchmarks import scopes


def read(ctx):
    return scopes.group_ms(ctx, "head_loss")
