"""Device time a train step spends in the gated short convolutions: median
over the traced steps of the seconds under the ``short_conv*`` scopes (the
in- and out-projections, the two gates and the taps), forward and backward
(``benchmarks/scopes_conv.py``)."""

from benchmarks import scopes_conv


def read(ctx):
    return scopes_conv.scope_ms(ctx)
