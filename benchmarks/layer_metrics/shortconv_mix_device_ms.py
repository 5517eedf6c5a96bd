"""Device time a train step spends in the gated short convolutions' gates
and taps alone: median over the traced steps of the seconds under their
``mix`` scopes, the two products left out (``benchmarks/scopes_conv.py``).
Memory-bound work that XLA's fusions do: no kernel of this repo computes it,
so it has no roofline share."""

from benchmarks import scopes_conv


def read(ctx):
    return scopes_conv.scope_ms(ctx, mix_only=True)
