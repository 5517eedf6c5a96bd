"""Device time a train step spends in the attention layers' head-wise output
gates: median over the traced steps of the seconds under their ``gate``
scopes (the gate's projection, its sigmoid and its product with the heads'
outputs, forward and backward), in the sliding and the full layers alike
(``benchmarks/scopes_swa.py``). Memory-bound work that XLA's fusions do: no
kernel of this repo computes it, so it has no roofline share."""

from benchmarks import scopes_swa


def read(ctx):
    return scopes_swa.scope_ms(ctx, "gate")
