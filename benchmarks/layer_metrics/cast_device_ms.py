"""Device time a train step spends casting the masters to the compute dtype: median
over the traced steps of the seconds of the operations under the ``cast`` scope
(``_cast_for_compute`` under a precision policy), the cast's backward (gradients
back to f32) included. 0.0 where no policy casts: explicitly-dtyped layers cast
their own weights under their own scopes (``benchmarks/scopes.py``)."""

from benchmarks import scopes


def read(ctx):
    return scopes.group_ms(ctx, "cast")
