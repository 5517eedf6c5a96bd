"""Share of the causal (query, key) pairs the attention layers kept, over the
whole fit and all layers: the program's counters ``selected_pairs`` over
``causal_pairs`` (``nn.GroupedQueryAttention``'s state, read after the fit).
By shape 43.8 at T = 8192 and topk 2048; more only where scores tie."""

from benchmarks import scopes_dsa


def read(ctx):
    totals = scopes_dsa.counter_totals(ctx)
    if totals is None:
        return None
    return 100.0 * totals["selected_pairs"] / totals["causal_pairs"]
