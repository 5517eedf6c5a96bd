"""Share of the causal (query, key) pairs that lie inside the sliding
layers' window, over the whole fit and all sliding layers: the program's
counters ``window_pairs`` over ``causal_pairs``
(``nn.GroupedQueryAttention``'s state, read after the fit). By shape 12.1 at
T = 8192 and a window of 512."""

from benchmarks import scopes_swa


def read(ctx):
    totals = scopes_swa.counter_totals(ctx)
    if totals is None:
        return None
    return 100.0 * totals["window_pairs"] / totals["causal_pairs"]
