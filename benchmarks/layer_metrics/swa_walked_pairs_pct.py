"""How tight the windowed kernels' walk is: the pairs of the sub-tiles they
computed over the causal pairs, over the whole fit and all sliding layers
(the program's counters ``walked_pairs`` over ``causal_pairs``). The walk
computes whole sub-tiles, so this stands above ``swa_window_pairs_pct`` by
the sub-tiles the diagonal and the window's edge cross (15.1 by shape at T =
8192, window 512 and sub-tiles of 128: five sub-tiles a row of them where
the window holds four); a walk over the whole triangle would read about
100."""

from benchmarks import scopes_swa


def read(ctx):
    totals = scopes_swa.counter_totals(ctx)
    if totals is None or totals["walked_pairs"] <= 0:
        return None
    return 100.0 * totals["walked_pairs"] / totals["causal_pairs"]
