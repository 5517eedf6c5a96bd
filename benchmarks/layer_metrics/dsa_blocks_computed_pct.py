"""Share of the flash kernels' grid blocks at or below the diagonal that
held a selected pair and were walked, over the whole fit and all layers: the
program's counters ``blocks_computed`` over ``blocks_total``. 100 while the
selection is spread over all keys, as a freshly initialised indexer's is;
it falls as the indexer learns to leave whole blocks out."""

from benchmarks import scopes_dsa


def read(ctx):
    totals = scopes_dsa.counter_totals(ctx)
    if totals is None or totals["blocks_total"] <= 0:
        return None
    return 100.0 * totals["blocks_computed"] / totals["blocks_total"]
