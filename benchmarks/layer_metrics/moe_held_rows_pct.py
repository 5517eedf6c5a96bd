"""Share of a step's (token, choice) pairs whose expert this chip holds, over
the whole fit and all expert layers: the program's counters ``moe.held_rows``
over ``moe.pairs`` (``nn.DroplessMoE``'s state, read after the fit). An even
router gives experts held over experts routed over (12.5 for 16 of 128)."""

from benchmarks import scopes_moe


def read(ctx):
    totals = scopes_moe.counter_totals(ctx)
    if totals is None:
        return None
    return 100.0 * totals["held_rows"] / totals["pairs"]
