"""How uneven the router is: the busiest expert's pairs in a step over the
mean expert's (pairs over all the experts routed over), averaged over the
fit's steps and the expert layers, from the program's counters. 1 is even."""

from benchmarks import scopes_moe


def read(ctx):
    totals = scopes_moe.counter_totals(ctx)
    if totals is None:
        return None
    mean_load = totals["pairs"] / totals["steps"] / ctx.telemetry[
        "router_experts"]
    return totals["load_max_sum"] / totals["steps"] / mean_load
