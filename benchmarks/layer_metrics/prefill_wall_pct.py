"""Share of the pass's wall inside the engine's ``prefill`` span (stall
report of ``Engine.run``)."""


def read(ctx):
    t = ctx.telemetry
    if "prefill" not in t or not t.get("total_seconds"):
        return None
    return 100.0 * t["prefill"] / t["total_seconds"]
