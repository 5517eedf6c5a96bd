"""Mean share of the decode slots that hold a running request, over the
decode steps of the pass (``running`` of the ``engine/step_seconds``
ring)."""


def read(ctx):
    ring = ctx.telemetry.get("decode_ring")
    if not ring:
        return None
    mean = sum(r["running"] for r in ring) / len(ring)
    return 100.0 * mean / ctx.telemetry["max_slots"]
