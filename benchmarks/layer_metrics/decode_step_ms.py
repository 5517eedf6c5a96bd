"""Median wall of the engine's ``decode`` span (dispatch and the fetch of
the sampled tokens), from the ``engine/step_seconds`` ring."""

from benchmarks import harness


def read(ctx):
    ring = ctx.telemetry.get("decode_ring")
    if not ring:
        return None
    return 1e3 * harness.median(r["seconds"] for r in ring)
