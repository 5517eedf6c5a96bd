"""Median over the requests after the ramp of ``first_token_s -
admitted_s`` (the engine's per-request lifecycle rows): what the engine
adds to time to first token once a slot is granted, the wait behind other
admitted prompts included."""

from benchmarks import harness


def read(ctx):
    samples = ctx.telemetry.get("first_token_ms")
    return harness.median(samples) if samples else None
