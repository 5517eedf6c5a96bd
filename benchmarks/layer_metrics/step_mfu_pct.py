"""Model-FLOPs utilisation of the train step: operations forward and
backward require per step (``benchmarks/flops.py``: causal attention at one
half, Adam and recomputation not counted) over device time x chips x peak."""


def read(ctx):
    step_ms = ctx.values.get("step_device_ms")
    if not step_ms or ctx.peaks is None:
        return None
    t = ctx.telemetry
    ops = t["train_flops_per_token"] * t["tokens_per_step"]
    return 100.0 * ops / (
        step_ms * 1e-3 * t["chips"] * ctx.peaks["bf16_flops_per_s"])
