"""Device time a train step spends in the sliding-window attention layers:
median over the traced steps of the seconds under the
``multi_head_attention_swa`` scopes, forward and backward, kernels,
projections, norms, rotation and gate (``benchmarks/scopes_swa.py``).
``attn_device_ms`` holds these and the full layers' together."""

from benchmarks import scopes_swa


def read(ctx):
    return scopes_swa.scope_ms(ctx, "swa")
