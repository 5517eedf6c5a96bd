"""Roofline share of the flash-attention kernels at latent attention's
widths (queries and keys ``qk_nope_head_dim + qk_rope_head_dim`` wide,
values ``v_head_dim``): the least time the chip could take for the traced
calls (causal half; 2 + 3 + 4 products, the score-side ones as deep as q,
the value-side ones as deep as v) over their device time in the trace."""

from benchmarks import flops_deepseek_v3, trace as trace_lib

KINDS = {"dtpu_flash_fwd": "fwd", "dtpu_flash_dq": "dq",
         "dtpu_flash_dkv": "dkv"}


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    cfg, t = ctx.config, ctx.telemetry
    if dev is None or ctx.peaks is None or "qk_rope_head_dim" not in cfg:
        return None
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return trace_lib.roofline_pct(dev, {
        needle: flops_deepseek_v3.mla_flash_cost(
            kind, t["rows_per_chip"], t["seq_len"],
            cfg["num_attention_heads"], qk, cfg["v_head_dim"])
        for needle, kind in KINDS.items()}, ctx.peaks)
