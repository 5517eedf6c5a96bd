"""Roofline share of the selection-taking flash-attention kernels
(``dtpu_flash_{fwd,dq,dkv}_sel``): the least time the chip could take for the
traced calls counted on the *selected* pairs (2 + 3 + 4 products 128 deep,
K and V read once a group: ``flops_keye_vl2.dsa_flash_cost``) over their
device time in the trace. The kernels walk every block at or below the
diagonal that holds a selected pair and mask inside it, so a selection
spread evenly over the keys leaves this share at most selected over causal
pairs (43.8% at T = 8192, topk 2048) of what the plain causal walk reads:
the head-room of a walk that gathers the selected keys."""

from benchmarks import flops_keye_vl2, trace as trace_lib

KINDS = {"dtpu_flash_fwd_sel": "fwd", "dtpu_flash_dq_sel": "dq",
         "dtpu_flash_dkv_sel": "dkv"}


def read(ctx):
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    cfg, t = ctx.config, ctx.telemetry
    if dev is None or ctx.peaks is None or "sa_config" not in cfg:
        return None
    return trace_lib.roofline_pct(dev, {
        needle: flops_keye_vl2.dsa_flash_cost(
            kind, t["rows_per_chip"], t["seq_len"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["sa_config"]["topk"])
        for needle, kind in KINDS.items()}, ctx.peaks)
