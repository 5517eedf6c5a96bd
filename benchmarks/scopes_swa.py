"""Device seconds of a train step inside the sliding-window attention
layers' own scopes and inside the attention layers' gates, the windowed
layers' counters, and the roofline share of one kind of layer's flash
kernels.

``nn.GroupedQueryAttention`` with a window runs under
``multi_head_attention_swa`` (a full layer under ``multi_head_attention_gqa``;
``benchmarks/scopes.py`` puts both in its group ``attention``), and a gated
layer of either kind opens ``gate`` inside its own scope (the gate's
projection, its sigmoid and its product with the heads' outputs). This file
reads the two out, ``gate`` wherever it lies under an attention layer and
``swa`` as all of a sliding layer, its gate included, with the same join of
events to ``op_name`` and the same own-time rule (``scopes.steps``), forward
and backward together. A program without such scopes gives None.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional

from benchmarks import flops_laguna, harness, scopes, trace as trace_lib

SWA = re.compile(r"^multi_head_attention_swa(_\d+)?$")
COUNTERS = ("steps", "queries", "causal_pairs", "window_pairs",
            "walked_pairs")


def _kinds(path: List[str]) -> List[str]:
    """Which of ``swa`` and ``gate`` an operation at ``path`` counts under."""
    for i, s in enumerate(path):
        if s.startswith("multi_head_attention"):
            return (["swa"] if SWA.match(s) else []) + (
                ["gate"] if "gate" in path[i + 1:] else [])
    return []


def step_sums(ctx) -> List[Dict[str, float]]:
    """For each traced step, seconds by ``_kinds``; [] without a trace or
    without such scopes. Kept on the trace: two readers ask for it."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx.trace, "swa_scope_sums"):
        path = trace_lib.find_xplane(
            os.path.join(scopes.TRACE_ROOT, ctx.cell["name"]))
        sums = []
        for _, rows in (scopes.steps(ctx.trace, scopes.op_names(path))
                        if path else []):
            table: Dict[str, float] = {}
            for _, _, _, scope_path, seconds in rows:
                for kind in _kinds(scope_path):
                    table[kind] = table.get(kind, 0.0) + seconds
            sums.append(table)
        ctx.trace.swa_scope_sums = sums if any(sums) else []
    return ctx.trace.swa_scope_sums


def scope_ms(ctx, kind: str) -> Optional[float]:
    """Median over the traced steps of the milliseconds under ``kind``; None
    where no step has such a scope."""
    sums = step_sums(ctx)
    if not any(kind in t for t in sums):
        return None
    return 1e3 * harness.median(t.get(kind, 0.0) for t in sums)


def counter_totals(ctx) -> Optional[Dict[str, float]]:
    """The windowed layers' counters summed over the layers, or None where
    the program counted nothing."""
    layers = list((ctx.telemetry.get("window_counters") or {}).values())
    totals = {k: sum(c.get(k, 0.0) for c in layers) for k in COUNTERS}
    return totals if totals["steps"] > 0 and totals["causal_pairs"] > 0 else None


def flash_roofline(ctx, kind: str, suffix: str) -> Optional[float]:
    """Roofline share of the flash kernels ``dtpu_flash_{fwd,dq,dkv}<suffix>``
    of the configuration's layers of ``kind``: the least time the chip could
    take for the traced calls, counted on the pairs such a layer's queries
    see (``flops_laguna.gqa_flash_cost``), over their device time. None
    without a trace, for another family's configuration, or where the stack
    has no such layer or the trace no such kernel."""
    dev = trace_lib.device(ctx.trace) if ctx.trace else None
    cfg, t = ctx.config, ctx.telemetry
    if (dev is None or ctx.peaks is None or "sliding_window" not in cfg
            or "num_attention_heads_per_layer" not in cfg):
        return None
    heads = flops_laguna.layers_of(cfg, kind)
    if not heads:
        return None
    pairs = (flops_laguna.window_pairs(t["seq_len"], cfg["sliding_window"])
             if kind == "sliding_attention"
             else flops_laguna.causal_pairs(t["seq_len"]))
    return trace_lib.roofline_pct(dev, {
        f"dtpu_flash_{kernel}{suffix}": flops_laguna.gqa_flash_cost(
            kernel, t["rows_per_chip"], pairs, t["seq_len"], heads[0],
            cfg["num_key_value_heads"], cfg["head_dim"])
        for kernel in ("fwd", "dq", "dkv")}, ctx.peaks)
