"""Device seconds of a train step inside the selecting attention layers' own
scopes, and their counters.

``nn.GroupedQueryAttention`` with an indexer opens ``indexer`` under its own
scope (``multi_head_attention_gqa``: the indexer's projections, the index
scores, L_I and its gradient) and ``select`` inside that (the row-wise
top-k). ``benchmarks/scopes.py`` puts all of it in its group ``attention``;
this file splits that group by those two scopes, ``select`` taken out of
``indexer``, with the same join of events to ``op_name`` and the same
own-time rule (``scopes.steps``). A program without such scopes gives None.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from benchmarks import harness, scopes, trace as trace_lib

COUNTERS = ("steps", "queries", "causal_pairs", "selected_pairs",
            "blocks_total", "blocks_computed")


def _inner(path: List[str]) -> Optional[str]:
    """``select`` or ``indexer`` where ``path`` enters one under an attention
    layer (the innermost of the two), else None."""
    for i, s in enumerate(path):
        if s.startswith("multi_head_attention"):
            rest = path[i + 1:]
            return ("select" if "select" in rest
                    else "indexer" if "indexer" in rest else None)
    return None


def step_sums(ctx) -> List[Dict[str, float]]:
    """For each traced step, seconds by ``_inner``; [] without a trace or
    without such scopes. Kept on the trace: two readers ask for it."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx.trace, "dsa_scope_sums"):
        path = trace_lib.find_xplane(
            os.path.join(scopes.TRACE_ROOT, ctx.cell["name"]))
        sums = []
        for _, rows in (scopes.steps(ctx.trace, scopes.op_names(path))
                        if path else []):
            table: Dict[str, float] = {}
            for _, _, _, scope_path, seconds in rows:
                inner = _inner(scope_path)
                if inner is not None:
                    table[inner] = table.get(inner, 0.0) + seconds
            sums.append(table)
        ctx.trace.dsa_scope_sums = sums if any(sums) else []
    return ctx.trace.dsa_scope_sums


def scope_ms(ctx, inner: str) -> Optional[float]:
    """Median over the traced steps of the milliseconds under ``inner``."""
    sums = step_sums(ctx)
    if not sums:
        return None
    return 1e3 * harness.median(t.get(inner, 0.0) for t in sums)


def counter_totals(ctx) -> Optional[Dict[str, float]]:
    """The selecting layers' counters summed over the layers, or None where
    the program counted nothing."""
    layers = list((ctx.telemetry.get("select_counters") or {}).values())
    totals = {k: sum(c.get(k, 0.0) for c in layers) for k in COUNTERS}
    return totals if totals["steps"] > 0 and totals["causal_pairs"] > 0 else None
