"""Device seconds of a train step inside the expert layers' own scopes.

``nn.DroplessMoE`` opens three scopes under its own (``moe``, ``moe_1``, ...):
``route`` (router, top-k, the sort, the gather into the experts' buffer and
the weighted sum back), ``experts`` (the grouped matmuls and the activation
between them) and ``shared``. ``benchmarks/scopes.py`` puts all of them in
its group ``mlp``; this file splits that group by the scope that follows the
layer's, with the same join of events to ``op_name`` and the same own-time
rule (``scopes.steps``). A program without such scopes gives None.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional

from benchmarks import harness, scopes, trace as trace_lib

MOE = re.compile(r"^moe(_\d+)?$")


def _inner(path: List[str]) -> Optional[str]:
    """The scope that follows the expert layer's on ``path``, if any."""
    for i, s in enumerate(path[:-1]):
        if MOE.match(s):
            return path[i + 1]
    return None


def step_sums(ctx) -> List[Dict[str, float]]:
    """For each traced step, seconds by the expert layers' inner scope; []
    without a trace or without such scopes. Kept on the trace: two readers
    ask for it."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx.trace, "moe_scope_sums"):
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
        ctx.trace.moe_scope_sums = sums if any(sums) else []
    return ctx.trace.moe_scope_sums


def scope_ms(ctx, inner: str) -> Optional[float]:
    """Median over the traced steps of the milliseconds under ``inner``."""
    sums = step_sums(ctx)
    if not sums:
        return None
    return 1e3 * harness.median(t.get(inner, 0.0) for t in sums)


def counter_totals(ctx) -> Optional[Dict[str, float]]:
    """The expert layers' counters summed over the layers (``steps`` is then
    layer-steps), or None where the program counted nothing."""
    layers = (ctx.telemetry.get("moe_counters") or {}).values()
    totals = {k: sum(c[k] for c in layers)
              for k in ("steps", "pairs", "held_rows", "load_max_sum")}
    return totals if totals["steps"] > 0 and totals["pairs"] > 0 else None
