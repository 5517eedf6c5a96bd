"""Device seconds of a train step inside the gated short convolutions' own
scopes.

``nn.ShortConv`` runs under its parameter key (``short_conv``: the two
products, the gates and the taps) and opens ``mix`` inside it (the gates and
the taps without the products). ``benchmarks/scopes.py`` files all of it
under ``other``; this file reads the two scopes out, ``mix`` as a part of
``short_conv``, with the same join of events to ``op_name`` and the same
own-time rule (``scopes.steps``), forward and backward together. A program
without such scopes gives None.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional

from benchmarks import harness, scopes, trace as trace_lib

CONV = re.compile(r"^short_conv(_\d+)?$")


def _inner(path: List[str]) -> Optional[str]:
    """``mix`` or ``short_conv`` where ``path`` enters a gated short
    convolution (``mix`` where it goes on into the layer's ``mix``), else
    None."""
    for i, s in enumerate(path):
        if CONV.match(s):
            return "mix" if "mix" in path[i + 1:] else "short_conv"
    return None


def step_sums(ctx) -> List[Dict[str, float]]:
    """For each traced step, seconds by ``_inner``; [] without a trace or
    without such scopes. Kept on the trace: two readers ask for it."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx.trace, "conv_scope_sums"):
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
        ctx.trace.conv_scope_sums = sums if any(sums) else []
    return ctx.trace.conv_scope_sums


def scope_ms(ctx, mix_only: bool = False) -> Optional[float]:
    """Median over the traced steps of the milliseconds under the layers'
    own scopes, ``mix`` included, or under their ``mix`` alone."""
    sums = step_sums(ctx)
    if not sums:
        return None
    return 1e3 * harness.median(
        t.get("mix", 0.0) if mix_only else sum(t.values()) for t in sums)
