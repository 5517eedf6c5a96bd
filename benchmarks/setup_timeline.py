"""A run's set-up, read from the program's own span timeline and compile
ledger.

The program (``distributed_tpu.obs``) keeps one record per closed span
(``path``, ``start``, ``end``, ``thread``, ``parent``: the registry's
``timeline`` journal) and one per program and compile stage (``fun_name``,
``stage`` of ``trace`` / ``lower`` / ``backend``, ``start``, ``end``,
``thread``, ``span``, and on a ``backend`` record ``cache``: its
``compile_ledger`` journal), every time in Unix nanoseconds on one clock,
and says when the OS started the process (``obs.spans.process_start_ns``).
This file holds the join once, for the seven ``setup_*`` readers, and reads
the process's registry directly, as ``drivers/serve.py`` does.

The set-up WINDOW of a run is the process's start to the end of the last
``fit_setup`` span of the main thread, which is where the timed ``fit``'s
step loop begins: everything in ``setup_s`` but the warm-up steps, which the
step metrics measure. Inside it the main thread's time is either inside one
of the program's top-level spans (``parent`` None: ``import``, ``compile``,
``build``, ``fit_setup``, the first fit's ``input_wait`` / ``dispatch``
spans, ``fit_teardown``) or between them, which is the caller's (the
benchmark's batches, its reference, its ``global_norm``) and no metric of
the program's; nor are the programs compiled there (the ledger's records
whose ``span`` is None: :func:`own_compiles`).

A program without a timeline (the parent of the PR that added it), a run
that closed no ``fit_setup`` span, or ``DTPU_OBS=0`` gives None everywhere.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from benchmarks import trace as trace_lib


@dataclasses.dataclass
class Setup:
    zero: int             # the process's start, Unix nanoseconds
    end: int              # the window's end
    main: int             # the main thread's ident
    spans: List[dict]     # the timeline's records that end inside the window
    compiles: List[dict]  # the ledger's records that end inside the window


def _covered_s(intervals, lo: int, hi: int) -> float:
    """Seconds of ``[lo, hi]`` that the union of ``intervals`` covers."""
    cut = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return trace_lib.total(trace_lib.union(cut)) / 1e9


def setup_of(timeline: List[dict], ledger: List[dict], zero: int,
             main: int) -> Optional[Setup]:
    ends = [r["end"] for r in timeline if r["path"] == "fit_setup"
            and r["thread"] == main and r["parent"] is None]
    if not ends:
        return None
    end = max(ends)
    return Setup(zero=zero, end=end, main=main,
                 spans=[r for r in timeline if r["end"] <= end],
                 compiles=[r for r in ledger if r["end"] <= end])


def read_setup(ctx) -> Optional[Setup]:
    """The set-up of this process, from the program's default registry; kept
    in the context's telemetry so that seven readers join once."""
    if "_setup" in ctx.telemetry:
        return ctx.telemetry["_setup"]
    setup = None
    try:
        import threading

        from distributed_tpu.obs import default_registry, spans

        reg = default_registry()
        if hasattr(reg, "journal") and hasattr(spans, "process_start_ns"):
            setup = setup_of(
                reg.journal("timeline"), reg.journal("compile_ledger"),
                spans.process_start_ns(), threading.main_thread().ident)
    except ImportError:  # a program without the package's obs: nothing
        pass
    ctx.telemetry["_setup"] = setup
    return setup


def top_level(setup: Setup) -> List[dict]:
    return sorted((r for r in setup.spans if r["thread"] == setup.main
                   and r["parent"] is None), key=lambda r: r["start"])


def span_end_s(setup: Setup, path: str) -> Optional[float]:
    """Seconds from the process's start to the end of the first top-level
    span called ``path``."""
    for r in top_level(setup):
        if r["path"] == path:
            return (r["end"] - setup.zero) / 1e9
    return None


def span_s(setup: Setup, path: str) -> Optional[float]:
    """Seconds inside the spans whose path is ``path``, wherever they were
    opened from (``build`` is one path under a fit that builds)."""
    found = [(r["start"], r["end"]) for r in setup.spans
             if r["path"] == path and r["thread"] == setup.main]
    if not found:
        return None
    return _covered_s(found, setup.zero, setup.end)


def own_compiles(setup: Setup) -> List[dict]:
    """The ledger's records that a span of the program asked for. One with
    no span is a program the caller compiled between the program's spans
    (the benchmark's reference, its ``global_norm``): ``setup_s`` takes the
    reference's seconds out, and no metric of the program's counts it."""
    return [r for r in setup.compiles if r.get("span") is not None]


def stage_s(setup: Setup, stage: str) -> float:
    """Seconds the union of the program's ``stage`` intervals covers in the
    window: a ``jit`` traced inside another's trace is counted once."""
    return _covered_s(((r["start"], r["end"]) for r in own_compiles(setup)
                       if r["stage"] == stage), setup.zero, setup.end)


def cache_misses(setup: Setup) -> int:
    return sum(r["stage"] == "backend" and r.get("cache") == "miss"
               for r in own_compiles(setup))


def unseen_s(setup: Setup) -> float:
    """Seconds inside the program's own top-level spans that no child span
    and no record of the ledger covers. The gaps between top-level spans
    are the caller's and are not in it."""
    total = 0.0
    for top in top_level(setup):
        lo, hi = top["start"], top["end"]
        inside = lambda r: (r["thread"] == setup.main and r["start"] >= lo
                            and r["end"] <= hi)
        covered = [(r["start"], r["end"]) for r in setup.spans
                   if r is not top and inside(r)
                   and r["parent"] is not None]
        covered += [(r["start"], r["end"]) for r in setup.compiles
                    if inside(r)]
        total += (hi - lo) / 1e9 - _covered_s(covered, lo, hi)
    return total
