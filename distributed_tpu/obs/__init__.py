"""Unified observability runtime.

One always-cheap telemetry surface for a codebase that had five
(``Model.last_fit_telemetry``, ``Engine.last_run_telemetry``, fleet
request rows, supervisor recovery rows, the resilience event log):

- :mod:`~distributed_tpu.obs.registry` — counters, gauges, fixed-bucket
  histograms, bounded per-step rings; the legacy ``last_*_telemetry``
  dicts are views stored here (``set_report``/``get_report``).
- :mod:`~distributed_tpu.obs.spans` — nested host-side spans
  (``obs.span("prefill")``) that accrue into the registry, forward to
  ``jax.profiler.TraceAnnotation`` (same names on XProf), and carry the
  ``StepTimer`` stall-category attribution through one code path; each
  closed span is also one record (start, end, thread, parent) of the
  registry's ``timeline``, on one clock from the process's start.
- :mod:`~distributed_tpu.obs.compile_ledger` — one record per program and
  stage (trace, lower, backend; cache hit or miss) from JAX's own
  monitoring events, put down to the span that asked for the compile.
- :mod:`~distributed_tpu.obs.flight` — a bounded ring of the last N
  per-step records, dumped (fsync'd JSONL) on preemption, fault-injected
  kills, and unhandled exceptions: the seconds before death.
- :mod:`~distributed_tpu.obs.aggregate` — cross-rank skew + straggler
  attribution over ``metrics_snapshot`` events flushed through the
  ``DTPU_EVENT_LOG`` transport; the supervisor names the slowest rank.
- :mod:`~distributed_tpu.obs.export` — Prometheus text format + JSONL
  snapshot files.
- :mod:`~distributed_tpu.obs.cli` — the ``dtpu-events`` postmortem CLI.

Gate: tests/test_obs.py asserts the telemetry parity contracts
and that an injected slow rank is correctly named on a supervised gang.
See docs/OBSERVABILITY.md.

jax-free at import (controller processes import it next to the
supervisor); spans resolve jax lazily.
"""

from __future__ import annotations

from . import aggregate, compile_ledger, export, flight, registry, spans
from .flight import FlightRecorder, default_recorder, dump as dump_flight
from .registry import (
    MetricsRegistry,
    default_registry,
    enabled,
    set_enabled,
)
from .spans import Span, current_span, span

__all__ = [
    "FlightRecorder",
    "MetricsRegistry",
    "Span",
    "aggregate",
    "compile_ledger",
    "current_span",
    "default_recorder",
    "default_registry",
    "dump_flight",
    "enabled",
    "export",
    "flight",
    "registry",
    "set_enabled",
    "span",
    "spans",
]
