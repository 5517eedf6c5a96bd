"""Metrics registry: counters, gauges, histograms, per-step rings.

One process-global :class:`MetricsRegistry` (``default_registry()``) that
every subsystem publishes into — ``Model.fit``, ``serving.Engine.run``,
``fleet.ServingFleet``, ``rl.PostTrainer``, and the resilience stack —
instead of five incompatible ad-hoc telemetry surfaces. The legacy
``last_fit_telemetry`` / ``last_run_telemetry`` dicts are VIEWS stored
here (:meth:`MetricsRegistry.set_report`), key-for-key identical to what
they always held (pinned by tests/test_obs.py's parity tests).

Always cheap: every mutator is a dict update under one lock (~1 µs), and
``set_enabled(False)`` (or ``DTPU_OBS=0``) turns all of them into no-ops
— the bare loop an instrumented one is compared against
(tests/test_obs.py runs both halves).

Deterministic snapshots: :meth:`snapshot` emits every section with sorted
keys, so the same run produces the same key sequence (and the Prometheus
/ JSONL exporters in ``obs.export`` inherit the stability).

jax-free by design: the registry is importable on jax-free controllers
(the supervisor's rule), and the span tracer keeps its jax dependency
lazy in ``obs.spans``.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, List, Optional

ENABLE_ENV = "DTPU_OBS"

# Seconds-scale latency buckets: wide enough for everything from a CPU-sim
# dispatch (~1 ms) to a gang restore (~10 s). Fixed at registry level so
# cross-rank aggregation compares like with like.
DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0)

DEFAULT_RING_SIZE = 256

# Records a journal keeps (the span timeline and the compile ledger, each).
# A set-up of the largest benchmark cell leaves a few hundred of either.
JOURNAL_CAPACITY = 4096

_enabled = os.environ.get(ENABLE_ENV, "1") != "0"


def enabled() -> bool:
    """Whether the registry (and with it spans and the flight recorder)
    records anything. ``DTPU_OBS=0`` disables at import; ``set_enabled``
    flips it at runtime (a bare loop beside an instrumented one)."""
    return _enabled


def set_enabled(value: bool) -> bool:
    global _enabled
    prev = _enabled
    _enabled = bool(value)
    return prev


class Histogram:
    """Fixed-bucket histogram (cumulative-le semantics on export): counts
    per upper bound plus an overflow bucket, a running sum, and a count."""

    __slots__ = ("buckets", "counts", "overflow", "sum", "count")

    def __init__(self, buckets=DEFAULT_BUCKETS):
        self.buckets = tuple(float(b) for b in buckets)
        self.counts = [0] * len(self.buckets)
        self.overflow = 0
        self.sum = 0.0
        self.count = 0

    def record(self, value: float):
        v = float(value)
        self.sum += v
        self.count += 1
        for i, b in enumerate(self.buckets):
            if v <= b:
                self.counts[i] += 1
                return
        self.overflow += 1

    def snapshot(self) -> dict:
        return {
            "buckets": list(self.buckets),
            "counts": list(self.counts),
            "overflow": self.overflow,
            "sum": round(self.sum, 6),
            "count": self.count,
        }


class MetricsRegistry:
    """Counters, gauges, fixed-bucket histograms, bounded per-step rings,
    and stored structured reports. Thread-safe (fit loops, checkpoint
    writer threads, and fleet step threads all publish concurrently)."""

    def __init__(self, ring_size: int = DEFAULT_RING_SIZE):
        self.ring_size = int(ring_size)
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._rings: Dict[str, collections.deque] = {}
        self._journals: Dict[str, List[dict]] = {}
        self._reports: Dict[str, dict] = {}

    # ------------------------------------------------------------- mutators
    def counter(self, name: str, inc: float = 1.0) -> None:
        """Monotonic accumulator (counts, seconds-of-stall, bytes)."""
        if not _enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + float(inc)

    def gauge(self, name: str, value: float) -> None:
        """Last-value-wins instantaneous reading (queue depth, utilization,
        bytes per device)."""
        if not _enabled:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float, buckets=None) -> None:
        """Record one sample into the named fixed-bucket histogram."""
        if not _enabled:
            return
        with self._lock:
            hist = self._histograms.get(name)
            if hist is None:
                hist = Histogram(buckets or DEFAULT_BUCKETS)
                self._histograms[name] = hist
            hist.record(value)

    def ring_append(self, name: str, record: dict) -> None:
        """Append to the named bounded per-step ring (newest-last; the
        oldest record falls off past ``ring_size``). Records should be
        small flat dicts — they ride in cross-rank snapshot flushes."""
        if not _enabled:
            return
        with self._lock:
            ring = self._rings.get(name)
            if ring is None:
                ring = collections.deque(maxlen=self.ring_size)
                self._rings[name] = ring
            ring.append(dict(record))

    def journal_append(self, name: str, record: dict) -> None:
        """Append to the named journal, which keeps its FIRST
        ``JOURNAL_CAPACITY`` records, in order: what a process does first
        (its set-up) is never pushed out by what it does for hours after.
        A record past the capacity is dropped and counted
        (``journal_dropped/<name>``). The span timeline (``obs.spans``) and
        the compile ledger (``obs.compile_ledger``) live here; neither
        rides in :meth:`snapshot` (``obs.flight.dump_timeline`` writes
        them out)."""
        if not _enabled:
            return
        with self._lock:
            journal = self._journals.setdefault(name, [])
            if len(journal) < JOURNAL_CAPACITY:
                journal.append(record)
                return
            key = f"journal_dropped/{name}"
            self._counters[key] = self._counters.get(key, 0.0) + 1.0

    def set_report(self, name: str, report: dict) -> dict:
        """Store a structured telemetry view (e.g. the dict behind
        ``model.last_fit_telemetry``) and return the STORED object, so the
        legacy attribute and the registry hold the same dict — the
        derived-view contract the parity tests pin. Stored even when
        disabled: reports are the backward-compatible surface, and
        ``set_enabled(False)`` must not silently null legacy telemetry."""
        with self._lock:
            self._reports[name] = report
        return report

    # -------------------------------------------------------------- readers
    def get_report(self, name: str) -> Optional[dict]:
        with self._lock:
            return self._reports.get(name)

    def ring(self, name: str) -> List[dict]:
        with self._lock:
            ring = self._rings.get(name)
            return [dict(r) for r in ring] if ring is not None else []

    def journal(self, name: str) -> List[dict]:
        with self._lock:
            return [dict(r) for r in self._journals.get(name, ())]

    def journal_clear(self, name: str) -> None:
        """Empty the named journal, so that it keeps the next
        ``JOURNAL_CAPACITY`` records (a long-lived process that has
        written its set-up out; a test in a worker that ran a thousand
        before it)."""
        with self._lock:
            self._journals.pop(name, None)

    def counter_value(self, name: str) -> float:
        with self._lock:
            return self._counters.get(name, 0.0)

    def gauge_value(self, name: str) -> Optional[float]:
        with self._lock:
            return self._gauges.get(name)

    def snapshot(self) -> dict:
        """Deterministic full snapshot: every section sorted by name, so
        identical runs produce identical key sequences (pinned by
        tests/test_obs.py) and exporters emit stable output."""
        with self._lock:
            return {
                "ts": time.time(),
                "counters": {
                    k: round(self._counters[k], 6)
                    for k in sorted(self._counters)
                },
                "gauges": {
                    k: round(self._gauges[k], 6) for k in sorted(self._gauges)
                },
                "histograms": {
                    k: self._histograms[k].snapshot()
                    for k in sorted(self._histograms)
                },
                "rings": {
                    k: [dict(r) for r in self._rings[k]]
                    for k in sorted(self._rings)
                },
                "reports": {k: self._reports[k] for k in sorted(self._reports)},
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
            self._rings.clear()
            self._journals.clear()
            self._reports.clear()


_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-global registry every built-in producer publishes to."""
    return _default


__all__ = [
    "DEFAULT_BUCKETS",
    "Histogram",
    "JOURNAL_CAPACITY",
    "MetricsRegistry",
    "default_registry",
    "enabled",
    "set_enabled",
]
