"""Host-side span tracer: nested named regions, one code path for all
attribution.

``with obs.span("prefill"):`` times a block and

- accrues the elapsed seconds into the metrics registry as a
  ``span_seconds/<path>`` histogram (``<path>`` is the slash-joined
  nesting, e.g. ``decode/sample``) plus a ``span_calls/<path>`` counter,
- appends one record to the registry's ``timeline`` journal: ``path``,
  ``start`` and ``end`` (Unix nanoseconds, see "One clock"), ``thread``
  and ``parent``, the path of whatever was open on the thread when the
  span began (the span that caused it), so that spans can be ordered,
  laid beside one another and beside what else keeps time,
- forwards the block to ``jax.profiler.TraceAnnotation`` so the SAME
  name shows up on XProf/TensorBoard device timelines, and
- optionally attributes into a live ``StepTimer`` (``span(name,
  timer=t)`` calls ``t.attribute(name, seconds)``), which is how the
  train/serve/checkpoint stall categories flow through one code path
  instead of hand-rolled ``perf_counter`` pairs.

One clock. Durations are ``time.perf_counter()`` differences. A record's
``start`` and ``end`` are Unix nanoseconds derived from ONE
(``time.time_ns()``, ``time.perf_counter()``) pair taken when this module
is imported (:func:`unix_ns`), so the timeline, JAX's compile events
(``time.time()``: ``obs.compile_ledger``) and a profiler trace share an
axis, and no span moves against another when the wall clock is stepped.
The timeline's zero is :func:`process_start_ns`, the process's start as
the OS has it: the interpreter's start and whatever is imported before
this package are one interval, from there to the ``import`` span.

Bounded. The journal keeps its first ``registry.JOURNAL_CAPACITY`` (4,096)
records and counts what it drops. A loop that closes a span every step
passes ``timeline=`` from :func:`loop_gate`: its first
``LOOP_SPANS_KEPT`` (8) spans of each name enter the timeline, the rest
only accrue into their histograms (``Model.fit``'s ``input_wait`` and
``dispatch``, the engine's ``prefill``, ``decode`` and ``draft``). Every
other span always enters.

Where a region cannot be a ``with`` block (a module's imports; the part of
``Model.fit`` before its step loop, which ends inside the first epoch)
:func:`begin` opens a PHASE and ``.end()`` closes it. A phase is a span in
the registry and the timeline, but it is not on the thread's stack: spans
inside it keep their own paths (``span_seconds/build`` is one key whether
or not ``fit`` did the building) and name the phase as their ``parent``,
and a phase that an exception leaves open corrupts no later path (the
thread holds it weakly and forgets it with its owner). A phase writes no
``TraceAnnotation``.

The jax import is lazy (and optional): a jax-free controller process can
use spans — they just skip the trace annotation. When the registry is
disabled (``obs.set_enabled(False)`` / ``DTPU_OBS=0``) a span degrades to
a plain timed block: the timer attribution still happens (legacy
telemetry must not change when observability is off), the registry,
timeline and annotation work is skipped.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
import weakref
from typing import Callable, Optional

from . import registry as registry_mod

TIMELINE = "timeline"  # the journal's name in the registry
LOOP_SPANS_KEPT = 8

_ANCHOR_UNIX_NS, _ANCHOR_PERF = time.time_ns(), time.perf_counter()

_tls = threading.local()

_trace_annotation = None  # resolved lazily: jax.profiler.TraceAnnotation


def _annotation(name: str):
    global _trace_annotation
    if _trace_annotation is None:
        try:
            import jax

            _trace_annotation = jax.profiler.TraceAnnotation
        except Exception:  # jax-free controller: spans still time/attribute
            _trace_annotation = contextlib.nullcontext
    try:
        return _trace_annotation(name)
    except TypeError:  # nullcontext() takes no useful arg on some versions
        return contextlib.nullcontext()


def unix_ns(perf_counter: float) -> int:
    """Unix nanoseconds of a ``time.perf_counter()`` reading."""
    return _ANCHOR_UNIX_NS + int((perf_counter - _ANCHOR_PERF) * 1e9)


def process_start_ns() -> int:
    """When the OS started this process, in Unix nanoseconds: the start
    time of ``/proc/self/stat`` (clock ticks since boot) against
    ``CLOCK_BOOTTIME`` now, good to a clock tick (10 ms). Where the OS
    does not say, the moment this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            # Field 22, counted after the parenthesised command name.
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return _ANCHOR_UNIX_NS
    return unix_ns(time.perf_counter() - age)


def loop_gate() -> Callable[[str], bool]:
    """``keep(name)`` for one run of a loop: true for the first
    ``LOOP_SPANS_KEPT`` calls with each name, so that a loop of a million
    steps leaves its first steps in the timeline and not a million
    records."""
    seen: collections.Counter = collections.Counter()

    def keep(name: str) -> bool:
        seen[name] += 1
        return seen[name] <= LOOP_SPANS_KEPT

    return keep


def span_stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _open_phase() -> Optional["Phase"]:
    ref = getattr(_tls, "phase", None)
    phase = ref() if ref is not None else None
    return phase if phase is not None and phase.seconds is None else None


def current_span() -> Optional[str]:
    """Slash-joined path of the innermost open span on this thread; with
    none open, the open phase (:func:`begin`), if any."""
    stack = span_stack()
    if stack:
        return "/".join(stack)
    phase = _open_phase()
    return phase.name if phase is not None else None


def _close(reg, path: str, parent: Optional[str], t0: float, t1: float,
           timeline: bool) -> None:
    reg = reg or registry_mod.default_registry()
    reg.observe(f"span_seconds/{path}", t1 - t0)
    reg.counter(f"span_calls/{path}")
    if timeline:
        reg.journal_append(TIMELINE, {
            "path": path, "start": unix_ns(t0), "end": unix_ns(t1),
            "thread": threading.get_ident(), "parent": parent})


class Span:
    """Yielded handle: ``seconds`` is filled when the block exits, so the
    caller can reuse the measured wall time (the fit loop's flight-record
    rows) without timing the block twice."""

    __slots__ = ("name", "path", "seconds")

    def __init__(self, name: str, path: str):
        self.name = name
        self.path = path
        self.seconds = 0.0


@contextlib.contextmanager
def span(name: str, *, timer=None, registry=None, timeline: bool = True):
    """Time a named, nestable region. See module docstring.

    ``timer``: a ``utils.profiler.StepTimer`` to attribute the elapsed
    seconds to (category = ``name``, NOT the nested path — stall buckets
    stay flat, matching the pre-span contract). ``registry``: override
    the target registry (default: the process-global one). ``timeline``:
    false from a loop past its first spans (:func:`loop_gate`).
    """
    stack = span_stack()
    parent = current_span()
    stack.append(name)
    path = "/".join(stack)
    handle = Span(name, path)
    on = registry_mod.enabled()
    ctx = _annotation(name) if on else contextlib.nullcontext()
    t0 = time.perf_counter()
    try:
        with ctx:
            yield handle
    finally:
        t1 = time.perf_counter()
        handle.seconds = t1 - t0
        stack.pop()
        if timer is not None:
            timer.attribute(name, t1 - t0)
        if on:
            _close(registry, path, parent, t0, t1, timeline)


class Phase:
    """An open phase (:func:`begin`); ``end()`` closes it, once. The thread
    holds it weakly: a phase whose owner raised and is gone reads as
    closed, and is never recorded."""

    __slots__ = ("name", "parent", "start", "seconds", "_outer",
                 "__weakref__")

    def __init__(self, name: str, start: Optional[float]):
        self.name = name
        self.parent = current_span()
        self.start = time.perf_counter() if start is None else start
        self.seconds = None  # filled by end()
        self._outer = getattr(_tls, "phase", None)
        _tls.phase = weakref.ref(self)

    def end(self) -> None:
        if self.seconds is not None:
            return
        t1 = time.perf_counter()
        self.seconds = t1 - self.start
        ref = getattr(_tls, "phase", None)
        if ref is not None and ref() is self:
            _tls.phase = self._outer
        if registry_mod.enabled():
            _close(None, self.name, self.parent, self.start, t1, True)


def begin(name: str, *, start: Optional[float] = None) -> Phase:
    """Open the phase ``name`` on this thread, from now or from ``start``
    (a ``time.perf_counter()`` reading taken earlier). See "Where a region
    cannot be a ``with`` block" in the module docstring."""
    return Phase(name, start)


__all__ = ["LOOP_SPANS_KEPT", "Phase", "Span", "TIMELINE", "begin",
           "current_span", "loop_gate", "process_start_ns", "span",
           "span_stack", "unix_ns"]
