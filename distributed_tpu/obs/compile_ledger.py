"""Compile ledger: one record per program and stage, from JAX's own events.

What the span timeline cannot say of a set-up is which programs were
traced, lowered, compiled or read from the persistent cache, for how long,
and on whose behalf. JAX says it itself (0.9: ``jax/_src/dispatch.py``
wraps each stage in ``log_elapsed_time``, which calls
``jax.monitoring.record_event_time_span(event, start, end, fun_name=...)``;
``jax/_src/compiler.py`` records the cache's lookups, hits and retrieval
seconds), so nothing is wrapped and nothing inside a jitted program
changes. :func:`install` registers three listeners, once, and each stage
becomes one record of the registry's ``compile_ledger`` journal:

- ``fun_name`` and ``stage``: ``trace`` (the Python function to a jaxpr),
  ``lower`` (the jaxpr to an MLIR module; Pallas bodies become Mosaic
  here) or ``backend`` (XLA's compile, or the cache's read in its place).
  JAX names the function ``step`` when it traces it and ``jit(step)``
  after: the record holds ``step`` for all three;
- ``start`` and ``end`` in Unix nanoseconds (JAX takes them with
  ``time.time()``; ``obs.spans`` derives its own from the same clock), and
  ``thread``;
- ``span``: what ``obs.current_span()`` names on that thread (``build/init``,
  ``dispatch``, the phase ``fit_setup``, or None for the caller's own
  programs). A stage is one synchronous call, so what is open at its end
  was open at its start;
- on a ``backend`` record, ``cache``: ``hit``, ``miss`` (looked up, not
  found, compiled; a process with no cache directory looks up too) or
  ``uncached`` (no lookup: the cache is off, or does not take the
  backend), and on a hit ``retrieval_s``. The
  cache's events carry no name and fire inside the backend stage, before
  its span is emitted: they are held per thread and attached to the next
  ``backend`` record of that thread.

A ``jit`` traced inside another's trace emits its own ``trace`` record
inside the outer one's interval: sums are taken over the UNION of
intervals (``obs.cli``'s renderer, ``benchmarks/setup_timeline.py``), and
the records keep their nesting, as intervals that contain one another, for
whoever wants the inner programs by name. Every ``jnp`` call, traced or
eager, emits a ``trace`` event, most of them a lookup of under 0.1 ms: a
``trace`` under ``TRACE_MIN_S`` (1 ms) is counted (``compile/short_traces``,
``compile/short_trace_seconds``: the dump's header carries both and
``dtpu-events --timeline`` prints them, so that what the ledger left out is
on the page beside what it kept) and not kept. Inside another trace that
moves no union; the outermost ones it leaves out were 887 records and
0.026 s of 4.9 s traced in ``gpt2-medium``'s set-up on the chip (PR 36).

Bounded like the timeline (``registry.JOURNAL_CAPACITY``, first records
kept). Two more counters, JAX's two cache events as they fire:
``compile/cache_lookups`` and ``compile/cache_hits``
(``chip_smoke.CacheCounter`` reads them). How many programs there were and
how many missed the cache is in the records themselves. ``DTPU_OBS=0``
turns all of it off with the rest.

jax-free at import: :func:`install` imports ``jax.monitoring``, and is
called where the package already has jax (``distributed_tpu/__init__.py``).
"""

from __future__ import annotations

import threading

from . import registry as registry_mod
from . import spans

LEDGER = "compile_ledger"  # the journal's name in the registry

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
TRACE_MIN_S = 0.001
CACHE_LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_installed = False
_install_lock = threading.Lock()
_tls = threading.local()  # .cache: this thread's cache events since its
#                           last backend record


def _pending() -> dict:
    cache = getattr(_tls, "cache", None)
    if cache is None:
        cache = _tls.cache = {}
    return cache


def _on_event(event: str, **_) -> None:
    if not registry_mod.enabled():
        return
    if event == CACHE_LOOKUP:
        _pending()["lookup"] = True
        registry_mod.default_registry().counter("compile/cache_lookups")
    elif event == CACHE_HIT:
        _pending()["hit"] = True
        registry_mod.default_registry().counter("compile/cache_hits")


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event == CACHE_RETRIEVAL and registry_mod.enabled():
        _pending()["retrieval_s"] = float(duration_secs)


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kwargs) -> None:
    stage = STAGES.get(event)
    if stage is None or not registry_mod.enabled():
        return
    reg = registry_mod.default_registry()
    if stage == "trace" and end_time - start_time < TRACE_MIN_S:
        reg.counter("compile/short_traces")
        reg.counter("compile/short_trace_seconds", end_time - start_time)
        return
    name = str(kwargs.get("fun_name"))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    record = {
        "fun_name": name, "stage": stage,
        "start": int(start_time * 1e9), "end": int(end_time * 1e9),
        "thread": threading.get_ident(), "span": spans.current_span(),
    }
    if stage == "backend":
        cache = _pending()
        if cache.get("hit"):
            record["cache"] = "hit"
            record["retrieval_s"] = cache.get("retrieval_s")
        elif cache.get("lookup"):
            record["cache"] = "miss"
        else:
            record["cache"] = "uncached"
        cache.clear()
    reg.journal_append(LEDGER, record)


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once a process
    (JAX has no call that takes a listener back)."""
    global _installed
    with _install_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_time_span_listener(_on_time_span)
        _installed = True


__all__ = ["LEDGER", "STAGES", "install"]
