"""Profiling and tracing hooks.

The reference's observability is log lines and a progress bar
(/root/reference/README.md:395-412); SURVEY.md §5 schedules the TPU-native
upgrade: ``jax.profiler`` trace capture (device timelines, XLA HLO, memory)
plus structured step events. Traces are chief-only so an SPMD gang produces
one trace directory, and are viewable in TensorBoard / XProf.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

from . import logging as dlog


@contextlib.contextmanager
def trace(logdir: str, *, chief_only: bool = True):
    """Capture a profiler trace for the duration of the block.

        with dtpu.utils.profiler.trace("/tmp/trace"):
            model.fit(...)
    """
    active = not (chief_only and jax.process_index() != 0)
    if active:
        jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        if active:
            jax.profiler.stop_trace()


def device_memory_stats(device=None):
    """Allocator stats of one device as ``{bytes_in_use, peak_bytes_in_use,
    bytes_limit}`` — the numbers a ZeRO/FSDP run watches to know how close
    to the HBM ceiling it sits. Reads ``device.memory_stats()`` (default:
    ``jax.local_devices()[0]``); returns None on backends without an
    instrumented allocator (XLA:CPU, including the simulated-device test
    mesh) — use :func:`tree_bytes_per_device` there for the model-state
    share, which is the part sharding controls anyway."""
    d = device if device is not None else jax.local_devices()[0]
    try:
        stats = d.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(
            stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
        ),
    }
    if "bytes_limit" in stats:
        out["bytes_limit"] = int(stats["bytes_limit"])
    return out


def tree_bytes_per_device(*trees) -> dict:
    """Per-device resident bytes of pytrees of arrays, live OR abstract.

    Live ``jax.Array`` leaves are measured from their addressable shard
    buffers (no transfers, no allocator needed — works on every backend,
    including the CPU sim). Abstract ``jax.ShapeDtypeStruct`` leaves are
    *predicted* from their attached sharding: a leaf carrying a
    ``NamedSharding`` contributes ``prod(shard_shape) * itemsize`` to every
    device of its mesh (exactly what materializing it would cost — the
    auto-shard planner's dry-run path, which never builds the 30M-param
    tree it is pricing); an abstract leaf with no sharding counts once into
    a synthetic ``"<abstract>"`` device (the single-device placement).
    Replicated leaves count once PER DEVICE (that is the cost replication
    pays and sharding avoids); host numpy leaves are skipped. Returns
    ``{"max_bytes_per_device", "total_bytes", "devices"}`` where
    ``total_bytes`` sums over all devices. Live and abstract numbers agree
    exactly for the same tree + placement (pinned by
    tests/test_autoshard.py)."""
    import numpy as np
    from jax.sharding import NamedSharding

    per: dict = {}
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array):
                for s in leaf.addressable_shards:
                    key = str(s.device)
                    per[key] = per.get(key, 0) + int(s.data.nbytes)
            elif isinstance(leaf, jax.ShapeDtypeStruct):
                itemsize = jax.numpy.dtype(leaf.dtype).itemsize
                sh = getattr(leaf, "sharding", None)
                if isinstance(sh, NamedSharding):
                    nbytes = int(
                        np.prod(sh.shard_shape(leaf.shape), dtype=np.int64)
                    ) * itemsize
                    for d in sh.mesh.devices.flat:
                        key = str(d)
                        per[key] = per.get(key, 0) + nbytes
                else:
                    nbytes = int(
                        np.prod(leaf.shape, dtype=np.int64)
                    ) * itemsize
                    per["<abstract>"] = per.get("<abstract>", 0) + nbytes
    return {
        "max_bytes_per_device": max(per.values()) if per else 0,
        "total_bytes": sum(per.values()),
        "devices": len(per),
    }


def redundancy_report(state_bytes: int, mirror_host_bytes: int,
                      world: Optional[int] = None) -> dict:
    """Price the buddy-redundancy tier's memory overhead, measured not
    asserted: ``state_bytes`` is this process's resident model state
    (``tree_bytes_per_device(...)["total_bytes"]`` over its addressable
    shards of params+state+opt_state) and ``mirror_host_bytes`` the bytes
    its store segment holds (its own shard's RAM survival copy + the ring
    buddy's mirror). ``overhead_ratio`` is (state + mirror) / state — for
    1/N-sized ZeRO/FSDP shards each mirror is 1/N of the model, the
    (1+1/N)x-flavored pricing the tier's cheapness rests on; replicated
    strategies pay proportionally more, which this report makes visible
    instead of hiding (docs/RESILIENCE.md "Recovery tiers"). Rides in
    ``model.last_fit_telemetry["redundancy"]`` when the tier is armed."""
    state = int(state_bytes)
    mirror = int(mirror_host_bytes)
    return {
        "state_bytes": state,
        "mirror_host_bytes": mirror,
        "overhead_ratio": (
            round((state + mirror) / state, 4) if state > 0 else None
        ),
        "world": int(world) if world is not None else None,
    }


class StepTimer:
    """Steps/sec measurement with warmup exclusion; emits structured events.

    Used standalone around a custom loop, or via `report()` for one-line
    telemetry. Warmup steps (compile) are excluded from the rate.

    Stall accounting: ``attribute(category, seconds)`` accrues wall time
    into named buckets — the train loop uses the convention
    ``{input_wait, dispatch, checkpoint_wait}`` (time blocked waiting for
    the next staged batch / blocked on the device behind a donated
    dispatch / blocked on checkpoint saves-and-flushes), and
    ``stall_report()`` turns the buckets into seconds + fractions of the
    timer's lifetime, including the ``input_stall_fraction`` to
    compare across prefetch depths.
    """

    def __init__(self, warmup: int = 1):
        self.warmup = int(warmup)
        self.steps = 0
        self._t0 = None
        self._measured_from = 0  # step count when the clock started
        self.stalls = {}  # category -> accumulated seconds
        self._wall0 = time.perf_counter()

    def tick(self, steps: int = 1):
        """Count ``steps`` completed optimizer steps. Pass ``steps=K`` when
        one call covers a fused multi-step dispatch
        (``compile(steps_per_execution=K)``) so ``steps_per_sec`` reports
        true per-STEP throughput, not per-dispatch. The warmup window
        closes at the first tick that reaches it; steps beyond the
        boundary inside that same tick are excluded from the rate along
        with the warmup itself (the clock hasn't started yet)."""
        self.steps += int(steps)
        if self._t0 is None and self.steps >= self.warmup:
            self._t0 = time.perf_counter()
            self._measured_from = self.steps

    def attribute(self, category: str, seconds: float):
        """Accrue ``seconds`` of wall time to a stall ``category``. The
        train loop's categories: ``input_wait`` (blocked on the staged
        batch), ``dispatch`` (blocked on the device — donated dispatches
        wait out the previous step), ``checkpoint_wait`` (blocked on
        checkpoint saves/flushes). Free-form categories are allowed for
        custom loops.

        Every attribution is ALSO accumulated into the obs metrics
        registry (``stall_seconds/<category>`` counters) — the one-code-
        path contract: whether the caller is the fit loop's spans, the
        serving engine, or a checkpoint callback, stall accounting lands
        in the same registry the exporters and cross-rank aggregation
        read. Registry-disabled runs skip the forward (the bare loop's
        half)."""
        self.stalls[category] = self.stalls.get(category, 0.0) + float(seconds)
        from ..obs import registry as _obs_registry  # lazy: import order

        if _obs_registry.enabled():
            _obs_registry.default_registry().counter(
                f"stall_seconds/{category}", seconds
            )

    def stall_report(self) -> dict:
        """Attributed seconds per category, the timer's total lifetime
        (``total_seconds``, wall clock since construction), per-category
        fractions of that total (``<category>_fraction`` — overlap and
        obs work reads the dispatch/checkpoint fractions, not just
        input), the ``unattributed`` remainder (total minus the
        categories' sum: callbacks, Python bookkeeping, epoch sync — an
        honest residual instead of a silent one), and the legacy
        ``input_stall_fraction`` (= ``input_wait_fraction``) to
        compare across prefetch depths."""
        elapsed = max(time.perf_counter() - self._wall0, 1e-9)
        out = {}
        for cat in ("input_wait", "dispatch", "checkpoint_wait"):
            out[cat] = round(self.stalls.get(cat, 0.0), 6)
        for cat, secs in self.stalls.items():
            out[cat] = round(secs, 6)
        attributed = sum(out.values())
        out["unattributed"] = round(max(elapsed - attributed, 0.0), 6)
        for cat in list(out):
            out[f"{cat}_fraction"] = round(
                min(out[cat] / elapsed, 1.0), 6
            )
        out["total_seconds"] = round(elapsed, 6)
        out["input_stall_fraction"] = round(out["input_wait"] / elapsed, 6)
        return out

    @property
    def steps_per_sec(self) -> float:
        counted = self.steps - self._measured_from
        if self._t0 is None or counted <= 0:
            return 0.0
        return counted / (time.perf_counter() - self._t0)

    def report(self, **extra):
        rate = self.steps_per_sec
        if jax.process_index() == 0:
            dlog.event("step_rate", steps_per_sec=rate, steps=self.steps, **extra)
            dlog.info(
                f"{rate:.2f} steps/s over "
                f"{self.steps - self._measured_from} steps"
            )
        return rate
