"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What a v5e
trace looks like (looked at by hand in PR 22, see PERF.md section 3):

- one plane per chip, ``/device:TPU:<n>`` (beside ``#Chip0 ...`` and
  ``/host:metadata`` planes that hold nothing used here); its line ``XLA
  Modules`` holds one event per executed program (``jit_step(<hash>)``; the
  engine's dispatches are ``functools.partial`` objects and all read
  ``jit__unknown(<hash>)``), its line ``XLA Ops`` one event per HLO
  operation the TensorCore ran, in order; ``Async XLA Ops`` holds the
  ``-start``..``-done`` spans of asynchronous copies and collectives, which
  overlap the operations and are not counted as busy time;
- an operation's event name is its whole HLO line, ``%fusion.12 = bf16[...]
  fusion(...)``: the operation's own name is what stands between ``%`` and
  `` = `` (operands named after a collective must not make a fusion one);
- a Mosaic kernel is a ``custom-call`` that the compiler names after the
  scope it was called in: ``%jvp_dtpu_flash_fwd_packed_.24 = (...)
  custom-call(...)``, ``%transpose_jvp_dtpu_flash_dq_packed__.3`` (the step
  compiled for a described v5e holds 74 such calls at 24 layers, and the
  traced run of PR 22 listed them under these names, trailing underscores
  and all). The XLA operations around a kernel carry its name only in
  their ``tf_op`` statistic (``jit(step)/jvp(dtpu_flash_fwd_packed)/...``)
  and are not calls of it;
- collectives are operations whose own name starts with ``all-gather``,
  ``all-reduce``, ``reduce-scatter``, ``all-to-all`` or
  ``collective-permute`` (``-start`` / ``-done`` included);
- host threads are lines of the plane ``/host:CPU``; the program's spans
  (``obs.span`` -> ``jax.profiler.TraceAnnotation``) are events of the
  ``main`` thread under their own names, on the same clock as the device
  planes (a dispatch's span opens 0.3-0.5 ms before its program starts).

All times are seconds; intervals are ``(start, end)`` on the profiler's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
KERNEL = re.compile(r"dtpu_[a-z0-9_]+")
OP_NAME = re.compile(r"^%?([^\s=]+)")
# Host spans the program writes (obs.span names) and the benchmark's own.
HOST_SPANS = ("input_wait", "dispatch", "checkpoint_wait", "prefill", "decode",
              "queue_wait")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    text: str = ""  # name plus every string statistic, for kernel search

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """The operation's own name: ``fusion.12`` of ``%fusion.12 = ...``."""
        return OP_NAME.match(self.name).group(1)


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: Dict[str, List[Interval]]  # span name -> intervals
    window: Interval                       # first to last device op

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


# ------------------------------------------------------------ intervals --
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``union(a)`` that ``union(b)`` does not cover."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    return subtract([window], busy)


# --------------------------------------------------------------- reading --
def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        start = ev.start_ns * 1e-9
        text = [ev.name]
        for key, value in ev.stats:
            if isinstance(value, str):
                text.append(value)
        out.append(Event(ev.name, start, start + ev.duration_ns * 1e-9,
                         " ".join(text)))
    return out


def parse(profile) -> Trace:
    """``Trace`` of a ``jax.profiler.ProfileData``."""
    devices, spans = [], {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            if ops:
                devices.append(DeviceTrace(int(m.group(2)), ops, modules))
        elif HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        start = ev.start_ns * 1e-9
                        spans.setdefault(ev.name, []).append(
                            (start, start + ev.duration_ns * 1e-9))
    devices.sort(key=lambda d: d.ordinal)
    if devices:
        lo = min(d.ops[0].start for d in devices)
        hi = max(max(e.end for e in d.ops) for d in devices)
    else:
        lo = hi = 0.0
    return Trace(devices, spans, (lo, hi))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return parse(ProfileData.from_file(path))


# ------------------------------------------------------------ reductions --
def busy(dev: DeviceTrace, window: Optional[Interval] = None
         ) -> List[Interval]:
    """Union of the intervals in which an operation ran on ``dev``."""
    ivs = [(e.start, e.end) for e in dev.ops]
    return union(clip(ivs, window) if window else ivs)


def busy_seconds(trace: Trace) -> float:
    """Busy seconds inside the traced window, averaged over the devices."""
    if not trace.devices:
        return 0.0
    return sum(total(busy(d, trace.window)) for d in trace.devices) / len(
        trace.devices)


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.devices or trace.window_s <= 0:
        return None
    return 1.0 - busy_seconds(trace) / trace.window_s


def is_collective(ev: Event) -> bool:
    return bool(COLLECTIVE.match(ev.op))


def kernel_name(ev: Event) -> Optional[str]:
    """``dtpu_<kernel>`` of a Mosaic kernel's event, else None: the call is
    the operation named after the kernel. The XLA operations around it,
    which carry its name in their ``tf_op`` scope only, do not count."""
    m = KERNEL.search(ev.op)
    return m.group(0).rstrip("_") if m else None


def kernel_names(trace: Trace) -> List[str]:
    """Names of the Mosaic kernels that ran on the first device."""
    dev = device(trace)
    return sorted({kernel_name(e) for e in dev.ops} - {None}) if dev else []


def matching(dev: DeviceTrace, needle: str) -> List[Event]:
    """Kernel calls on ``dev`` whose kernel name starts with ``needle``
    (``dtpu_flash_fwd``)."""
    return [e for e in dev.ops if (kernel_name(e) or "").startswith(needle)]


def device(trace: Trace, ordinal: int = 0) -> Optional[DeviceTrace]:
    for d in trace.devices:
        if d.ordinal == ordinal:
            return d
    return trace.devices[0] if trace.devices else None


def exposed_collective_seconds(dev: DeviceTrace,
                               window: Optional[Interval] = None) -> float:
    """Seconds in which a collective ran on ``dev`` and no other operation
    did."""
    coll = [(e.start, e.end) for e in dev.ops if is_collective(e)]
    comp = [(e.start, e.end) for e in dev.ops if not is_collective(e)]
    if window:
        coll, comp = clip(coll, window), clip(comp, window)
    return total(subtract(coll, comp))


def module_runs(dev: DeviceTrace) -> List[Event]:
    """Executions on ``dev`` of the program that took most time (the train
    step of a fit), whatever hash its name carries."""
    base = lambda m: re.sub(r"\(\d+\)$", "", m.name)
    by_name: Dict[str, float] = {}
    for m in dev.modules:
        by_name[base(m)] = by_name.get(base(m), 0.0) + m.seconds
    if not by_name:
        return []
    top = max(by_name, key=by_name.get)
    return [m for m in dev.modules if base(m) == top]


def module_runs_in(dev: DeviceTrace, spans: Sequence[Interval]
                   ) -> List[Event]:
    """Executions of whatever program started while one of the host
    ``spans`` was open: the engine's dispatches carry no name of their own,
    but each is launched inside its span (``prefill``, ``decode``)."""
    spans = sorted(spans)
    out = []
    for m in dev.modules:
        if any(s <= m.start < e for s, e in spans):
            out.append(m)
    return out


def run_busy_seconds(dev: DeviceTrace, runs: Sequence[Event]) -> List[float]:
    """For each program execution, the busy union of the operations inside
    its interval."""
    return [total(busy(dev, (r.start, r.end))) for r in runs]


def roofline_pct(dev: DeviceTrace, costs: Dict[str, Tuple[float, float]],
                 peaks: dict) -> Optional[float]:
    """Roofline share of a family of kernels: for each ``needle`` of
    ``costs`` (its value the operations and bytes one call needs), the least
    time the chip could take for the traced calls over their traced time."""
    from benchmarks import flops

    least = measured = 0.0
    for needle, (ops, nbytes) in costs.items():
        events = matching(dev, needle)
        least += len(events) * flops.least_seconds(ops, nbytes, peaks)[0]
        measured += sum(e.seconds for e in events)
    return 100.0 * least / measured if measured > 0 else None


def top_ops(trace: Trace, n: int = 10, ordinal: int = 0
            ) -> List[Tuple[str, float]]:
    """The ``n`` device operations with most total time, by event name with
    a trailing instance number (``fusion.123``) stripped; a Mosaic kernel is
    listed under its own ``dtpu_*`` name."""
    dev = device(trace, ordinal)
    if dev is None:
        return []
    sums: Dict[str, float] = {}
    for e in dev.ops:
        key = kernel_name(e) or re.sub(r"(\.remat\d*|\.clone|[.\-_]?\d+)+$",
                                          "", e.op)
        sums[key] = sums.get(key, 0.0) + e.seconds
    return sorted(sums.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, n: int = 10, ordinal: int = 0
              ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of a device, each named by the host span
    that covers most of it (``none`` where no span was open)."""
    dev = device(trace, ordinal)
    if dev is None:
        return []
    out = []
    longest = sorted(gaps(busy(dev, trace.window), trace.window),
                     key=lambda g: g[0] - g[1])[:n]
    for gap in longest:
        best, best_s = "none", 0.0
        for name, ivs in trace.host_spans.items():
            covered = total(clip(ivs, gap))
            if covered > best_s:
                best, best_s = name, covered
        out.append((best, gap[1] - gap[0]))
    return out


def describe(profile, limit: int = 12) -> str:
    """Planes, lines and the most frequent event names of a trace, as text:
    what to look at by hand before trusting the reduction."""
    rows = []
    for plane in profile.planes:
        rows.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            rows.append(f"  line {line.name!r}: {len(events)} events")
            counts: Dict[str, Tuple[int, float]] = {}
            for ev in events:
                c, s = counts.get(ev.name, (0, 0.0))
                counts[ev.name] = (c + 1, s + ev.duration_ns * 1e-9)
            for name, (c, s) in sorted(counts.items(),
                                       key=lambda kv: -kv[1][1])[:limit]:
                rows.append(f"    {c:6d} x {s:10.6f}s  {name[:120]}")
            if line.name == OPS_LINE:
                kernels: Dict[Tuple[str, str], Tuple[int, float]] = {}
                for ev in _events(line):
                    m = KERNEL.search(ev.text)
                    if m:
                        key = (re.sub(r"[.\d]+$", "", ev.op), m.group(0))
                        c, s = kernels.get(key, (0, 0.0))
                        kernels[key] = (c + 1, s + ev.seconds)
                for key, (c, s) in sorted(kernels.items()):
                    rows.append(f"    kernel scope {key}: {c} x {s:.6f}s")
            if events and line.name in (OPS_LINE, MODULES_LINE):
                ev = max(events, key=lambda e: e.duration_ns)
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in ev.stats}
                rows.append(f"    longest: {ev.name[:100]} stats={stats}")
    return "\n".join(rows)


if __name__ == "__main__":  # python3 benchmarks/trace.py <file.xplane.pb>
    import sys

    from jax.profiler import ProfileData

    print(describe(ProfileData.from_file(sys.argv[1])))
