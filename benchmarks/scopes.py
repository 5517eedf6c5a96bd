"""Device seconds of a train step by the program's own scopes.

The program names every device operation of a train step after the layer and
the phase it belongs to (``jax.named_scope``; ``nn/core.py:child_scope`` and
the phase scopes of ``training/model.py``): the scope path of a layer is its
parameter path, ``residual_6/main/multi_head_attention``, and the phases
outside the layers are ``cast``, ``loss``, ``metrics`` and ``optimizer``. JAX
adds its own wrappers, so an operation's ``op_name`` reads

    jit(step)/jvp(residual_7)/main/dense_1/dot_general             forward
    jit(step)/transpose(jvp(residual_7))/main/dense_1/transpose    backward
    jit(step)/optimizer/sub                                        neither

and the profiler writes it, with a trailing ``:``, into the ``tf_op``
statistic of the device event's *metadata* (one per HLO instruction, beside
``hlo_category``, ``flops``, ``bytes_accessed`` and ``source``; looked at by
hand on a v5e trace, PR 24). ``jax.profiler.ProfileData`` hands out an
event's own statistics only (``device_offset_ps``, ``device_duration_ps``),
so ``trace.Event.text`` never holds the path, and this file reads that one
statistic from the ``.xplane.pb`` itself (``op_names``, a reader of the
protobuf wire format for the five messages it needs) and joins it to
``trace``'s events by their name, the HLO line. It then puts each operation
of each traced execution of the train program (``trace.module_runs``, device
0) down to one *group* and one *phase*, by rule on that path and nothing
model-specific beyond layer names:

- the path is the first ``jit(...)/...`` found in the statistic; segments
  that are JAX's and not the program's are dropped (``jit(f)``, ``while``,
  ``body``, ``checkpoint``, ...), transform wrappers (``jvp(x)``,
  ``transpose(jvp(x))``) are read for the phase and unwrapped, and the last
  segment, the primitive's name, is not a scope. Under ``jax.checkpoint``
  the backward pass enters the name stack again and the head of the path
  repeats (``transpose(jvp(a))/jvp(a)/checkpoint/main/...``), which no rule
  below minds;
- phase: ``backward`` if a ``transpose(`` wrapper is on the path (recomputed
  forward operations of a rematerialised block run in the backward pass and
  count there), ``forward`` if only a ``jvp(`` is, ``neither`` otherwise;
- group: ``attention`` (a ``multi_head_attention`` component; the flash
  kernels fall here by their scope), ``mlp`` (a ``dense*`` or ``moe*``
  component under a ``residual`` / ``residual_N`` block), ``head_loss`` (the
  top-level ``dense*`` and everything under ``loss``; the xent kernels fall
  here), ``optimizer``, ``cast``, ``other`` (any other scope of the program:
  embeddings, layer norms, residual adds, ``metrics``) and ``unattributed``
  (no scope of the program on the event: XLA's own copies, what the
  partitioner names after nothing).

An operation counts once, with its own duration; one that encloses others on
the device's line (a ``while`` around its body's operations) counts the part
of its interval they do not cover. Collectives count in the group whose scope
they carry. A fusion carries one ``op_name``, that of the operation XLA chose,
so sums are exact and a boundary can be off by the fused elementwise work.

    python3 benchmarks/scopes.py <file.xplane.pb>

prints the group x phase table, the scopes of ``other``, the operations of
``unattributed`` and, by group, the bare ``convert`` operations: where no
precision policy casts the masters (scope ``cast``), each explicitly-dtyped
layer casts its own weights under its own scope, and those casts are what XLA
leaves standing as bare converts.
"""

from __future__ import annotations

import bisect
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, trace as trace_lib  # noqa: E402

GROUPS = ("attention", "mlp", "head_loss", "optimizer", "cast", "other",
          "unattributed")
PHASES = ("forward", "backward", "neither")

SCOPE_PATH = re.compile(r"(?:^|\s)(jit\(\S*)")
WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# Segments JAX writes for its own loops, checkpoints and shard_maps (seen in
# the steps compiled with scan=True, head_chunks and remat, and in the fsdp4
# trace): no scope of the program's.
NOT_A_SCOPE = re.compile(
    r"^(while|body|cond|closed_call|checkpoint|rematted_computation"
    r"|shard_map)$")
FUNCTION_WRAPPERS = ("jit", "pjit", "xla_call")
BLOCK = re.compile(r"^residual(_\d+)?$")
MLP_LEAF = re.compile(r"^(dense|moe)(_\d+)?$")
HEAD = re.compile(r"^dense(_\d+)?$")

Key = Tuple[str, str]  # (group, phase)


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one serialized protobuf message: an int
    for a varint, a memoryview for a length-delimited field (a string, a
    sub-message); fixed-width fields are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def op_names(path: str, ordinal: int = 0) -> Dict[str, str]:
    """Event name (the HLO line) -> ``tf_op`` of the operations of device
    plane ``ordinal`` in an ``.xplane.pb``. Field numbers (xplane.proto):
    ``XSpace.planes`` 1; ``XPlane.name`` 2, ``.event_metadata`` 4 and
    ``.stat_metadata`` 5 (maps: key 1, value 2); ``XEventMetadata.name`` 2,
    ``.stats`` 5; ``XStatMetadata.name`` 2; ``XStat.metadata_id`` 1,
    ``.str_value`` 5, ``.ref_value`` 7 (the string is then the name of that
    statistic's metadata)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    text = lambda value: bytes(value).decode()
    for number, plane in _fields(space):
        if number != 1:
            continue
        m = trace_lib.DEVICE_PLANE.match(
            text(dict(_fields(plane)).get(2, b"")))
        if not m or int(m.group(2)) != ordinal:
            continue
        stat_names: Dict[int, str] = {}
        events = []
        for n, entry in _fields(plane):
            if n == 5:
                entry = dict(_fields(entry))  # proto3 leaves a zero key out
                stat_names[entry.get(1, 0)] = text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
            elif n == 4:
                events.append(dict(_fields(entry)).get(2, b""))
        out: Dict[str, str] = {}
        for meta in events:
            name = op = None
            for n, value in _fields(meta):
                if n == 2:
                    name = text(value)
                elif n == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op = (text(stat[5]) if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if name and op:
                out[name] = op
        return out
    return {}


def scope_of(text: str) -> Tuple[List[str], str]:
    """``(scope components, phase)`` of an operation's ``tf_op``: the
    program's scopes on its ``jit(...)/...`` path, outermost first, and the
    phase its wrappers name. No path, or none of the program's scopes on it,
    gives ``([], phase)``."""
    m = SCOPE_PATH.search(text)
    if not m:
        return [], "neither"
    # XLA joins the names of merged operations with ';': the first stands.
    segments = m.group(1).split(";")[0].split("/")[:-1]  # less the primitive
    scopes: List[str] = []
    wrappers = set()
    for seg in segments:
        w = WRAPPED.match(seg)
        while w and w.group(1) not in FUNCTION_WRAPPERS:
            wrappers.add(w.group(1))
            seg = w.group(2)
            w = WRAPPED.match(seg)
        if not w and seg and not NOT_A_SCOPE.match(seg):
            scopes.append(seg)
    phase = ("backward" if "transpose" in wrappers
             else "forward" if "jvp" in wrappers else "neither")
    return scopes, phase


def group_of(scopes: List[str]) -> str:
    """The group of a scope path (see the module's docstring)."""
    if not scopes:
        return "unattributed"
    if any(s.startswith("multi_head_attention") for s in scopes):
        return "attention"
    top = scopes[0]
    if top in ("optimizer", "cast"):
        return top
    if top == "loss" or HEAD.match(top):
        return "head_loss"
    for i, s in enumerate(scopes):
        if BLOCK.match(s) and any(MLP_LEAF.match(t) for t in scopes[i + 1:]):
            return "mlp"
    return "other"


def self_seconds(events) -> List[float]:
    """For each event of one device line, in the order given (by start), its
    duration less what the events inside its interval cover."""
    out = [e.seconds for e in events]
    open_: List[int] = []  # indices of the events that enclose the current
    for i, e in enumerate(events):
        while open_ and events[open_[-1]].end <= e.start:
            open_.pop()
        if open_ and e.end <= events[open_[-1]].end:
            out[open_[-1]] -= e.seconds
        open_.append(i)
    return out


def steps(trace, names: Dict[str, str]):
    """For each execution of the train program on device 0: ``(table,
    rows)``, the table the seconds of its operations by ``(group, phase)``,
    a row ``(event, group, phase, scopes, own seconds)``. ``names`` is
    ``op_names`` of the trace's file."""
    dev = trace_lib.device(trace) if trace else None
    if dev is None:
        return []
    ops = sorted(dev.ops, key=lambda e: (e.start, -e.end))
    own = self_seconds(ops)
    starts = [e.start for e in ops]
    out = []
    for run in trace_lib.module_runs(dev):
        lo = bisect.bisect_left(starts, run.start)
        hi = bisect.bisect_left(starts, run.end)
        table: Dict[Key, float] = {}
        rows = []
        for e, s in zip(ops[lo:hi], own[lo:hi]):
            scopes, phase = scope_of(names.get(e.name, ""))
            group = group_of(scopes)
            table[group, phase] = table.get((group, phase), 0.0) + s
            rows.append((e, group, phase, scopes, s))
        out.append((table, rows))
    return out


def group_seconds(table: Dict[Key, float], group: str) -> float:
    return sum(s for (g, _), s in table.items() if g == group)


# -------------------------------------------------------------- readers --
# A traced run leaves its file in <checkout>/.bench_trace/<cell>/ (run.py);
# a reader is handed the parsed trace, not the file, and finds it there.
TRACE_ROOT = os.path.join(harness.ROOT, ".bench_trace")


def tables_of(ctx) -> List[Dict[Key, float]]:
    """The step tables of a traced run, for a reader; ``[]`` without a
    trace, a run of the program, or the program's scopes: a commit before
    the scopes gives a trace that names its kernels and nothing of its
    layers or phases, every operation falls in ``other`` or
    ``unattributed``, and the readers report nothing for it. Kept on the
    trace: six readers ask for the same tables."""
    if ctx.trace is None:
        return []
    if not hasattr(ctx.trace, "scope_tables"):
        path = trace_lib.find_xplane(
            os.path.join(TRACE_ROOT, ctx.cell["name"]))
        tables = [t for t, _ in steps(ctx.trace, op_names(path))
                  ] if path else []
        named = set(GROUPS) - {"other", "unattributed"}
        if not any(g in named for t in tables for g, _ in t):
            tables = []
        ctx.trace.scope_tables = tables
    return ctx.trace.scope_tables


def group_ms(ctx, group: str) -> Optional[float]:
    """Median over the traced steps of the milliseconds in ``group``, both
    phases."""
    tables = tables_of(ctx)
    if not tables:
        return None
    return 1e3 * harness.median(group_seconds(t, group) for t in tables)


def unattributed_pct(ctx) -> Optional[float]:
    """Median over the traced steps of the share of the step's operation
    seconds that carry no scope of the program."""
    tables = [t for t in tables_of(ctx) if sum(t.values()) > 0]
    if not tables:
        return None
    return harness.median(
        100.0 * group_seconds(t, "unattributed") / sum(t.values())
        for t in tables)


# ------------------------------------------------------------- printing --
def _median_table(tables) -> Dict[Key, float]:
    cells = {c for t in tables for c in t}
    return {c: harness.median(t.get(c, 0.0) for t in tables) for c in cells}


def describe(path: str, limit: int = 12) -> str:
    """Of an ``.xplane.pb``: the group x phase table (milliseconds, median
    over the traced steps), the scopes of ``other``, the operations of
    ``unattributed`` and the bare converts by group."""
    runs = steps(trace_lib.load(path), op_names(path))
    if not runs:
        return "no execution of a program on a device in this trace"
    tables = [t for t, _ in runs]
    med = _median_table(tables)
    whole = harness.median(sum(t.values()) for t in tables)
    rows = [f"{len(tables)} steps; operation seconds a step, median "
            f"{1e3 * whole:.3f} ms",
            f"{'group':14s}" + "".join(f"{p:>11s}" for p in PHASES)
            + f"{'all':>11s}{'share':>8s}"]
    for g in GROUPS:
        by_phase = [1e3 * med.get((g, p), 0.0) for p in PHASES]
        all_ms = 1e3 * harness.median(group_seconds(t, g) for t in tables)
        rows.append(f"{g:14s}" + "".join(f"{v:11.3f}" for v in by_phase)
                    + f"{all_ms:11.3f}{100 * all_ms / (1e3 * whole):7.2f}%")
    # What stands behind the two groups that name no layer: summed over the
    # traced steps, per step.
    n = len(tables)
    other: Dict[str, float] = {}
    unattributed: Dict[str, float] = {}
    converts: Dict[str, float] = {}
    for _, events in runs:
        for e, group, phase, scopes, s in events:
            if " convert(" in e.name:  # the opcode, not a fusion's name
                key = f"{group} {phase}"
                converts[key] = converts.get(key, 0.0) + s / n
            if group == "other":
                leaf = re.sub(r"_\d+$", "", scopes[-1])
                top = re.sub(r"_\d+$", "", scopes[0])
                key = f"{top}/../{leaf} {phase}" if len(scopes) > 1 else (
                    f"{top} {phase}")
                other[key] = other.get(key, 0.0) + s / n
            elif group == "unattributed":
                key = trace_lib.kernel_name(e) or re.sub(
                    r"(\.remat\d*|\.clone|[.\-_]?\d+)+$", "", e.op)
                unattributed[key] = unattributed.get(key, 0.0) + s / n
    for title, sums in (("other, by scope", other),
                        ("unattributed, by operation", unattributed),
                        ("bare convert operations, by group", converts)):
        rows.append(f"{title} (ms a step):")
        for key, s in sorted(sums.items(), key=lambda kv: -kv[1])[:limit]:
            rows.append(f"  {1e3 * s:10.3f}  {key}")
    return "\n".join(rows)


if __name__ == "__main__":  # python3 benchmarks/scopes.py <file.xplane.pb>
    print(describe(sys.argv[1]))
