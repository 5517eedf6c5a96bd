"""``dtpu-events``: summarize an event log + flight dumps into a postmortem.

    dtpu-events run.events.jsonl
    dtpu-events run.events.jsonl --flight /tmp/flight-rank1-pid33.jsonl
    dtpu-events run.events.jsonl --json
    dtpu-events run.events.jsonl --follow   # live tail for a running gang
    dtpu-events --timeline timeline-rank0-pid33.jsonl   # a set-up, by span

Reads a supervised run's JSONL event log (``utils.events``) and renders a
human postmortem: the attempt timeline, injected faults, per-recovery
MTTR rows, cross-rank skew / straggler attribution (``obs.aggregate``),
and the tail of every flight-recorder dump the run referenced
(``flight_dump`` events; ``--flight`` adds files by hand) — the seconds
before each death, not just the lifecycle facts. ``--json`` emits the
same summary as one machine-readable object.

``--follow`` tails a LIVE log instead: one rendered line per event as
it lands, surviving the writer's rotate/truncate the same way
``EventLog`` survives its reader's (stat the inode, reopen on change)
and skipping a torn tail line until its newline arrives — watch a
serving gang (``serve_service``) or a supervised training run without
re-running the postmortem.

``--timeline`` reads what ``obs.flight.dump_timeline`` wrote at a
process's exit instead (the span timeline and the compile ledger) and
renders the process from its start: each top-level span with its start,
duration and self time (the duration less what its child spans and the
compile ledger's records cover), the gaps between top-level spans as
``caller``, under each span its children and the programs it compiled with
their stage seconds and ``hit`` / ``miss``, and the set-up window's sums
over the programs that a span of the program asked for (the caller's own
are under its ``caller`` rows and in no sum; docs/OBSERVABILITY.md
"Compile ledger").

jax-free: runs on any controller box against a copied log file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List, Optional

from ..utils import event_schema as evs
from ..utils.events import read_events
from . import aggregate
from .flight import read_dump


def _fmt_ts(ts) -> str:
    try:
        return time.strftime("%H:%M:%S", time.localtime(float(ts)))
    except (TypeError, ValueError):
        return "?"


def summarize(events: List[dict], flight_paths=(),
              straggler_threshold: float = aggregate.DEFAULT_THRESHOLD
              ) -> dict:
    """The postmortem as data; ``render`` turns it into text."""
    attempts = [e for e in events if e["event"] == evs.ATTEMPT_START]
    ends = [e for e in events if e["event"] == evs.ATTEMPT_END]
    faults = [e for e in events if e["event"] == evs.FAULT_INJECTED]
    recoveries = [e for e in events if e["event"] == evs.RECOVERY]
    resizes = [e for e in events if e["event"] == evs.GANG_RESIZE]
    terminal = next(
        (e for e in reversed(events)
         if e["event"] in (evs.RUN_COMPLETE, evs.BUDGET_EXHAUSTED,
                           evs.PREEMPTION_CAP_EXHAUSTED)),
        None,
    )
    dump_paths: List[str] = [
        e["path"] for e in events
        if e["event"] == evs.FLIGHT_DUMP and e.get("path")
    ]
    for p in flight_paths:
        if str(p) not in dump_paths:
            dump_paths.append(str(p))
    dumps = []
    for p in dump_paths:
        records = read_dump(p)
        header = next(
            (r for r in records if r.get("kind") == "flight_header"), None
        )
        dumps.append({
            "path": str(p),
            "readable": bool(records),
            "reason": (header or {}).get("reason"),
            "rank": (header or {}).get("rank"),
            "records": [r for r in records
                        if r.get("kind") != "flight_header"],
        })
    return {
        "events": len(events),
        "attempts": [
            {
                "attempt": a.get("attempt"),
                "world_size": a.get("world_size"),
                "started": a.get("ts"),
                "ok": next(
                    (e.get("ok") for e in ends
                     if e.get("attempt") == a.get("attempt")), None
                ),
                "failed_ranks": next(
                    (e.get("failed_ranks") for e in ends
                     if e.get("attempt") == a.get("attempt")), None
                ),
            }
            for a in attempts
        ],
        "terminal": terminal,
        "faults": faults,
        "resizes": resizes,
        "recoveries": recoveries,
        "rank_skew": aggregate.skew_report(events),
        "straggler": aggregate.straggler(events, straggler_threshold),
        "straggler_events": [e for e in events if e["event"] == evs.STRAGGLER],
        # Last-writer-wins: one schedule/bubble row per postmortem (each
        # fit re-emits; the latest reflects the run that ended the log).
        "pipeline_schedule": next(
            (e for e in reversed(events)
             if e["event"] == evs.PIPELINE_SCHEDULE_SELECTED), None
        ),
        "bubble": next(
            (e for e in reversed(events)
             if e["event"] == evs.BUBBLE_REPORT), None
        ),
        # Speculation-that-pays timeline: last spec_verify aggregate,
        # every draft sync / per-tenant k move, and the gossip traffic.
        "spec_verify": next(
            (e for e in reversed(events)
             if e["event"] == evs.SPEC_VERIFY), None
        ),
        "draft_syncs": [e for e in events if e["event"] == evs.DRAFT_SYNC],
        "spec_k_adjusts": [e for e in events
                           if e["event"] == evs.SPEC_K_ADJUST],
        "gossip_advertises": [e for e in events
                              if e["event"] == evs.PREFIX_GOSSIP_ADVERTISE],
        "gossip_adopts": [e for e in events
                          if e["event"] == evs.PREFIX_GOSSIP_ADOPT],
        "flight_dumps": dumps,
    }


def render(summary: dict, *, tail: int = 10) -> str:
    lines = [f"postmortem: {summary['events']} events"]
    for a in summary["attempts"]:
        status = ("ok" if a["ok"] else
                  "FAILED" if a["ok"] is not None else "no end record")
        extra = (f" failed_ranks={a['failed_ranks']}"
                 if a.get("failed_ranks") else "")
        lines.append(
            f"  attempt {a['attempt']} [{_fmt_ts(a['started'])}] "
            f"world={a['world_size']}: {status}{extra}"
        )
    term = summary["terminal"]
    if term is not None:
        lines.append(f"  terminal: {term['event']}")
    for f in summary["faults"]:
        where = f" replica={f['replica']}" if f.get("replica") else ""
        lines.append(
            f"  fault injected: {f.get('mode')} at step {f.get('step')}"
            f"{where} [{_fmt_ts(f.get('ts'))}]"
        )
    for rs in summary["resizes"]:
        lines.append(
            f"  gang resize {rs.get('from_world')} -> {rs.get('to_world')} "
            f"({rs.get('reason')}, {rs.get('trigger')})"
        )
    for r in summary["recoveries"]:
        lines.append(
            f"  recovery (attempt {r.get('failed_attempt')} -> "
            f"{r.get('recovered_attempt')}): detect={r.get('detect_s')}s "
            f"gang_reform={r.get('gang_reform_s')}s "
            f"restore={r.get('restore_s')}s[{r.get('restore_tier')}] "
            f"recompile={r.get('recompile_s')}s"
        )
        for p in r.get("flight_dumps") or ():
            lines.append(f"    flight dump: {p}")
    skew = summary["rank_skew"]
    if skew is not None:
        lines.append(
            f"  rank skew: gang median {skew['gang_median_step_s']}s/step, "
            f"max skew {skew['max_skew']}x (rank {skew['slowest_rank']})"
        )
        for row in skew["ranks"]:
            lines.append(
                f"    rank {row['rank']}: median {row['median_step_s']}s "
                f"(x{row['skew']}, {row['samples']} samples)"
            )
    sched = summary.get("pipeline_schedule")
    if sched is not None:
        lines.append(
            f"  pipeline schedule: {sched.get('schedule')} "
            f"(interleave={sched.get('interleave')}, "
            f"stages={sched.get('num_stages')}, "
            f"microbatches={sched.get('num_microbatches')})"
        )
    bub = summary.get("bubble")
    if bub is not None:
        lines.append(
            f"  pipeline bubble: {bub.get('bubble_fraction')} idle "
            f"over {bub.get('ticks')} ticks"
        )
    sv = summary.get("spec_verify")
    if sv is not None:
        lines.append(
            f"  speculative decode: accept_rate={sv.get('accept_rate')} "
            f"({sv.get('accepted')}/{sv.get('proposed')} over "
            f"{sv.get('rounds')} rounds, "
            f"{sv.get('tokens_per_dispatch')} tok/dispatch)"
        )
    for ds in summary.get("draft_syncs", ()):
        lines.append(
            f"  draft sync [{_fmt_ts(ds.get('ts'))}]: "
            f"weights_version={ds.get('weights_version')} "
            f"staleness={ds.get('staleness')} source={ds.get('source')}"
        )
    for ka in summary.get("spec_k_adjusts", ()):
        lines.append(
            f"  spec_k adjust [{_fmt_ts(ka.get('ts'))}]: "
            f"tenant={ka.get('tenant')} {ka.get('old_k')} -> "
            f"{ka.get('new_k')} (accept_ema={ka.get('accept_ema')})"
        )
    adv = summary.get("gossip_advertises", ())
    adp = summary.get("gossip_adopts", ())
    if adv or adp:
        lines.append(
            f"  prefix gossip: {len(adv)} advertise(s) "
            f"({sum(int(e.get('blocks', 0)) for e in adv)} blocks), "
            f"{len(adp)} adopt(s) "
            f"({sum(int(e.get('blocks', 0)) for e in adp)} blocks)"
        )
        for e in adp:
            lines.append(
                f"    adopt [{_fmt_ts(e.get('ts'))}]: {e.get('source')} "
                f"-> {e.get('replica')} ({e.get('blocks')} blocks, "
                f"weights_version={e.get('weights_version')})"
            )
    strag = summary["straggler"] or next(
        iter(summary["straggler_events"]), None
    )
    if strag is not None:
        lines.append(
            f"  STRAGGLER: rank {strag.get('rank')} at "
            f"{strag.get('skew')}x the gang median "
            f"(threshold {strag.get('threshold')})"
        )
    for d in summary["flight_dumps"]:
        if not d["readable"]:
            lines.append(f"  flight dump {d['path']}: unreadable/empty")
            continue
        lines.append(
            f"  flight dump {d['path']} (rank {d['rank']}, "
            f"reason={d['reason']!r}): last {min(tail, len(d['records']))} "
            f"of {len(d['records'])} records"
        )
        for rec in d["records"][-tail:]:
            body = {k: v for k, v in rec.items() if k not in ("ts", "kind")}
            lines.append(
                f"    [{_fmt_ts(rec.get('ts'))}] {rec.get('kind')} "
                + " ".join(f"{k}={v}" for k, v in body.items())
            )
    return "\n".join(lines)


# ------------------------------------------------------------- timeline --
PROGRAM_MIN_S = 0.1  # programs under this are summed, not named
GAP_MIN_S = 0.001  # gaps between top-level spans under this are not shown
MISSED_SHOWN = 5  # cache misses named in the window's line, longest first
STAGES = ("trace", "lower", "backend")


def _union_s(intervals, lo=None, hi=None) -> float:
    """Seconds the union of ``(start, end)`` nanosecond intervals covers,
    each cut to ``[lo, hi]`` first."""
    total, reach = 0, None
    for s, e in sorted(intervals):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total / 1e9


def _programs(records) -> dict:
    """Compile records of one span, by program: seconds a stage, cache
    verdicts, and the union a stage over all of them."""
    by_name: dict = {}
    for r in records:
        row = by_name.setdefault(
            r["fun_name"], {"name": r["fun_name"], "seconds": 0.0,
                            **{st: 0.0 for st in STAGES}, "cache": []})
        seconds = (r["end"] - r["start"]) / 1e9
        row[r["stage"]] += seconds
        row["seconds"] += seconds
        if r.get("cache"):
            row["cache"].append(r["cache"])
    rows = sorted(by_name.values(), key=lambda row: -row["seconds"])
    verdicts = [c for row in rows for c in row["cache"]]
    return {
        "named": [row for row in rows if row["seconds"] >= PROGRAM_MIN_S],
        "others": sum(row["seconds"] < PROGRAM_MIN_S for row in rows),
        "stage_s": {st: _union_s((r["start"], r["end"]) for r in records
                                 if r["stage"] == st) for st in STAGES},
        "hit": verdicts.count("hit"), "miss": verdicts.count("miss"),
        "uncached": verdicts.count("uncached"),
    }


def summarize_timeline(records: List[dict]) -> dict:
    """A timeline dump as data: the main thread's top-level spans in order
    with the ``caller`` gaps between them, each span's children, programs
    and self time, and the set-up window's sums. Times are seconds from
    the process's start."""
    header = next((r for r in records
                   if r.get("kind") == "timeline_header"), {})
    spans = [r for r in records if r.get("kind") == "span"]
    compiles = [r for r in records if r.get("kind") == "compile"]
    zero = header.get("process_start") or min(
        [r["start"] for r in spans + compiles], default=0)
    main = header.get("main_thread")
    if main is None and spans:
        main = spans[0]["thread"]

    def node(span):
        lo, hi, thread = span["start"], span["end"], span["thread"]
        inside = lambda r: (r["thread"] == thread and r["start"] >= lo
                            and r["end"] <= hi)
        children = sorted(
            (c for c in spans if c is not span and inside(c)
             and c["parent"] == span["path"]), key=lambda c: c["start"])
        mine = [r for r in compiles
                if inside(r) and r.get("span") == span["path"]]
        covered = ([(c["start"], c["end"]) for c in children]
                   + [(r["start"], r["end"]) for r in mine])
        return {
            "path": span["path"], "start": (lo - zero) / 1e9,
            "seconds": (hi - lo) / 1e9,
            "self_seconds": (hi - lo) / 1e9 - _union_s(covered, lo, hi),
            "children": [node(c) for c in children],
            "programs": _programs(mine) if mine else None,
        }

    top = sorted((sp for sp in spans
                  if sp["thread"] == main and sp["parent"] is None),
                 key=lambda sp: sp["start"])
    rows, reach = [], zero
    for sp in top:
        if sp["start"] - reach >= GAP_MIN_S * 1e9:
            gap = [r for r in compiles if r["thread"] == main
                   and r["start"] >= reach and r["end"] <= sp["start"]]
            rows.append({
                "path": "(before import)" if sp["path"] == "import"
                and reach == zero else "caller",
                "start": (reach - zero) / 1e9,
                "seconds": (sp["start"] - reach) / 1e9,
                "self_seconds": None, "children": [],
                "programs": _programs(gap) if gap else None})
        rows.append(node(sp))
        reach = max(reach, sp["end"])
    setups = [sp for sp in top if sp["path"] == "fit_setup"]
    window = None
    if setups:
        end = setups[-1]["end"]
        # The program's own: a record with no span is a program the
        # caller compiled (its rows show it), and no sum of the window's.
        inside = [r for r in compiles
                  if r["end"] <= end and r.get("span") is not None]
        window = {
            "seconds": (end - zero) / 1e9,
            "before_import_s": next(
                (r["seconds"] for r in rows
                 if r["path"] == "(before import)"), 0.0),
            "spans_s": _union_s(((sp["start"], sp["end"]) for sp in top),
                                zero, end),
            **{f"{st}_s": _union_s(((r["start"], r["end"]) for r in inside
                                    if r["stage"] == st), zero, end)
               for st in STAGES},
            "cache_misses": sum(r.get("cache") == "miss" for r in inside),
            "longest_missed": [
                [r["fun_name"], (r["end"] - r["start"]) / 1e9]
                for r in sorted(inside, key=lambda r: r["start"] - r["end"])
                if r.get("cache") == "miss"][:MISSED_SHOWN],
        }
    return {
        "pid": header.get("pid"), "rank": header.get("rank"),
        "spans": len(spans), "compile_records": len(compiles),
        "dropped": header.get("dropped") or {},
        "short_traces": header.get("short_traces", 0),
        "short_trace_seconds": header.get("short_trace_seconds", 0.0),
        "other_threads": sorted({sp["thread"] for sp in spans} - {main}),
        "rows": rows, "window": window,
    }


def _program_lines(programs: dict, pad: str) -> List[str]:
    st = programs["stage_s"]
    lines = [
        f"{pad}programs: trace {st['trace']:.3f} s, lower "
        f"{st['lower']:.3f}, backend {st['backend']:.3f} (union); "
        f"{programs['hit']} hit, {programs['miss']} miss, "
        f"{programs['uncached']} uncached"]
    for row in programs["named"]:
        cache = ",".join(sorted(set(row["cache"]))) or "-"
        lines.append(
            f"{pad}  {row['name']}: trace {row['trace']:.3f} lower "
            f"{row['lower']:.3f} backend {row['backend']:.3f} [{cache}]")
    if programs["others"]:
        lines.append(f"{pad}  and {programs['others']} under "
                     f"{PROGRAM_MIN_S} s each")
    return lines


def render_timeline(summary: dict) -> str:
    dropped = sum(summary["dropped"].values())
    lines = [
        f"timeline: pid {summary['pid']} rank {summary['rank']}, "
        f"{summary['spans']} spans, {summary['compile_records']} compile "
        f"records, {dropped} dropped, {summary['short_traces']} traces "
        f"under 1 ms ({summary['short_trace_seconds']:.3f} s) counted and "
        "not kept; seconds from the process's start",
        f"{'start':>9} {'seconds':>9} {'self':>9}  span"]

    def emit(row, depth):
        self_s = ("-" if row["self_seconds"] is None
                  else f"{row['self_seconds']:.3f}")
        lines.append(f"{row['start']:9.3f} {row['seconds']:9.3f} "
                     f"{self_s:>9}  {'  ' * depth}{row['path']}")
        if row["programs"]:
            lines.extend(_program_lines(
                row["programs"], " " * 32 + "  " * depth))
        for child in row["children"]:
            emit(child, depth + 1)

    for row in summary["rows"]:
        emit(row, 0)
    if summary["other_threads"]:
        lines.append(f"  spans of {len(summary['other_threads'])} other "
                     "thread(s) are in the file and not shown")
    w = summary["window"]
    if w is not None:
        missed = str(w["cache_misses"]) + (
            " (longest: " + ", ".join(
                f"{name} {seconds:.1f} s"
                for name, seconds in w["longest_missed"]) + ")"
            if w["longest_missed"] else "")
        lines.append(
            f"set-up window (the process's start to the end of the last "
            f"fit_setup): {w['seconds']:.3f} s; before import "
            f"{w['before_import_s']:.3f}, in the program's top-level "
            f"spans {w['spans_s']:.3f}, caller "
            f"{w['seconds'] - w['before_import_s'] - w['spans_s']:.3f}; "
            f"the program's own compiles: trace {w['trace_s']:.3f}, "
            f"lower {w['lower_s']:.3f}, backend {w['backend_s']:.3f}; "
            f"cache misses: {missed}")
    return "\n".join(lines)


def event_line(event: dict) -> str:
    """One event as one follow-mode line: timestamp, kind, then the
    payload keys in emit order (the transport's own ts/event/pid are
    folded into the prefix)."""
    body = {k: v for k, v in event.items()
            if k not in ("ts", "event", "pid")}
    fields = " ".join(f"{k}={v}" for k, v in body.items())
    return (f"[{_fmt_ts(event.get('ts'))}] {event.get('event')}"
            + (f" {fields}" if fields else ""))


def follow(path, *, poll_s: float = 0.2, stop=None):
    """Yield events appended to ``path`` as they land, forever (or until
    ``stop()`` returns true — the test seam). The reader mirrors
    ``EventLog``'s writer idiom from the other side: on EOF, stat the
    path and reopen when the inode changed or the file shrank (rotation/
    truncation), and hold back a torn tail line until its newline
    arrives — a half-written record is pending, not corrupt. A path that
    does not exist yet is waited for, so the tail can start before the
    gang does."""
    path = str(path)
    f = None
    ino = None
    buf = ""
    try:
        while True:
            if f is None:
                try:
                    f = open(path, "r")
                    ino = os.fstat(f.fileno()).st_ino
                    buf = ""
                except FileNotFoundError:
                    if stop is not None and stop():
                        return
                    time.sleep(poll_s)
                    continue
            chunk = f.read()
            if chunk:
                buf += chunk
                while "\n" in buf:
                    line, buf = buf.split("\n", 1)
                    if not line.strip():
                        continue
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        continue  # torn mid-rotation: skip, keep tailing
                continue
            try:
                st = os.stat(path)
                rotated = (st.st_ino != ino
                           or st.st_size < f.tell() - len(buf))
            except FileNotFoundError:
                rotated = True
            if rotated:
                f.close()
                f = None
                continue
            if stop is not None and stop():
                return
            time.sleep(poll_s)
    finally:
        if f is not None:
            f.close()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="dtpu-events", description=__doc__)
    ap.add_argument("event_log", type=str, nargs="?",
                    help="JSONL event log (the supervisor's DTPU_EVENT_LOG)")
    ap.add_argument("--timeline", type=str, metavar="FILE",
                    help="render a timeline dump (timeline-rank<r>-pid<p>"
                         ".jsonl beside the flight dumps) instead: the "
                         "process from its start by span, with the "
                         "programs each span compiled")
    ap.add_argument("--flight", action="append", default=[],
                    help="extra flight-dump file(s) to include (dumps "
                         "referenced by flight_dump events are found "
                         "automatically)")
    ap.add_argument("--tail", type=int, default=10,
                    help="flight records to show per dump (default 10)")
    ap.add_argument("--straggler-threshold", type=float,
                    default=aggregate.DEFAULT_THRESHOLD)
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object instead of "
                         "the human rendering")
    ap.add_argument("--follow", action="store_true",
                    help="tail the log live (one line per event as it "
                         "lands; waits for the file if it does not exist "
                         "yet; ctrl-C to stop)")
    args = ap.parse_args(argv)
    if args.timeline:
        records = read_dump(args.timeline)
        if not records:
            print(f"dtpu-events: no readable timeline in {args.timeline}",
                  file=sys.stderr)
            return 2
        summary = summarize_timeline(records)
        print(json.dumps(summary) if args.json
              else render_timeline(summary))
        return 0
    if args.event_log is None:
        ap.error("an event log, or --timeline FILE, is required")
    if args.follow:
        try:
            for event in follow(args.event_log):
                print(json.dumps(event) if args.json
                      else event_line(event), flush=True)
        except KeyboardInterrupt:
            pass
        return 0
    if not Path(args.event_log).exists():
        print(f"dtpu-events: no such event log: {args.event_log}",
              file=sys.stderr)
        return 2
    events = read_events(args.event_log)
    summary = summarize(events, flight_paths=args.flight,
                        straggler_threshold=args.straggler_threshold)
    if args.json:
        print(json.dumps(summary))
    else:
        print(render(summary, tail=args.tail))
    return 0


if __name__ == "__main__":
    sys.exit(main())
