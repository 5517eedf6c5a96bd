"""Parse jax.profiler xplane protos into per-op device-time tables.

Host wall-clock noise makes sub-10ms A/Bs meaningless; the xplane trace
records exact device timestamps. tensorboard-plugin-profile's converter is version-
incompatible with the installed TF, so this parses the raw proto
(tensorflow.tsl.profiler.protobuf.xplane_pb2) directly.

Usage:
    table = capture(lambda: [step() for _ in range(5)])  # dict name -> ps
    print_table(table, top=25)
"""

import glob
import os
import tempfile
from collections import defaultdict

import jax
import numpy as np


def _load_xspace(logdir):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no xplane.pb under {logdir}")
    xs = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def capture(run, logdir=None, line_name="XLA Ops"):
    """Run ``run()`` under a profiler trace; return {op_name: total_ps} from
    the device plane's ``line_name`` line (which tiles the step exactly)."""
    logdir = logdir or tempfile.mkdtemp(prefix="xplane_")
    jax.profiler.start_trace(logdir)
    try:
        out = run()
        np.asarray(jax.device_get(
            jax.tree_util.tree_leaves(out)[0].ravel()[:1]))
    finally:
        jax.profiler.stop_trace()
    xs = _load_xspace(logdir)
    table = defaultdict(int)
    counts = defaultdict(int)
    for plane in xs.planes:
        if "TPU" not in plane.name and "device" not in plane.name.lower():
            continue
        meta = {m_id: m.name for m_id, m in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != line_name:
                continue
            for ev in line.events:
                name = meta.get(ev.metadata_id, str(ev.metadata_id))
                table[name] += ev.duration_ps
                counts[name] += 1
    return dict(table), dict(counts)


def bucketize(table, buckets):
    """Aggregate {op: ps} into labeled buckets by substring match against
    the op NAME only (the text before ' = ' — full event names embed operand
    lists, which poison substring matches). First match wins, in order;
    returns {label: ms} with an 'other' catch-all."""
    out = defaultdict(float)
    for name, ps in table.items():
        op = name.split(" = ")[0]
        for label, subs in buckets:
            if any(s in op for s in subs):
                out[label] += ps / 1e9
                break
        else:
            out["other"] += ps / 1e9
    return dict(out)


def print_table(table, counts=None, top=30):
    rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
    total = sum(table.values())
    print(f"{'op':<64} {'ms':>9} {'%':>5}  n")
    for name, ps in rows:
        n = counts.get(name, 0) if counts else 0
        print(f"{name[:64]:<64} {ps/1e9:9.3f} {ps/total*100:5.1f}  {n}")
    print(f"{'TOTAL':<64} {total/1e9:9.3f}")
