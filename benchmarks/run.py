#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Exits non-zero, printing no result, unless JAX's default backend
is a TPU with the chips the cell asks for. The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
with ``--trace 1``, ``breakdown``. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a short traced stretch.

``--rehearsal`` takes the tiny cells of ``tests/bench_harness/rehearsal.json``
instead, runs anywhere, and prints every metric as null with the platform
named: it proves the control flow, and no number of it is a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, trace as trace_lib  # noqa: E402

REHEARSAL_MANIFEST = os.path.join(ROOT, "tests", "bench_harness",
                                  "rehearsal.json")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    return ap.parse_args(argv)


def layer_metrics(env, manifest, cell_name, telemetry, device_kind):
    """(metrics, parsed trace) of a traced run. A reader that finds nothing
    to read returns None and its metric is left out of the line; a reader
    that raises fails the run."""
    xplane = trace_lib.find_xplane(env.trace_dir)
    parsed = trace_lib.load(xplane) if xplane else None
    peaks = None if env.rehearsal else harness.peaks_for(device_kind)
    ctx = harness.LayerContext(
        trace=parsed, telemetry=telemetry, config=env.config,
        traffic=env.traffic, cell=env.cell, peaks=peaks, values={})
    reported = {m["name"] for m in harness.metrics_of(
        manifest, "end_to_end", cell_name)}
    out = {}
    for metric in harness.metrics_of(manifest, "per_layer", cell_name):
        if metric["moves"] not in reported:
            continue
        reader = harness.load_module(manifest, "layer_metrics",
                                     metric["name"])
        value = reader.read(ctx)
        if value is None:
            if env.rehearsal:  # a rehearsal lists every name, valueless
                out[metric["name"]] = {"value": None, "unit": metric["unit"]}
            continue
        ctx.values[metric["name"]] = float(value)
        out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out, parsed


def main(argv=None) -> int:
    args = parse_args(argv)
    manifest = harness.load_manifest(
        REHEARSAL_MANIFEST if args.rehearsal else harness.MANIFEST)
    cell = harness.entry(manifest, "workloads", args.workload)
    config_entry = harness.entry(manifest, "configs", cell["config"])
    config = harness.load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = harness.load_json(
        harness.find_file(manifest, "traffic", cell["traffic"]))
    seconds = args.seconds if args.seconds is not None else float(
        manifest["run_seconds"])

    device = harness.device_gate(cell["chips"], args.rehearsal)
    import jax

    # A rehearsal keeps no cache: XLA:CPU cache hits log kilobytes to stderr.
    cache_dir = None if args.rehearsal else harness.enable_compile_cache()
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    env = harness.Env(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        rehearsal=args.rehearsal, t_start=T_START, device=device,
        cache=harness.CacheCounter(), trace_dir=trace_dir,
        family=harness.load_module(manifest, "families", config["family"]),
        reference=harness.load_module(manifest, "reference",
                                      config["family"]),
    )
    env.log(event="start", workload=args.workload, seed=args.seed,
            seconds=seconds, trace=args.trace, device=device,
            compile_cache_dir=cache_dir, jax=jax.__version__)
    driver = harness.load_module(manifest, "drivers", traffic["driver"])
    result = driver.run(env)
    env.log(event="checks", **result["checks"])

    device = dict(device, memory_peak_bytes=result["memory_peak_bytes"])
    values = dict(result["end_to_end"])
    correct, breakdown = result["correct"], None
    if args.trace:
        # The trace stays in .bench_trace/<cell>/ until the cell's next run:
        # python3 benchmarks/trace.py <file> describes it for a reader.
        metrics, parsed = layer_metrics(
            env, manifest, args.workload, dict(result["telemetry"]),
            device["kind"])
        if parsed is not None and parsed.devices:
            breakdown = {
                "device_ops": [[n, s] for n, s in trace_lib.top_ops(parsed)],
                "idle_gaps": [[n, s] for n, s in trace_lib.idle_gaps(parsed)],
            }
            device["busy_s"] = trace_lib.busy_seconds(parsed)
            device["window_s"] = parsed.window_s
        if not args.rehearsal:
            # The Mosaic kernels the mix expects ran on the device.
            ran = trace_lib.kernel_names(parsed) if parsed else []
            missing = [k for k in traffic.get("expect_kernels", [])
                       if not any(name.startswith(k) for name in ran)]
            env.log(event="kernels", ran=ran, missing=missing)
            correct = correct and not missing
    else:
        metrics = {}
        for metric in harness.metrics_of(manifest, "end_to_end",
                                         args.workload):
            if metric["name"] not in values:
                raise harness.BenchmarkError(
                    f"driver {traffic['driver']!r} did not measure "
                    f"{metric['name']!r} for {args.workload}")
            metrics[metric["name"]] = {"value": float(values[metric["name"]]),
                                       "unit": metric["unit"]}
    if args.rehearsal:
        # A rehearsal's numbers are not measurements: name the platform,
        # keep the names, drop the values.
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}
    print(harness.result_line(
        correct=correct, attempted=result["attempted"],
        failed=result["failed"], metrics=metrics, device=device,
        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchmarkError as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        sys.exit(2)
