"""What every cell shares: the manifest, finding a cell's files by name, the
device gate, the compile-cache counter, the peaks table and the result line.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric sits in a file of its own under one of the manifest's
``paths`` and is found here by its name, so a later PR adds files and entries
to ``BENCHMARK.json`` and edits nothing:

- ``<path>/configs/<config>.json`` (the manifest names it itself, by ``file``)
- ``<path>/traffic/<traffic>.json``      parameters of one traffic mix
- ``<path>/drivers/<driver>.py``         ``run(env) -> dict``; named by the mix
- ``<path>/families/<family>.py``        builds the system's model for a config
- ``<path>/reference/<family>.py``       the plain float32 reference
- ``<path>/layer_metrics/<metric>.py``   ``read(ctx) -> float | None``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class BenchmarkError(RuntimeError):
    """The benchmark cannot run as asked; the process exits non-zero."""


# -------------------------------------------------------------- manifest --
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    return load_json(path)


def entry(manifest: dict, section: str, name: str) -> dict:
    for item in manifest[section]:
        if item["name"] == name:
            return item
    known = ", ".join(i["name"] for i in manifest[section])
    raise BenchmarkError(f"no {section} entry named {name!r}; have: {known}")


def find_file(manifest: dict, kind: str, name: str, exts=(".json",)) -> str:
    """``<path>/<kind>/<name><ext>`` in the first of the manifest's paths
    that has it."""
    tried = []
    for base in manifest["paths"]:
        for ext in exts:
            path = os.path.join(ROOT, base, kind, name + ext)
            if os.path.isfile(path):
                return path
            tried.append(os.path.relpath(path, ROOT))
    raise BenchmarkError(f"no {kind} file for {name!r}; tried {tried}")


def load_module(manifest: dict, kind: str, name: str):
    """Import ``<path>/<kind>/<name>.py`` by file, under a name of its own."""
    path = find_file(manifest, kind, name, (".py",))
    mod_name = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest: dict, section: str, cell: str) -> List[dict]:
    """The metrics of ``section`` that ``cell`` reports: those that list it
    under ``workloads``, and those that list none."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------- device --
def device_gate(chips: int, rehearsal: bool) -> dict:
    """Refuse to measure anywhere but on a TPU with the chips the cell asks
    for. A rehearsal runs anywhere and reports where."""
    import jax

    backend = jax.default_backend()
    devices = jax.devices()
    if not rehearsal:
        if backend != "tpu":
            raise BenchmarkError(
                f"jax.default_backend() is {backend!r}, not 'tpu': the "
                "benchmark measures on the chip only (no CPU fallback)")
        if len(devices) < chips:
            raise BenchmarkError(
                f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": min(chips, len(devices)) if rehearsal else chips}


def memory_peak_bytes(devices) -> Optional[int]:
    """The allocator's ``peak_bytes_in_use`` on the fullest of ``devices``,
    over the life of the process (None where the backend does not say, as
    XLA:CPU). On the v5e it counts live buffers (weights, optimizer state,
    cache, batches), not the temporaries of a running program: at
    ``gpt2-medium`` B=8 it read 5.34 GB where the compiled step needs
    12.4 GB (PR 22)."""
    found = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            found.append(int(stats["peak_bytes_in_use"]))
    return max(found) if found else None


def start_trace(trace_dir: str) -> None:
    """Start the profiler for a short stretch: device planes and the
    program's TraceAnnotation spans, no Python call stacks, no HLO dump."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def enable_compile_cache() -> str:
    """The program's own cache rule (``$JAX_COMPILATION_CACHE_DIR`` or the
    fixed ``<checkout>/.jax_cache``), with the entry thresholds at zero so
    that every program of a cell, the sub-second ones too, is found again
    by the cell's next run."""
    import jax

    from distributed_tpu.utils import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CacheCounter:
    """Counts persistent-compile-cache lookups and hits (jax.monitoring).
    Copied from ``chip_smoke.py`` (PR 21)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits

    def since(self, snap) -> dict:
        return {"lookups": self.requests - snap[0],
                "hits": self.hits - snap[1]}


def peaks_for(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    table = load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchmarkError(
            f"no peaks for device kind {device_kind!r} in benchmarks/"
            f"peaks.json (have {sorted(table['devices'])}): add it with its "
            "source rather than guess")
    return table["devices"][device_kind]


# ------------------------------------------------------------ arithmetic --
def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (0-100) of ``values``."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise BenchmarkError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


# ----------------------------------------------------------- environment --
@dataclasses.dataclass
class Env:
    """What a driver is given."""
    manifest: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    t_start: float                 # process start on time.perf_counter()
    device: dict
    cache: CacheCounter
    trace_dir: str
    family: Any                    # <path>/families/<family>.py
    reference: Any                 # <path>/reference/<family>.py

    def log(self, **fields) -> None:
        """One JSON line on stderr: what the run did, for whoever reads
        the log. Never on stdout, whose last line is the result."""
        print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader is given."""
    trace: Any                     # benchmarks.trace.Trace, or None
    telemetry: Dict[str, Any]      # what the driver gathered from the program
    config: dict
    traffic: dict
    cell: dict
    peaks: Optional[dict]
    values: Dict[str, float]       # per-layer metrics already read


def result_line(*, correct, attempted, failed, metrics, device,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)
