"""Multi-host runtime bootstrap.

Replaces the reference's cluster handshake — TF_CONFIG parsed at strategy
construction, per-worker gRPC servers, blocking collective handshake at first
fit() (/root/reference/README.md:395-399) — with ``jax.distributed``: every
host runs the same SPMD program, process 0 hosts the coordinator service, and
all collectives are XLA-compiled over ICI/DCN (no gRPC worker in the loop).

``initialize()`` is idempotent and resolution-ordered (explicit spec >
DTPU_CONFIG/TF_CONFIG env > TPU runtime auto-detect > single-process no-op),
mirroring the reference's config-by-environment contract (SURVEY.md §1).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from ..utils import logging as dlog
from . import config as config_lib

_initialized = False
_gathered_cache = None  # explicit-coordinator spec, cached after the gather

# Elastic world-size override, exported by a resizing Supervisor: the
# relaunched gang must form a clean N'-process runtime even when the
# inherited DTPU_CONFIG/TF_CONFIG still names the old N workers.
ELASTIC_WORLD_ENV = "DTPU_ELASTIC_WORLD"


def _enable_cpu_collectives():
    """Give a multi-process CPU gang a working collectives layer.

    XLA:CPU compiles cross-process computations only through a host
    collectives implementation (gloo); without one, the FIRST cross-process
    operation — even a replicated ``device_put`` onto a 2-process mesh —
    fails with "Multiprocess computations aren't implemented on the CPU
    backend". TPU/GPU backends bring their own collectives, so this flips
    the switch only when the platform is explicitly CPU (the CI sim and
    the launcher tests), and must run BEFORE the backend initializes —
    which holds here because initialize() is documented as
    before-any-device-computation. Best-effort: a jax build without the
    gloo option keeps its old behavior."""
    plats = (
        jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
    ).lower()
    if "cpu" not in [p.strip() for p in plats.split(",")]:
        return
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # unknown config / unsupported build
        pass


def _gathered_workers(coordinator: str, n: int, index: int) -> list:
    """Real rank-ordered worker list for an explicit-coordinator init: every
    process contributes its own address via a host-level allgather (must run
    on ALL processes — it is a collective). Rank 0's entry keeps the
    coordinator's service port; other ranks report port 0 (informational
    address — jax processes run no per-worker server, unlike the reference's
    per-worker gRPC endpoints, /root/reference/README.md:398)."""
    from . import net

    mine = coordinator if index == 0 else f"{net.my_ip()}:0"
    if n <= 1:
        return [mine]
    import numpy as np
    from jax.experimental import multihost_utils

    cap = 256
    raw = mine.encode()
    if len(raw) > cap:
        raise ValueError(
            f"worker address {mine!r} exceeds {cap} bytes"
        )
    buf = np.zeros(cap, np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, np.uint8)
    gathered = multihost_utils.process_allgather(buf)  # (P, cap)
    return [
        bytes(row).rstrip(b"\x00").decode(errors="replace")
        for row in np.asarray(gathered)
    ]


def _apply_elastic_world(
    spec: config_lib.ClusterSpec,
) -> config_lib.ClusterSpec:
    """Honor ``DTPU_ELASTIC_WORLD`` over an env-inherited spec: truncate
    the worker list to the elastic world's first N' entries (rank order is
    the supervisor's contract — surviving workers keep a dense rank
    prefix). A rank outside the new world must not join at all: raising
    here beats N' workers hanging at a collective waiting for a ghost.
    Growing past the inherited list is impossible from this side (the
    override carries no addresses) — the launcher regenerates the spec on
    a real grow, so warn and keep the spec."""
    raw = os.environ.get(ELASTIC_WORLD_ENV)
    if not raw:
        return spec
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"{ELASTIC_WORLD_ENV} must be an integer, got {raw!r}"
        )
    if n < 1:
        raise ValueError(f"{ELASTIC_WORLD_ENV} must be >= 1, got {n}")
    if n == spec.num_processes:
        return spec
    if n > spec.num_processes:
        dlog.warning(
            f"{ELASTIC_WORLD_ENV}={n} exceeds the inherited spec's "
            f"{spec.num_processes} workers; an elastic grow needs a "
            "regenerated spec (the override carries no addresses) — "
            "keeping the inherited spec"
        )
        return spec
    if spec.index >= n:
        raise ValueError(
            f"rank {spec.index} is outside the elastic world of {n} "
            f"(inherited spec had {spec.num_processes} workers); this "
            "process should not have been launched"
        )
    return config_lib.ClusterSpec(
        workers=list(spec.workers[:n]), index=spec.index
    ).validate()


def _tpu_pod_spec() -> Optional[config_lib.ClusterSpec]:
    """Spec from the TPU runtime's own pod metadata (GCE TPU-VM env),
    giving auto-detected clusters a real worker list too."""
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES")
    if not hosts:
        return None
    index = int(
        os.environ.get("TPU_WORKER_ID")
        or os.environ.get("CLOUD_TPU_TASK_ID")
        or 0
    )
    workers = [f"{h.strip()}:8476" for h in hosts.split(",") if h.strip()]
    try:
        return config_lib.ClusterSpec(workers=workers, index=index).validate()
    except ValueError:
        return None


def _should_auto_init() -> bool:
    """Pod auto-detect is the DEFAULT on TPU platforms: fire when the TPU
    runtime's pod-slice markers are present. DTPU_AUTO_INIT=1 forces it,
    DTPU_AUTO_INIT=0 opts out (SURVEY.md §7 item 3)."""
    gate = os.environ.get("DTPU_AUTO_INIT")
    if gate == "1":
        return True
    if gate == "0":
        return False
    # Multi-host markers only: a single-host slice (TPU_WORKER_HOSTNAMES
    # with one entry, e.g. "localhost") needs no jax.distributed at all.
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hosts.split(",") if h.strip()]) > 1:
        return True
    return bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def initialize(
    spec: Optional[config_lib.ClusterSpec] = None,
    *,
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> config_lib.ClusterSpec:
    """Join (or form) the cluster. Call once, before any device computation —
    the same ordering constraint the reference enforces by requiring a fresh
    session before setting TF_CONFIG (/root/reference/README.md:316-317).

    Resolution order: explicit coordinator args > explicit/env spec
    (DTPU_CONFIG/TF_CONFIG) > TPU pod auto-detect (default on pod slices) >
    single-process. Returns the resolved ClusterSpec with a REAL worker
    list in every path that can know one.
    """
    global _initialized, _gathered_cache
    if coordinator is not None:
        n = int(num_processes or 1)
        idx = int(process_id or 0)
        if _gathered_cache is not None:
            # Repeat call (e.g. two libraries both bootstrapping): the
            # gather below is a collective and would hang if peers don't
            # re-enter it; the first call's result answers this one.
            return _gathered_cache
        if n > 1 and not _initialized:
            _enable_cpu_collectives()
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=n,
                process_id=idx,
            )
            _initialized = True
        _gathered_cache = config_lib.ClusterSpec(
            workers=_gathered_workers(coordinator, n, idx), index=idx
        )
        return _gathered_cache

    explicit = spec is not None
    spec = config_lib.resolve(spec)
    if spec is not None:
        if not explicit:
            # Env-inherited specs can be stale across an elastic resize;
            # an explicitly passed spec is the caller's authority and is
            # never rewritten.
            spec = _apply_elastic_world(spec)
        # An explicit/env spec always wins — including a single-process one
        # (debugging one worker on a pod VM must not be hijacked by
        # auto-detect).
        if spec.num_processes > 1 and not _initialized:
            _enable_cpu_collectives()
            jax.distributed.initialize(
                coordinator_address=spec.coordinator,
                num_processes=spec.num_processes,
                process_id=spec.index,
            )
            _initialized = True
            if spec.is_chief:
                dlog.info(
                    f"cluster up: {spec.num_processes} processes, "
                    f"coordinator {spec.coordinator}, "
                    f"{jax.device_count()} devices total"
                )
        return spec
    # Auto-detect path (only when nothing explicit resolved): on a TPU pod
    # slice each host sees its local chips and jax.distributed.initialize()
    # with no args uses the TPU metadata. This is the documented default
    # when pod markers are present; DTPU_AUTO_INIT=0 opts out.
    if _should_auto_init() and not _initialized:
        # Pod markers say this host is one of several: failing to join is
        # an error (initialize() must run before any backend use), never a
        # quiet single-process run on a fraction of the slice.
        jax.distributed.initialize()
        _initialized = True
    if jax.process_count() > 1:
        # Multi-process for real — whether our auto-init did it or the user
        # called jax.distributed.initialize() themselves. The returned spec
        # must agree with the actual runtime: only adopt the pod metadata's
        # worker list when it matches what jax.distributed really formed.
        # Conversely, when auto-init was opted out (DTPU_AUTO_INIT=0) or
        # failed and the runtime stayed single-process, pod env markers may
        # still be present — returning them would disable chief-gating on a
        # process that is in fact the only one (the single-process fall-
        # through below handles that case).
        pod = _tpu_pod_spec()
        if (
            pod is not None
            and pod.num_processes == jax.process_count()
            and pod.index == jax.process_index()
        ):
            return pod
        # Joined a real cluster but the runtime exposes no (consistent)
        # host list: still return truthful rank/size so chief-gating
        # works; addresses are unknowable here.
        return config_lib.ClusterSpec(
            workers=[f"unknown:{i}" for i in range(jax.process_count())],
            index=jax.process_index(),
        )
    return config_lib.ClusterSpec(workers=["localhost:0"], index=0)


def is_initialized() -> bool:
    return _initialized


def reset_for_relaunch() -> None:
    """Clear the module's memo state (``_initialized`` guard and the
    explicit-coordinator spec cache) so a re-formed — possibly resized —
    gang can ``initialize()`` cleanly in the same process. Without this an
    in-process relaunch silently reuses the stale cached spec: the old
    world size, the old coordinator, the old rank.

    This clears bookkeeping only; it does NOT tear down a live
    ``jax.distributed`` runtime — use :func:`shutdown` when this process
    actually joined one. (Single-process test gangs and the
    explicit-coordinator n=1 path never start the runtime, so for them
    this is the complete reset.)"""
    global _initialized, _gathered_cache
    _initialized = False
    _gathered_cache = None


def shutdown() -> None:
    """Leave the cluster: tear down ``jax.distributed`` (when this process
    initialized it) and clear the memo state, making ``initialize()``
    re-formable at a new world size. Best-effort on the runtime teardown —
    a coordinator that already died must not turn a relaunch into a crash."""
    global _initialized
    if _initialized:
        try:
            jax.distributed.shutdown()
        except Exception as e:  # dead coordinator / already torn down
            dlog.warning(f"jax.distributed shutdown failed (ignored): {e}")
    reset_for_relaunch()


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_chief() -> bool:
    return jax.process_index() == 0


def barrier(name: str = "barrier", timeout_s: int = 600):
    """Host-level sync point (the reference gets this implicitly from its
    first collective, README.md:399; we expose it explicitly)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
