"""Device-mesh construction.

The mesh is the framework's single source of truth for parallelism: every
strategy (DP today; TP/PP/SP/EP compose later) is an axis of one
``jax.sharding.Mesh``. This replaces the reference's flat worker list in
``TF_CONFIG`` (/root/reference/README.md:84-89, 322-327): where the reference
enumerates gRPC endpoints, we enumerate chips and name axes, and XLA emits the
collectives over ICI/DCN.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names, in fixed order. DP is one axis of a general design so
# the others compose later without re-plumbing (SURVEY.md §2c implication).
AXES = ("data", "fsdp", "pipe", "seq", "expert", "model")


def _slice_ids_of(devices) -> list:
    """Per-device slice index (0 everywhere on single-slice systems)."""
    out = []
    for d in devices:
        sid = getattr(d, "slice_index", None)
        out.append(0 if sid is None else int(sid))
    return out


def _hybrid_device_array(devices, names, sizes, dcn_axis, slice_ids):
    """Arrange a multi-slice device set so ``dcn_axis`` is slice-major:
    each slice contributes a contiguous block of that axis, and every
    other axis stays within one slice. Collectives over non-dcn axes then
    ride ICI; only the dcn axis crosses the data-center network — the
    standard hybrid recipe (data over DCN, model/fsdp within a slice)."""
    groups: Dict[int, list] = {}
    for d, s in zip(devices, slice_ids):
        groups.setdefault(s, []).append(d)
    n_slices = len(groups)
    dcn_i = names.index(dcn_axis)
    if sizes[dcn_i] % n_slices:
        raise ValueError(
            f"dcn axis {dcn_axis!r} size {sizes[dcn_i]} not divisible by "
            f"{n_slices} slices"
        )
    per = list(sizes)
    per[dcn_i] = sizes[dcn_i] // n_slices
    per_count = int(np.prod(per))
    subs = []
    for s in sorted(groups):
        devs = groups[s]
        if len(devs) != per_count:
            raise ValueError(
                f"slice {s} has {len(devs)} devices; the hybrid mesh "
                f"needs {per_count} per slice"
            )
        subs.append(np.array(devs, dtype=object).reshape(per))
    return np.concatenate(subs, axis=dcn_i)


def make_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    dcn_axis: Optional[str] = None,
    slice_ids: Optional[Sequence[int]] = None,
) -> Mesh:
    """Build a Mesh over `devices` (default: all) with named axes.

    ``axis_sizes`` maps axis name -> size; omitted axes get size 1 and are
    dropped unless explicitly given. With no arguments, all devices go on the
    'data' axis (pure DP — exactly the reference's MultiWorkerMirrored layout,
    /root/reference/README.md:122,364, re-expressed as a mesh).

    ``dcn_axis`` names the axis laid across TPU slices on a multi-slice
    (Megascale/DCN) system — typically 'data', so gradient all-reduce is
    the only cross-slice collective while model/fsdp/seq axes stay on ICI
    (BASELINE.json configs[4]'s multi-host shape). Ignored when every
    device reports the same slice. ``slice_ids`` overrides the per-device
    slice detection (tests use this to mock a 2-slice device set).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {"data": n}
    names = [a for a in AXES if a in axis_sizes]
    unknown = set(axis_sizes) - set(AXES)
    if unknown:
        raise ValueError(f"Unknown mesh axes {sorted(unknown)}; valid: {AXES}")
    sizes = [int(axis_sizes[a]) for a in names]
    total = int(np.prod(sizes))
    if total != n:
        raise ValueError(
            f"Mesh axes {dict(zip(names, sizes))} need {total} devices, got {n}"
        )
    ids = list(slice_ids) if slice_ids is not None else _slice_ids_of(devices)
    if len(ids) != n:
        raise ValueError(f"slice_ids has {len(ids)} entries for {n} devices")
    if dcn_axis is not None and len(set(ids)) > 1:
        if dcn_axis not in names:
            raise ValueError(
                f"dcn_axis {dcn_axis!r} not among mesh axes {names}"
            )
        if slice_ids is None:
            # Real multi-slice hardware: jax's hybrid topology helper
            # orders devices within each slice; its failure is an error,
            # not a reason to guess an ordering.
            dcn_shape = [1] * len(sizes)
            dcn_i = names.index(dcn_axis)
            n_slices = len(set(ids))
            per = list(sizes)
            per[dcn_i] = sizes[dcn_i] // n_slices
            dcn_shape[dcn_i] = n_slices
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per, dcn_shape, devices=devices
            )
            return Mesh(dev_array, axis_names=tuple(names))
        dev_array = _hybrid_device_array(devices, names, sizes, dcn_axis, ids)
        return Mesh(dev_array, axis_names=tuple(names))
    if len(devices) == jax.device_count():
        # The whole topology: the helper picks an ICI-friendly order on TPU
        # (a plain reshape elsewhere) and raises on a topology it cannot
        # lay out.
        dev_array = mesh_utils.create_device_mesh(sizes, devices=devices)
    else:
        # A caller-chosen subset (planner measurements, tests) is not a
        # topology the helper knows; it keeps the order it was given.
        dev_array = np.array(devices).reshape(sizes)
    return Mesh(dev_array, axis_names=tuple(names))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim across `axis`."""
    return NamedSharding(mesh, PartitionSpec(axis))
