"""Sharded (per-process) checkpointing for models larger than one host.

The npz ``Checkpointer`` gathers every leaf to full size on the host
(fine at the reference's 347k-param scale, /root/reference/README.md:236-247,
wrong for the FSDP-scale models this framework trains): per-host RAM is
O(total params) and one process writes everything. This module is the
scale-out design:

- **Save**: every process writes exactly the shard blocks it owns (its
  addressable shards with ``replica_id == 0``, so each unique block of the
  global array is written once cluster-wide) into its own
  ``proc-<i>.npz``. No host ever materializes a full leaf. Every block
  carries a CRC32 (the ``data/records.py`` corruption-is-loud idiom), so a
  torn or bit-flipped block is caught at read time and named precisely,
  never deserialized into garbage optimizer state.
- **Commit**: ``manifest.json`` is written by the chief *after* a cross-host
  barrier, so a checkpoint directory without a manifest is an aborted save
  and is ignored by ``all_steps()``. ``async_save=True`` moves the
  fetch+serialize half of the save onto a background writer
  ("dtpu-shard-writer") and DEFERS the barrier+commit to the next
  main-thread touchpoint (the following ``save()`` or an explicit
  ``wait()``), where collectives are safe — the cross-host barrier never
  runs concurrently with training collectives.
- **Restore**: arrays are rebuilt with ``jax.make_array_from_callback``
  under the *current* model's shardings; the callback reads only the saved
  blocks overlapping each requested shard. Because blocks carry explicit
  start offsets, the restoring mesh may have a different shape or axis
  layout than the saving one (resharding happens block-by-block on read).
  This covers the STRATEGY as well as the mesh: optimizer state saved
  from a ZeRO-1/FSDP run (data-sharded moments next to replicated
  ``inject_hyperparams`` scalars) restores into whatever the live
  strategy's ``init_opt_state`` template dictates — ZeRO-1 -> FSDP, FSDP
  -> replicated, any direction (tests/test_zero.py). A corrupt block in
  the newest step raises :class:`ShardCorruptionError` (block-addressed);
  auto-restore (``step=None``) skips that step and falls back to the
  previous retained one, while an explicitly requested step never
  silently substitutes.

The block machinery (`extract_blocks` / `restore_from_index` / the
overlap-reassembly reader) is deliberately reusable: the diskless buddy
redundancy tier (``resilience/redundancy.py``) encodes its in-memory
mirrors in exactly this layout, so a mirror restores through the same
code path a disk checkpoint does — only the medium differs. ``read_stats``
counts every block this module reads FROM DISK, which is how the recovery
tests assert the buddy path's zero-disk-reads claim.

Restore assumes the checkpoint directory is visible to every process
(shared filesystem / object store) — the standard deployment for sharded
formats; the single-writer npz/HDF5 paths remain for non-shared setups and
interchange.

Layout::

    dir/ckpt-<step>/
        manifest.json   # step, seed, input_shape, leaf shapes/dtypes, nprocs
        proc-0.npz      # this process's blocks: "<leaf-path>@<starts>" -> data
        proc-1.npz      # (+ "__crc__": JSON {block key -> crc32})
        ...
"""

from __future__ import annotations

import json
import re
import threading
import zlib
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from .core import (
    _ASYNC_CHECKPOINTERS,
    _atomic_write,
    _data_state_of,
    _device_snapshot,
    _is_chief,
    iter_leaf_paths as _iter_leaf_paths,
)

__all__ = ["ShardedCheckpointer", "ShardCorruptionError", "read_stats"]

# Disk-read accounting for the recovery tiers: every block read from a
# proc-*.npz lands here. The buddy-redundancy tests snapshot these
# counters around a restore to PROVE that a
# buddy-tier recovery touched zero disk blocks (docs/RESILIENCE.md
# "Recovery tiers").
read_stats = {"block_reads": 0, "block_bytes": 0}

CRC_KEY = "__crc__"


class ShardCorruptionError(RuntimeError):
    """A sharded-checkpoint block failed validation (CRC mismatch, torn
    file, garbage where an npz should be). Carries the offending file and
    block key so the error names exactly what is damaged instead of
    surfacing as a generic deserialization failure deep in restore."""

    def __init__(self, message: str, *, path=None, key: Optional[str] = None):
        super().__init__(message)
        self.path = str(path) if path is not None else None
        self.key = key


def _starts_of(index, shape) -> Tuple[int, ...]:
    """Concrete start offsets of a shard's index (slices may have None)."""
    starts = []
    for sl, _dim in zip(index, shape):
        starts.append(0 if sl.start is None else int(sl.start))
    return tuple(starts)


def _block_key(path: str, starts: Tuple[int, ...], shape: Tuple[int, ...]) -> str:
    # Start offsets AND block shape live in the key so restore can decide
    # overlap without reading any data.
    return (
        f"{path}@{','.join(map(str, starts))}@{','.join(map(str, shape))}"
    )


_KEY_RE = re.compile(r"^(?P<path>.*)@(?P<starts>[\d,]*)@(?P<shape>[\d,]*)$")


def _parse_key(key: str) -> Tuple[str, Tuple[int, ...], Tuple[int, ...]]:
    m = _KEY_RE.match(key)
    if not m:
        raise ValueError(f"malformed shard block key: {key!r}")

    def ints(s):
        return tuple(int(v) for v in s.split(",")) if s else ()

    return m.group("path"), ints(m.group("starts")), ints(m.group("shape"))


def block_crc(data: np.ndarray) -> int:
    """CRC32 of a block's raw bytes — the same integrity idiom as
    ``data/records.py`` record framing, applied per checkpoint block."""
    return zlib.crc32(np.ascontiguousarray(data).tobytes()) & 0xFFFFFFFF


def extract_blocks(tree, proc: int) -> Tuple[Dict[str, np.ndarray],
                                             Dict[str, dict], int]:
    """This process's owned shard blocks of a pytree, in the canonical
    block-key encoding: ``(blocks, leaves_meta, max_block_bytes)``.

    A ``jax.Array`` leaf contributes its addressable shards with
    ``replica_id == 0`` (each unique block written once cluster-wide);
    host-side leaves are replicated by construction, so the chief
    contributes them as one full block. ``leaves_meta`` records every
    leaf's GLOBAL shape/dtype regardless of who owns its blocks — it is
    identical on all processes and becomes the manifest. Shared by
    ``ShardedCheckpointer.save`` and the buddy-redundancy mirror encoding
    (``resilience/redundancy.py``)."""
    blocks: Dict[str, np.ndarray] = {}
    leaves_meta: Dict[str, dict] = {}
    max_block = 0
    for path, leaf in _iter_leaf_paths(tree):
        if isinstance(leaf, jax.Array):
            shape, dtype = tuple(leaf.shape), np.dtype(leaf.dtype)
            for shard in leaf.addressable_shards:
                if shard.replica_id != 0:
                    continue  # an identical copy is written elsewhere
                data = np.asarray(shard.data)
                max_block = max(max_block, data.nbytes)
                starts = _starts_of(shard.index, shape)
                blocks[_block_key(path, starts, data.shape)] = data
        else:
            # Host-side leaf (plain numpy/python scalar): replicated by
            # construction, chief writes it as one full block.
            data = np.asarray(leaf)
            shape, dtype = tuple(data.shape), data.dtype
            if proc == 0:
                max_block = max(max_block, data.nbytes)
                blocks[_block_key(path, (0,) * data.ndim, data.shape)] = data
        leaves_meta[path] = {
            "shape": list(shape),
            "dtype": dtype.name,
        }
    return blocks, leaves_meta, max_block


def _write_proc_npz(path: Path, blocks: Dict[str, np.ndarray]) -> None:
    """Atomic write of one process's block file, CRC map included."""
    crcs = {k: block_crc(v) for k, v in blocks.items()}
    payload = dict(blocks)
    payload[CRC_KEY] = np.frombuffer(
        json.dumps(crcs).encode(), dtype=np.uint8
    ).copy()
    _atomic_write(path, lambda tmp: np.savez(open(tmp, "wb"), **payload))


class _BlockIndex:
    """All saved blocks of one checkpoint: (leaf path) -> [(starts, file,
    key)], with lazily-opened npz handles so restore reads only the blocks
    it needs. Block reads are CRC-validated when the file carries a CRC
    map (older checkpoints without one load unvalidated) and counted into
    ``read_stats`` — this is the DISK reader; the buddy tier supplies its
    own in-memory index with the same two-method surface."""

    def __init__(self, step_dir: Path, nprocs: int):
        self._files = [step_dir / f"proc-{i}.npz" for i in range(nprocs)]
        self._handles: Dict[int, Any] = {}
        self._crcs: Dict[int, Optional[dict]] = {}
        self.blocks: Dict[str, list] = {}
        for fi, f in enumerate(self._files):
            if not f.exists():
                raise FileNotFoundError(
                    f"checkpoint shard file missing: {f} (manifest promises "
                    f"{nprocs} processes — is the directory shared?)"
                )
            try:
                with np.load(f, allow_pickle=False) as z:
                    names = list(z.files)
            except Exception as e:
                # Garbage where a zip should be (torn write, clobbered
                # file): name the file, let auto-restore fall back.
                raise ShardCorruptionError(
                    f"checkpoint shard file {f} is unreadable "
                    f"({type(e).__name__}: {e})", path=f,
                ) from e
            for key in names:
                if key == CRC_KEY:
                    continue
                path, starts, shape = _parse_key(key)
                self.blocks.setdefault(path, []).append(
                    (starts, shape, fi, key)
                )

    def _handle(self, fi: int):
        h = self._handles.get(fi)
        if h is None:
            try:
                h = np.load(self._files[fi], allow_pickle=False)
            except Exception as e:
                raise ShardCorruptionError(
                    f"checkpoint shard file {self._files[fi]} is unreadable "
                    f"({type(e).__name__}: {e})", path=self._files[fi],
                ) from e
            self._handles[fi] = h
            crcs = None
            if CRC_KEY in h.files:
                try:
                    crcs = json.loads(bytes(h[CRC_KEY]).decode())
                except Exception as e:
                    raise ShardCorruptionError(
                        f"CRC map of {self._files[fi]} is unreadable "
                        f"({type(e).__name__}: {e})", path=self._files[fi],
                    ) from e
            self._crcs[fi] = crcs
        return h

    def read(self, fi: int, key: str) -> np.ndarray:
        h = self._handle(fi)
        try:
            data = h[key]
        except Exception as e:
            raise ShardCorruptionError(
                f"block {key!r} of {self._files[fi]} failed to load "
                f"({type(e).__name__}: {e})",
                path=self._files[fi], key=key,
            ) from e
        crcs = self._crcs.get(fi)
        if crcs is not None:
            want = crcs.get(key)
            if want is not None and block_crc(data) != int(want):
                raise ShardCorruptionError(
                    f"CRC mismatch for block {key!r} in {self._files[fi]}: "
                    f"stored {int(want)}, computed {block_crc(data)} — the "
                    "block is corrupt on disk",
                    path=self._files[fi], key=key,
                )
        read_stats["block_reads"] += 1
        read_stats["block_bytes"] += int(data.nbytes)
        return data

    def close(self):
        for h in self._handles.values():
            h.close()
        self._handles.clear()


def restore_from_index(model, index, manifest: dict) -> Tuple[int, int]:
    """Rebuild params/state/opt_state onto ``model`` from a block index.

    ``index`` needs only ``blocks`` ({leaf path -> [(starts, shape,
    handle, key)]}) and ``read(handle, key) -> np.ndarray`` — the disk
    ``_BlockIndex`` and the buddy tier's in-memory mirror index both
    satisfy it, so a RAM restore is byte-for-byte the same reassembly as
    a disk one. ``manifest`` carries step/seed/input_shape/leaves (+
    optional data_state). Returns ``(step, max_block_bytes)``."""
    step = int(manifest["step"])
    if not model.built:
        model.build(manifest["input_shape"], seed=manifest.get("seed", 0))

    leaves_meta = manifest["leaves"]
    max_block = 0
    # Templates define structure AND target shardings. opt_state uses the
    # strategy's eager init so restored optimizer state keeps the same
    # placement as a fresh compile.
    templates = {
        "params": model.params,
        "state": model.state if model.state else {},
    }
    has_opt = any(p.startswith("opt_state") for p in leaves_meta)
    if model.compiled and has_opt:
        templates["opt_state"] = model.strategy.init_opt_state(
            model.tx, model.params
        )
    # Saved-before-compile checkpoints have no opt leaves: keep the
    # model's fresh optimizer init (same contract as Checkpointer).

    def rebuild(path, template_leaf):
        nonlocal max_block
        meta = leaves_meta.get(path)
        if meta is None:
            raise KeyError(
                f"checkpoint step {step} has no leaf {path!r} — "
                "wrong model or optimizer for this checkpoint"
            )
        shape = tuple(meta["shape"])
        dtype = np.dtype(meta["dtype"])
        t_shape = tuple(np.shape(template_leaf))
        if t_shape != shape:
            raise ValueError(
                f"checkpoint leaf {path!r} has global shape {shape} "
                f"but the model expects {t_shape}"
                " — wrong model for this checkpoint"
            )
        saved = index.blocks.get(path, [])
        if not saved:
            raise KeyError(
                f"no saved blocks for leaf {path!r} in step {step}"
            )
        cache: Dict[Tuple[Any, str], np.ndarray] = {}

        def read_block(fi, key):
            got = cache.get((fi, key))
            if got is None:
                got = index.read(fi, key)
                cache[(fi, key)] = got
            return got

        def cb(req_index):
            nonlocal max_block
            req = [
                (0 if sl.start is None else int(sl.start),
                 dim if sl.stop is None else int(sl.stop))
                for sl, dim in zip(req_index, shape)
            ]
            out = np.empty(
                tuple(hi - lo for lo, hi in req), dtype
            )
            filled = 0
            for starts, bshape, fi, key in saved:
                # Overlap of [bstart, bstop) with [lo, hi) per dim —
                # decided from the key alone; only overlapping
                # blocks are read from the medium.
                dst = []
                ok = True
                for d, (lo, hi) in enumerate(req):
                    bstart = starts[d] if d < len(starts) else 0
                    bstop = bstart + bshape[d]
                    s, e = max(bstart, lo), min(bstop, hi)
                    if s >= e:
                        ok = False
                        break
                    dst.append((s - lo, e - lo, s - bstart, e - bstart))
                if not ok:
                    continue
                block = read_block(fi, key)
                if tuple(block.shape) != tuple(bshape):
                    # np.load(mmap_mode=...) surfaces 0-d blocks as (1,);
                    # the key records the true shape — restore it (a view,
                    # no copy).
                    block = block.reshape(bshape)
                max_block = max(max_block, block.nbytes)
                out_sel = tuple(slice(a, b) for a, b, _, _ in dst)
                blk_sel = tuple(slice(c, d) for _, _, c, d in dst)
                out[out_sel] = block[blk_sel]
                filled += int(np.prod(out[out_sel].shape))
            if filled < int(np.prod(out.shape)):
                raise ValueError(
                    f"saved blocks for {path!r} do not cover the "
                    f"requested shard {req} (filled {filled} of "
                    f"{int(np.prod(out.shape))} elements)"
                )
            return out

        if isinstance(template_leaf, jax.Array):
            return jax.make_array_from_callback(
                shape, template_leaf.sharding, cb
            )
        full = cb(tuple(slice(0, d) for d in shape))
        return np.asarray(full, dtype)

    restored = {}
    for section, template in templates.items():
        paths, leaves = [], []
        for path, leaf in _iter_leaf_paths({section: template}):
            paths.append(path)
            leaves.append(leaf)
        new_leaves = [rebuild(p, l) for p, l in zip(paths, leaves)]
        treedef = jax.tree_util.tree_structure(template)
        restored[section] = jax.tree_util.tree_unflatten(
            treedef, new_leaves
        )

    model.params = restored["params"]
    if restored.get("state") is not None and model.state:
        model.state = restored["state"]
    if model.compiled and "opt_state" in restored:
        model.opt_state = restored["opt_state"]
    model.step = step
    model._seed = int(manifest.get("seed", model._seed))
    # fit() restores the data source from this via load_state() (the
    # state records the GLOBAL stream cursor, so it composes with
    # reshard("auto") after an elastic resize).
    model._restored_data_state = manifest.get("data_state")
    return step, max_block


class ShardedCheckpointer:
    """Per-process sharded checkpoints with mesh-shape-independent restore.

    Drop-in sibling of ``Checkpointer`` (same ``save(model)`` /
    ``restore_into(model)`` / ``all_steps`` surface), but save cost and
    host memory are O(addressable shards), not O(total params).

    ``async_save=True`` moves the device->host shard fetch, CRC, and npz
    serialization onto a background "dtpu-shard-writer" thread after a
    cheap donation-safe on-device snapshot. The cross-host commit (barrier
    + chief manifest) is DEFERRED to the next main-thread touchpoint — the
    following ``save()``, an explicit ``wait()``, or ``restore_into`` —
    so no collective ever runs on the writer thread concurrently with
    training collectives (the constraint that used to forbid async sharded
    saves outright). Until that commit the step directory has no manifest
    and is invisible to ``all_steps()``: interrupted async saves are
    aborted saves, exactly like a mid-write crash. On multi-process gangs
    the commit first allgathers per-process writer outcomes, so one
    process's failed write aborts the commit everywhere instead of
    publishing a checkpoint with a missing shard — the writer's exception
    re-raises on its own process at ``wait()``.
    """

    def __init__(self, directory, keep: int = 3, async_save: bool = False):
        self.directory = Path(directory)
        self.keep = int(keep)
        self.async_save = bool(async_save)
        # Diagnostics for tests/ops: the largest single host block touched
        # by the most recent save/restore (must stay << full leaf size for
        # sharded leaves — the whole point of the format).
        self.last_max_block_bytes = 0
        self._writer: Optional[threading.Thread] = None
        self._writer_error: Optional[BaseException] = None
        self._writer_lock = threading.Lock()
        self._pending: Optional[dict] = None  # manifest awaiting commit

    def wait(self) -> None:
        """Barrier: join any in-flight background shard write, then run the
        deferred cross-host commit (collective-safe: always the calling
        thread). Re-raises the writer's exception if it failed — the
        pending step is then abandoned, never committed. No-op for
        synchronous checkpointers, so generic callers (ModelCheckpoint
        train-end, the preemption flush) can call it unconditionally."""
        with self._writer_lock:
            writer, self._writer = self._writer, None
        if writer is not None:
            writer.join()
        err, self._writer_error = self._writer_error, None
        self._finalize_pending(failed=err is not None)
        if err is not None:
            raise err

    # ------------------------------------------------------------- layout --
    def _step_dir(self, step: int) -> Path:
        return self.directory / f"ckpt-{step}"

    def all_steps(self):
        if not self.directory.is_dir():
            return []
        steps = []
        for p in self.directory.glob("ckpt-*"):
            m = re.fullmatch(r"ckpt-(\d+)", p.name)
            # manifest.json is the commit marker: a dir without it is an
            # aborted save.
            if m and (p / "manifest.json").exists():
                steps.append(int(m.group(1)))
        return sorted(steps)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # --------------------------------------------------------------- save --
    def save(self, model, step: Optional[int] = None) -> Path:
        # Serialize the step family: an older in-flight write must land —
        # and its deferred commit run — before a newer save may start.
        self.wait()
        step = model.step if step is None else step
        tree = {
            "params": model.params,
            "state": model.state if model.state else {},
            "opt_state": model.opt_state,
        }
        step_dir = self._step_dir(int(step))
        step_dir.mkdir(parents=True, exist_ok=True)
        proc = jax.process_index()

        manifest = {
            "step": int(step),
            "seed": int(model._seed),
            "input_shape": list(model.input_shape or ()),
            "nprocs": jax.process_count(),
        }
        # Iterator cursor of the active fit source (data.Pipeline
        # state_dict), aligned to the trained step — captured NOW, on the
        # caller's thread, even for async saves (the source advances while
        # the writer runs). The manifest is read by EVERY process at
        # restore (shared directory), so unlike Checkpointer's chief-only
        # meta it resumes streaming input on whole gangs, including
        # resized (elastic) ones.
        dstate = _data_state_of(model, int(step))
        if dstate is not None:
            manifest["data_state"] = dstate

        if self.async_save:
            # Donation-safe on-device snapshot on the caller's thread
            # (ordered before any later donating dispatch); the writer
            # fetches shards from the snapshot at leisure. Extraction
            # touches only addressable shards — no collective.
            snap = _device_snapshot(tree)

            def write():
                try:
                    blocks, leaves_meta, max_block = extract_blocks(
                        snap, proc
                    )
                    _write_proc_npz(step_dir / f"proc-{proc}.npz", blocks)
                    self.last_max_block_bytes = max_block
                    manifest["leaves"] = leaves_meta
                except BaseException as e:  # surfaced at the next save/wait
                    self._writer_error = e

            self._pending = manifest
            writer = threading.Thread(
                target=write, name="dtpu-shard-writer", daemon=True
            )
            with self._writer_lock:
                self._writer = writer
            # Same global-flush contract as Checkpointer: the preemption
            # path's wait_all_async() joins this writer AND runs the
            # deferred commit before the final save (every rank takes the
            # preemption boundary together, so the commit's collective
            # stays lockstep).
            _ASYNC_CHECKPOINTERS.add(self)
            writer.start()
            return step_dir

        blocks, leaves_meta, max_block = extract_blocks(tree, proc)
        self.last_max_block_bytes = max_block
        _write_proc_npz(step_dir / f"proc-{proc}.npz", blocks)
        manifest["leaves"] = leaves_meta
        self._pending = manifest
        self._finalize_pending(failed=False)
        return step_dir

    def _finalize_pending(self, *, failed: bool) -> None:
        """The deferred commit: cross-host agreement that every process's
        shard write landed, then the chief publishes the manifest (the
        commit marker) and gc's old steps. Always runs on the calling
        thread — save()/wait()/restore_into() are executed in lockstep by
        every process of a gang, so the collective aligns."""
        pending, self._pending = self._pending, None
        if pending is None:
            return
        any_failed = failed
        if jax.process_count() > 1:
            # One collective doubles as the write barrier AND the outcome
            # agreement: a failed writer on ANY process aborts the commit
            # on ALL of them (a manifest must never promise a shard file
            # that was not fully written).
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                np.array([1 if failed else 0], np.int32)
            )
            any_failed = bool(np.asarray(flags).sum() > 0)
        if any_failed:
            return
        if _is_chief():
            _atomic_write(
                self._step_dir(int(pending["step"])) / "manifest.json",
                lambda tmp: Path(tmp).write_text(json.dumps(pending)),
            )
            self._gc()

    def _gc(self):
        import shutil

        steps = self.all_steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # ------------------------------------------------------------ restore --
    def _agreed_step(self, excluded) -> Optional[int]:
        """The newest committed step not yet ruled out, agreed gang-wide:
        the chief's view of the (shared) directory decides — filesystem
        visibility can lag on some hosts, and a per-process scan could
        silently desynchronize the gang onto different steps."""
        cands = [s for s in self.all_steps() if s not in excluded]
        step = cands[-1] if cands else None
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            chosen = np.array([-1 if step is None else int(step)], np.int64)
            step = int(multihost_utils.broadcast_one_to_all(chosen)[0])
            if step < 0:
                step = None
        return step

    def restore_into(self, model, step: Optional[int] = None) -> int:
        """Restore under the model's *current* strategy/mesh.

        Unlike ``Checkpointer._restore_multihost`` there is no broadcast:
        every process reads the (shared) directory itself and builds only
        its addressable shards. Host memory is therefore O(the target
        sharding's addressable shard sizes) — for a sharded target (FSDP/
        TP) no host ever assembles a full leaf; restoring into a
        *replicated* target necessarily assembles full leaves per host,
        exactly matching what that target keeps in device memory anyway.

        Auto-restore (``step=None``) survives corruption: a step whose
        blocks fail CRC (or whose shard files are torn) is skipped with a
        ``corrupt_checkpoint_skipped`` event and the scan falls back to
        the previous retained step — corruption costs one checkpoint
        interval, not the run. An EXPLICIT step propagates the
        block-addressed :class:`ShardCorruptionError` instead: silent
        substitution would hide the damage from a caller who named the
        step. (All processes of a gang read the same shared files, so a
        corruption-driven fallback is deterministic gang-wide.)
        """
        self.wait()  # flush + commit any pending async save first
        if step is not None:
            return self._restore_step(model, int(step))
        from ..utils import event_schema as evs
        from ..utils import events as events_lib
        from ..utils import logging as dlog

        excluded: set = set()
        while True:
            cand = self._agreed_step(excluded)
            if cand is None:
                raise FileNotFoundError(
                    f"No sharded checkpoints in {self.directory}"
                    + (f" ({len(excluded)} step(s) present but corrupt)"
                       if excluded else "")
                )
            try:
                return self._restore_step(model, cand)
            except ShardCorruptionError as e:
                dlog.warning(
                    f"ShardedCheckpointer: skipping corrupt step {cand} "
                    f"({e}); falling back to the previous retained step"
                )
                events_lib.emit(
                    evs.CORRUPT_CHECKPOINT_SKIPPED, step=int(cand),
                    path=e.path or str(self._step_dir(cand)), error=str(e),
                )
                excluded.add(cand)

    def _restore_step(self, model, step: int) -> int:
        step_dir = self._step_dir(step)
        try:
            manifest = json.loads((step_dir / "manifest.json").read_text())
        except FileNotFoundError:
            raise
        except (OSError, ValueError) as e:
            raise ShardCorruptionError(
                f"manifest of {step_dir} is unreadable "
                f"({type(e).__name__}: {e})", path=step_dir / "manifest.json",
            ) from e
        index = _BlockIndex(step_dir, int(manifest["nprocs"]))
        try:
            got, max_block = restore_from_index(model, index, manifest)
        finally:
            index.close()
        self.last_max_block_bytes = max_block
        return got
