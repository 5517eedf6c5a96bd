"""Input pipeline: prefetched shuffle/gather/decode/normalize batches.

Two implementations behind one API:

- **Native (C++)**: ``native/pipeline.cc`` compiled to a shared library and
  driven via ctypes. Worker threads keep a bounded ring of ready float32
  batches ahead of the consumer, overlapping host batch prep with device
  execution — the host-side analogue of the native machinery the reference
  gets from TF's C++ core (SURVEY.md §2b), which is what keeps a TPU fed at
  ImageNet scale.
- **Pure Python fallback**: same semantics (per-pass reshuffle, steps-per-
  pass, /255 normalization), used when no C++ toolchain is available.

Batch streams are deterministic in (seed, pass, step) ACROSS
implementations: the per-pass permutation is computed once, in numpy
(``np.random.default_rng((seed, pass))``), and handed to the native
pipeline as an index buffer — native and Python emit bit-identical
streams. ``DTPU_NATIVE_LEGACY_SHUFFLE=1`` restores the pre-unification
native order (splitmix64 Fisher-Yates, computed in C++) for experiments
pinned to old artifacts.

Record sources (``data.RecordSource``) add a third stage: host-side
**decode** of variable-length encoded records, optionally fanned across a
bounded worker pool (``decode_workers=W``) with work assigned by step
index and reassembled in order — the batch stream is bit-identical for
any ``W`` (including ``W=0``, which decodes inline).
"""

from __future__ import annotations

import ctypes
import os
import queue
import subprocess
import threading
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

from ..utils import logging as dlog

_NATIVE_DIR = Path(__file__).parent / "native"
_LIB_NAME = "libdtpu_pipeline.so"

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _load_native() -> Optional[ctypes.CDLL]:
    """Load (building on demand) the native pipeline library; None if
    unavailable. Gated off entirely by DTPU_NO_NATIVE=1."""
    global _lib, _lib_tried
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        if os.environ.get("DTPU_NO_NATIVE") == "1":
            return None
        so = _NATIVE_DIR / _LIB_NAME
        src = _NATIVE_DIR / "pipeline.cc"
        try:
            if not so.exists() or (
                src.exists() and src.stat().st_mtime > so.stat().st_mtime
            ):
                # Inter-process file lock: gang workers on one host may all
                # hit the missing-.so case at once, and make writes the .so
                # in place — an unsynchronized peer could dlopen a half-
                # written file and silently fall back to the Python RNG,
                # diverging its data order from the rest of the gang.
                import fcntl

                lock_path = _NATIVE_DIR / ".build.lock"
                with open(lock_path, "w") as lock_f:
                    fcntl.flock(lock_f, fcntl.LOCK_EX)
                    try:
                        if not so.exists() or (
                            src.exists()
                            and src.stat().st_mtime > so.stat().st_mtime
                        ):
                            subprocess.run(
                                ["make", "-C", str(_NATIVE_DIR)],
                                check=True,
                                capture_output=True,
                                timeout=120,
                            )
                    finally:
                        fcntl.flock(lock_f, fcntl.LOCK_UN)
            lib = ctypes.CDLL(str(so))
        except (OSError, subprocess.SubprocessError) as e:
            dlog.warning(f"native pipeline unavailable ({e}); using Python")
            return None
        lib.dtpu_pipeline_create_spans.restype = ctypes.c_void_p
        lib.dtpu_pipeline_create_spans.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),  # xs (span base pointers)
            ctypes.POINTER(ctypes.c_int64),   # span_rows
            ctypes.c_int64,   # n_spans
            ctypes.c_void_p,  # y
            ctypes.c_int64,   # n
            ctypes.c_int64,   # row_elems
            ctypes.c_int64,   # batch
            ctypes.c_int,     # shuffle
            ctypes.c_uint64,  # seed
            ctypes.c_int,     # depth
            ctypes.c_int,     # threads
            ctypes.c_float,   # scale
            ctypes.c_int64,   # start_step
            ctypes.c_int64,   # shard_index
            ctypes.c_int64,   # shard_count
            ctypes.c_int,     # external_perms
        ]
        lib.dtpu_pipeline_next.restype = ctypes.c_int64
        lib.dtpu_pipeline_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.dtpu_pipeline_steps_per_pass.restype = ctypes.c_int64
        lib.dtpu_pipeline_steps_per_pass.argtypes = [ctypes.c_void_p]
        lib.dtpu_pipeline_supply_perm.restype = None
        lib.dtpu_pipeline_supply_perm.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.dtpu_pipeline_destroy.restype = None
        lib.dtpu_pipeline_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load_native() is not None


_POLL_S = 0.05  # decode worker/consumer wake-up period for stop checks


class _DecodePool:
    """Bounded, order-preserving parallel decode stage.

    Work items are whole batch steps — ``(step, indices)`` — decoded by
    ``fn(indices)`` on one of ``workers`` daemon threads and reassembled
    by step key, so the consumed stream is bit-identical for ANY worker
    count: assignment order and thread timing never reach the output
    (``fn`` must be pure). The submission side (the Pipeline) bounds
    outstanding work, so results held here are bounded too.
    """

    def __init__(self, fn, workers: int):
        self._fn = fn
        self._tasks: "queue.Queue" = queue.Queue()
        self._results = {}
        self._cv = threading.Condition()
        self._error: Optional[BaseException] = None
        self._threads = [
            threading.Thread(
                target=self._run, name=f"dtpu-decode-{i}", daemon=True
            )
            for i in range(workers)
        ]
        for t in self._threads:
            t.start()

    def _run(self):
        while True:
            task = self._tasks.get()
            if task is None:  # poison pill from close()
                return
            step, idx = task
            try:
                out = self._fn(idx)
            except BaseException as e:  # surfaced to the consumer in get()
                with self._cv:
                    if self._error is None:
                        self._error = e
                    self._cv.notify_all()
                return
            with self._cv:
                self._results[step] = out
                self._cv.notify_all()

    def submit(self, step: int, idx: np.ndarray):
        self._tasks.put((int(step), idx))

    def get(self, step: int):
        """Block until step ``step``'s decode lands; re-raise any worker
        error with its original type."""
        with self._cv:
            while step not in self._results:
                if self._error is not None:
                    raise self._error
                self._cv.wait(timeout=_POLL_S)
            return self._results.pop(step)

    def close(self, join_timeout: float = 10.0):
        """Idempotent shutdown: drain pending tasks, poison every worker,
        join. Never raises — errors the consumer cares about surface in
        get()."""
        while True:  # unsubmitted work is abandoned, not decoded
            try:
                self._tasks.get_nowait()
            except queue.Empty:
                break
        for _ in self._threads:
            self._tasks.put(None)
        for t in self._threads:
            t.join(timeout=join_timeout)
        with self._cv:
            self._results.clear()


class Pipeline:
    """Iterator of ``(x_float32, y_int32)`` batches with background prefetch.

    Args:
      x: uint8 array (N, ...), a file-backed shard set (``FileSource`` or a
        directory path), or an indexed record store (``RecordSource``,
        whose pluggable ``decode_fn`` turns variable-length encoded
        records into fixed-shape rows).
      y: int labels (N,) or None.
      batch_size: rows per emitted batch.
      shuffle: reshuffle every pass (epoch) deterministically from ``seed``.
        The per-pass permutation is ONE numpy computation
        (``np.random.default_rng((seed, pass))``) shared by the native and
        Python implementations, so the stream is bit-identical across them
        (``DTPU_NATIVE_LEGACY_SHUFFLE=1`` restores the old C++ splitmix
        order).
      scale: multiplier applied during ->float32 conversion. Default
        (None): 1/255 — the reference's normalization,
        /root/reference/README.md:56 — for uint8 sources; 1.0 for record
        sources, whose ``decode_fn`` owns normalization.
      prefetch: ring depth — how many batches may be ready ahead.
      num_threads: native producer threads.
      use_native: force (True/False) or auto (None). Record sources always
        run the Python path (``decode_fn`` is Python).
      shard: optional ``(index, count)`` per-host input sharding: this
        pipeline prepares only rows ``[index * b/count, (index+1) * b/count)``
        of each global batch (``batch_size`` stays the GLOBAL batch). Every
        host runs the same (seed, pass, step) sequence, so the host slices
        assemble into exactly the batch an unsharded pipeline would emit —
        global-batch semantics unchanged, per-host memory and prep work
        divided by ``count`` (SURVEY.md §7 hard parts; contrast the
        reference's full-dataset-everywhere feeding,
        /root/reference/README.md:369-373). ``shard="auto"`` derives
        ``(jax.process_index(), jax.process_count())`` from the live
        runtime — the right spelling for elastic gangs, where the world
        size differs between relaunches (see :meth:`reshard`).
      decode_workers: record sources only — fan record decode across this
        many worker threads (0 decodes inline on the consumer thread).
        Work is assigned by step index and reassembled in order, so the
        batch stream is BIT-IDENTICAL for any worker count; workers give
        real speedup when ``decode_fn`` releases the GIL (zlib, PIL,
        numpy) or blocks on I/O (docs/API.md "Streaming input").
      decode_readahead: how many batch steps may be decoding (or decoded,
        unconsumed) ahead of the consumer. Default ``2 * decode_workers``.

    The stream is infinite (passes repeat, reshuffled); ``steps_per_pass``
    tells one epoch's length, matching ``fit(steps_per_epoch=...)``.
    :meth:`state_dict`/:meth:`load_state` capture and restore the iterator
    cursor for mid-epoch checkpoint resume (``Checkpointer`` records it
    automatically; see docs/API.md "Data").
    """

    def __init__(
        self,
        x,
        y: Optional[np.ndarray] = None,
        batch_size: int = 32,
        *,
        shuffle: bool = True,
        seed: int = 0,
        scale: Optional[float] = None,
        prefetch: int = 4,
        num_threads: int = 2,
        use_native: Optional[bool] = None,
        shard: Optional[Tuple[int, int]] = None,
        decode_workers: int = 0,
        decode_readahead: Optional[int] = None,
    ):
        from .filesource import FileSource
        from .records import RecordSource

        # Teardown-critical fields FIRST: __del__ runs on instances whose
        # __init__ raised partway (bad batch_size, a failed native handle),
        # and close() must find a consistent shape to tear down.
        self._lib = None
        self._handle = None
        self._closed = False
        self._py_step = 0
        self._decode_pool = None
        self.steps_emitted = 0  # lets fit() fast-forward on resume

        # x is an in-memory uint8 array, a file-backed shard set
        # (FileSource, or a directory path — streams through memory-mapped
        # spans, never loading the dataset into RAM), or a RecordSource of
        # variable-length encoded records (decoded on the host, optionally
        # in parallel).
        self._source: Optional[FileSource] = None
        self._records: Optional[RecordSource] = None
        self._decode_labels = False
        if isinstance(x, (str, os.PathLike)):
            x = FileSource(x)
        if isinstance(x, RecordSource):
            if x.decode_fn is None:
                raise ValueError(
                    "Pipeline needs a RecordSource with a decode_fn: "
                    "records are encoded bytes, and only the decoder "
                    "knows the row shape"
                )
            if use_native is True:
                raise ValueError(
                    "use_native=True is unavailable for record sources: "
                    "decode_fn runs in Python (decode parallelism comes "
                    "from decode_workers instead)"
                )
            self._records = x
            row_shape, self._decode_labels = x.probe()
            if self._decode_labels and y is not None:
                raise ValueError(
                    "labels come from decode_fn (it returns (row, label)); "
                    "do not also pass y"
                )
            n_rows = x.n
            self._x = None
        elif isinstance(x, FileSource):
            self._source = x
            if y is None:
                y = x.y  # labels from the shard set, if present
            n_rows = x.n
            row_shape = x.row_shape
            self._x = None
        else:
            x = np.ascontiguousarray(x)
            if x.dtype != np.uint8:
                raise TypeError(
                    f"Pipeline feeds raw uint8 data, got {x.dtype}"
                )
            self._x = x
            n_rows = x.shape[0]
            row_shape = x.shape[1:]
        if batch_size <= 0 or batch_size > n_rows:
            raise ValueError(
                f"batch_size {batch_size} invalid for {n_rows} rows"
            )
        self._y = (
            None if y is None else np.ascontiguousarray(y, dtype=np.int32)
        )
        if self._y is not None and len(self._y) != n_rows:
            raise ValueError("x and y lengths differ")
        self.batch_size = int(batch_size)
        self._row_shape = tuple(row_shape)
        self._set_shard(shard)
        self.shuffle = bool(shuffle)
        self.seed = int(seed)
        if scale is None:
            scale = 1.0 if self._records is not None else 1.0 / 255.0
        self.scale = float(scale)
        self.prefetch = max(1, int(prefetch))
        self.num_threads = max(1, int(num_threads))
        self._n = int(n_rows)
        self.steps_per_pass = self._n // self.batch_size
        self._row = int(np.prod(row_shape, dtype=np.int64))
        self._perm_cache = {}  # pass -> permutation (numpy, both impls)

        self.decode_workers = max(0, int(decode_workers))
        if self.decode_workers and self._records is None:
            raise ValueError(
                "decode_workers requires a RecordSource input (raw uint8 "
                "sources have nothing to decode)"
            )
        self._decode_readahead = (
            2 * self.decode_workers
            if decode_readahead is None
            else max(0, int(decode_readahead))
        )
        self._next_submit = 0  # next step handed to the decode pool

        lib = None
        if self._records is None:
            lib = _load_native() if use_native in (None, True) else None
            if use_native is True and lib is None:
                raise RuntimeError(
                    "Native pipeline requested but unavailable"
                )
        self._lib = lib
        # Unified shuffle: the native pipeline consumes numpy-computed
        # per-pass permutations unless the legacy env flag pins the old
        # C++ splitmix order (compat for artifacts recorded before the
        # unification).
        self._external_perms = (
            lib is not None
            and self.shuffle
            and os.environ.get("DTPU_NATIVE_LEGACY_SHUFFLE") != "1"
        )
        self._supplied_passes = set()
        if lib is not None:
            self._handle = self._create_handle(0)
        elif self.decode_workers:
            self._decode_pool = _DecodePool(
                self._decode_batch, self.decode_workers
            )

    def _set_shard(self, shard) -> None:
        """Validate + adopt a ``(index, count)`` slice of the global batch
        (None -> unsharded, "auto" -> the live process's rank/world).
        Shared by ``__init__`` and :meth:`reshard` so both agree on what a
        legal shard is; emitted shape follows (``batch_size`` stays the
        GLOBAL batch)."""
        if isinstance(shard, str):
            if shard != "auto":
                raise ValueError(
                    f"shard must be (index, count), None, or 'auto'; "
                    f"got {shard!r}"
                )
            import jax

            shard = (jax.process_index(), jax.process_count())
        if shard is None:
            shard = (0, 1)
        index, count = (int(shard[0]), int(shard[1]))
        if count < 1 or not (0 <= index < count):
            raise ValueError(f"shard index {index} not in [0, {count})")
        if self.batch_size % count:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"shard count {count}"
            )
        self.shard = (index, count) if count > 1 else None
        self.shard_rows = self.batch_size // count
        self.batch_shape = (self.shard_rows,) + self._row_shape

    def reshard(self, shard) -> "Pipeline":
        """Adopt a new ``(index, count)`` slice of the SAME global batch
        stream at the current position — the elastic-resize primitive. The
        global sequence depends only on (seed, pass, step), so after
        ``reshard`` the next emitted batch is this shard's rows of exactly
        the global batch the old sharding would have assembled next: the
        re-formed gang's slices still concatenate into the unsharded
        stream, and the loss trajectory is preserved across the resize
        (docs/RESILIENCE.md "Elastic gangs"). ``shard="auto"`` re-derives
        ``(process_index, process_count)`` from the live runtime. O(1) —
        the native ring (or decode pool) is recreated at the current step,
        nothing is replayed or re-prepared."""
        if self._closed:
            raise ValueError("Pipeline is closed")
        self._set_shard(shard)
        if self._handle is not None:
            # Same detach-before-recreate dance as seek(): a failed
            # recreate must not leave a handle close() would double-free.
            handle, self._handle = self._handle, None
            self._lib.dtpu_pipeline_destroy(handle)
            self._supplied_passes = set()
            self._handle = self._create_handle(self.steps_emitted)
        else:
            # Decoded-but-unconsumed results were sliced for the OLD
            # shard; drop and re-stage them for the new one.
            self._reset_decode_pool(self._py_step)
        return self

    def _create_handle(self, start_step: int):
        # One span for an in-memory array; one per memory-mapped shard for
        # a FileSource (np.memmap exposes the mapping's base address via
        # .ctypes like any ndarray — no copy).
        if self._source is not None:
            arrays = self._source.x_shards
        else:
            arrays = [self._x]
        n_spans = len(arrays)
        xs = (ctypes.c_void_p * n_spans)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays]
        )
        rows = (ctypes.c_int64 * n_spans)(*[a.shape[0] for a in arrays])
        handle = self._lib.dtpu_pipeline_create_spans(
            ctypes.cast(xs, ctypes.POINTER(ctypes.c_void_p)),
            ctypes.cast(rows, ctypes.POINTER(ctypes.c_int64)),
            n_spans,
            None if self._y is None
            else self._y.ctypes.data_as(ctypes.c_void_p),
            self._n,
            self._row,
            self.batch_size,
            1 if self.shuffle else 0,
            self.seed,
            self.prefetch,
            self.num_threads,
            self.scale,
            start_step,
            0 if self.shard is None else self.shard[0],
            1 if self.shard is None else self.shard[1],
            1 if self._external_perms else 0,
        )
        if not handle:
            raise RuntimeError("dtpu_pipeline_create failed")
        # Producers may immediately fill up to prefetch steps ahead; hand
        # them every permutation they can reach before they need it.
        self._supply_native_perms(handle, start_step + self.prefetch)
        return handle

    # ------------------------------------------------------------- shuffle --
    def _pass_perm(self, pass_idx: int) -> np.ndarray:
        """THE per-pass row permutation (identity when shuffle=False) —
        one seeded numpy computation shared by the Python fallback, the
        record decode stage, and the native pipeline (which receives it
        as an index buffer), so every implementation emits the same
        stream. Cached per pass; passes behind the consumer are pruned so
        memory stays bounded over arbitrarily long runs."""
        order = self._perm_cache.get(pass_idx)
        if order is None:
            if self.shuffle:
                rng = np.random.default_rng((self.seed, pass_idx))
                order = rng.permutation(self._n).astype(np.int64)
            else:
                order = np.arange(self._n, dtype=np.int64)
            self._perm_cache[pass_idx] = order
            cur = self.steps_emitted // max(1, self.steps_per_pass)
            for old in [p for p in self._perm_cache if p < cur]:
                del self._perm_cache[old]
        return order

    def _supply_native_perms(self, handle, max_step: int) -> None:
        """Feed the native ring every per-pass permutation its producers
        can reach while filling through ``max_step`` — called before
        every native next() so workers never wait on a missing pass."""
        if not self._external_perms or handle is None:
            return
        spp = max(1, self.steps_per_pass)
        for p in range(self.steps_emitted // spp, max_step // spp + 1):
            if p in self._supplied_passes:
                continue
            order = np.ascontiguousarray(self._pass_perm(p))
            self._lib.dtpu_pipeline_supply_perm(
                handle, p,
                order.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            )
            self._supplied_passes.add(p)
        cur = self.steps_emitted // spp
        self._supplied_passes = {
            p for p in self._supplied_passes if p >= cur
        }

    # -------------------------------------------------------------- decode --
    def _indices_for_step(self, step: int) -> np.ndarray:
        pass_idx, within = divmod(step, self.steps_per_pass)
        order = self._pass_perm(pass_idx)
        start = within * self.batch_size
        if self.shard is not None:
            start += self.shard[0] * self.shard_rows
        return order[start: start + self.shard_rows]

    def _decode_batch(self, idx: np.ndarray):
        """Fetch + CRC-validate + decode the records of one batch step.
        Pure in ``idx`` (decode_fn is required pure), so it runs
        identically inline or on any decode worker. Scaling happens here
        too, so under decode_workers it parallelizes with the decode."""
        src = self._records
        xb = np.empty((len(idx),) + self._row_shape, np.float32)
        yb = (
            np.empty((len(idx),), np.int32) if self._decode_labels else None
        )
        for i, g in enumerate(idx):
            out = src.decode(int(g))
            if self._decode_labels:
                row, label = out
                yb[i] = label
            else:
                row = out
            row = np.asarray(row)
            if row.shape != self._row_shape:
                raise ValueError(
                    f"decode_fn returned shape {row.shape} for record "
                    f"{int(g)}, but record 0 decoded to "
                    f"{self._row_shape} — rows must share one shape"
                )
            xb[i] = row
        if self.scale != 1.0:
            xb *= np.float32(self.scale)
        return xb, yb

    def _reset_decode_pool(self, step: int) -> None:
        """Recreate the decode pool at ``step``: in-flight and decoded-
        but-unconsumed work belongs to an abandoned cursor (seek/reshard)
        and is dropped, never consumed."""
        if self._decode_pool is not None:
            self._decode_pool.close()
            self._decode_pool = _DecodePool(
                self._decode_batch, self.decode_workers
            )
        self._next_submit = step

    def _fill_records(self, xb: np.ndarray, yb: np.ndarray) -> None:
        step = self._py_step
        self._py_step += 1
        if self._decode_pool is None:
            rows, labels = self._decode_batch(self._indices_for_step(step))
        else:
            # Keep the pool primed readahead steps past the consumer; work
            # is keyed by step and reassembled in order, so the stream is
            # identical for any worker count.
            if self._next_submit <= step:
                self._next_submit = step
            while self._next_submit <= step + self._decode_readahead:
                self._decode_pool.submit(
                    self._next_submit,
                    self._indices_for_step(self._next_submit),
                )
                self._next_submit += 1
            rows, labels = self._decode_pool.get(step)
        xb[:] = rows
        if labels is not None:
            yb[:] = labels
        elif self._y is not None:
            yb[:] = self._y[self._indices_for_step(step)]
        else:
            yb[:] = 0

    # ------------------------------------------------------------ iteration --
    def seek(self, step: int):
        """Jump to global step ``step`` in O(1): the stream position depends
        only on (seed, pass, within), so resume never replays or re-prepares
        skipped batches. Used by ``fit()`` on checkpoint-restart."""
        if self._closed:
            raise ValueError("Pipeline is closed")
        step = int(step)
        if step < 0:
            raise ValueError(f"seek target must be >= 0, got {step}")
        if self._handle is not None:
            # Detach before destroy/recreate: if _create_handle fails here,
            # close()/__del__ must not double-destroy the old handle.
            handle, self._handle = self._handle, None
            self._lib.dtpu_pipeline_destroy(handle)
            self._supplied_passes = set()
            self._perm_cache = {}
            self.steps_emitted = step  # perm pruning keys off the cursor
            self._handle = self._create_handle(step)
        else:
            self._py_step = step
            self._perm_cache = {}
            self._reset_decode_pool(step)
        self.steps_emitted = step

    # ------------------------------------------------------ iterator state --
    def state_dict(self, consumed_steps: Optional[int] = None) -> dict:
        """JSON-serializable iterator cursor for mid-epoch checkpoint
        resume: (pass, step-in-pass, global step) plus the identity
        fields ``load_state`` validates against. ``consumed_steps``
        overrides the recorded cursor — ``Checkpointer`` passes the
        step the MODEL actually trained, which can trail
        ``steps_emitted`` when a prefetch producer has staged batches
        ahead. The shard cursor is recorded for diagnostics but NOT
        restored: after an elastic resize the live pipeline keeps its
        own (new-world) shard and still replays the same global stream
        (see :meth:`reshard`)."""
        steps = (
            self.steps_emitted
            if consumed_steps is None else int(consumed_steps)
        )
        spp = max(1, self.steps_per_pass)
        return {
            "kind": "dtpu.data.Pipeline",
            "steps_emitted": int(steps),
            "pass": int(steps // spp),
            "step_in_pass": int(steps % spp),
            "seed": int(self.seed),
            "batch_size": int(self.batch_size),
            "shuffle": bool(self.shuffle),
            "n_rows": int(self._n),
            "shard_cursor": list(self.shard) if self.shard else [0, 1],
        }

    def load_state(self, state: dict) -> "Pipeline":
        """Restore the cursor captured by :meth:`state_dict` in O(1) — no
        batch is replayed or re-prepared. The stream identity fields
        (seed, batch_size, shuffle, row count) must match the live
        pipeline or this raises: silently resuming a DIFFERENT stream at
        a saved step would train on wrong data without any signal. The
        saved shard cursor is ignored (elastic resizes legitimately
        change it)."""
        for key, mine in (
            ("seed", self.seed),
            ("batch_size", self.batch_size),
            ("shuffle", self.shuffle),
            ("n_rows", self._n),
        ):
            if key in state and state[key] != mine:
                raise ValueError(
                    f"iterator state mismatch: checkpoint has {key}="
                    f"{state[key]!r} but this pipeline has {mine!r} — "
                    "resuming would replay a different stream"
                )
        self.seek(int(state["steps_emitted"]))
        return self

    @property
    def is_native(self) -> bool:
        return self._handle is not None

    def __iter__(self):
        return self

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._closed:
            raise ValueError("Pipeline is closed")
        xb = np.empty(self.batch_shape, np.float32)
        yb = np.empty((self.shard_rows,), np.int32)
        self._fill(xb, yb)
        return xb, yb

    def _fill(self, xb: np.ndarray, yb: np.ndarray) -> None:
        """Write the next batch into caller-provided buffers (contiguous
        float32/int32 of batch_shape/(shard_rows,)) — the one batch-emit
        implementation behind __next__ and next_k."""
        if self._records is not None:
            self._fill_records(xb, yb)
            self.steps_emitted += 1
            return
        if self._handle is not None:
            # The call below advances consumed to steps_emitted + 1, after
            # which producers may fill through steps_emitted + prefetch:
            # supply every permutation that window can touch first.
            self._supply_native_perms(
                self._handle, self.steps_emitted + self.prefetch
            )
            step = self._lib.dtpu_pipeline_next(
                self._handle,
                xb.ctypes.data_as(ctypes.c_void_p),
                yb.ctypes.data_as(ctypes.c_void_p),
            )
            if step < 0:
                raise StopIteration
            self.steps_emitted += 1
            return
        # Python fallback: identical pass/step semantics, same numpy perm.
        step = self._py_step
        self._py_step += 1
        idx = self._indices_for_step(step)
        rows = (
            self._source.gather(idx) if self._source is not None
            else self._x[idx]
        )
        xb[:] = rows.astype(np.float32) * self.scale
        if self._y is not None:
            yb[:] = self._y[idx]
        else:
            yb[:] = 0
        self.steps_emitted += 1

    def next_k(self, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """The next ``k`` batches collated into stacked arrays of shape
        ``(k,) + batch_shape`` / ``(k, shard_rows)`` — the super-batch
        ``Model.fit`` transfers once under ``steps_per_execution=K``.

        Each batch is written straight into its row of the output (the
        native ring's producer buffer, or the Python path's gather, fills
        the slice in place), so collation adds NO copy over ``k`` separate
        ``__next__`` calls — it just moves the allocation up front."""
        k = int(k)
        if k < 1:
            raise ValueError(f"next_k needs k >= 1, got {k}")
        if self._closed:
            raise ValueError("Pipeline is closed")
        xs = np.empty((k,) + self.batch_shape, np.float32)
        ys = np.empty((k, self.shard_rows), np.int32)
        for i in range(k):
            self._fill(xs[i], ys[i])
        return xs, ys

    def close(self):
        """Idempotent shutdown, safe in every degraded state: a partially
        constructed instance (``__init__`` raised before the native handle
        existed), a repeated close, and interpreter shutdown — where module
        globals (the ctypes lib, its function pointers) may already be torn
        down while native prefetch threads are still live. Every lookup is
        defensive and the destroy itself is allowed to fail silently; the
        alternative is an exception out of ``__del__`` at exit."""
        self._closed = True
        pool = getattr(self, "_decode_pool", None)
        self._decode_pool = None
        if pool is not None:
            try:
                pool.close()
            except Exception:
                pass
        handle = getattr(self, "_handle", None)
        self._handle = None
        if handle:
            destroy = getattr(getattr(self, "_lib", None),
                              "dtpu_pipeline_destroy", None)
            if destroy is not None:
                try:
                    destroy(handle)
                except Exception:
                    pass  # shutdown-time ctypes teardown; nothing to save

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
