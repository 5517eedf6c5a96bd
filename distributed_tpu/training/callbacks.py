"""Training callbacks.

The reference's captured logs call out exactly what is missing from its own
training loop: "ModelCheckpoint callback is not provided. Workers will need
to restart training if any fails" (/root/reference/README.md:400). This
module supplies that callback (periodic checkpoints + resume) and the other
loop-control hooks a Keras-shaped ``fit`` is expected to have.

All side effects (file writes, logs) are chief-only; every process still
executes the same control flow, so callbacks never desynchronize an SPMD
gang. EarlyStopping decides from epoch logs that are already all-reduced
(identical on every process), so all processes stop on the same epoch.
"""

from __future__ import annotations

import math
import os
import time
from pathlib import Path
import jax
import numpy as np

from ..checkpoint import Checkpointer, ShardedCheckpointer
from ..obs import spans as obs_spans
from ..utils import event_schema as evs
from ..utils import events as devents
from ..utils import logging as dlog


class Callback:
    """Hook points around the training loop (all optional)."""

    def on_train_begin(self, model):
        pass

    def on_epoch_begin(self, model, epoch: int):
        pass

    def on_batch_end(self, model, step: int, logs: dict):
        """After each optimizer step. ``logs['loss']`` is a *device* scalar;
        reading it forces a host sync, so fast callbacks should not touch it
        every step."""

    def on_epoch_end(self, model, epoch: int, logs: dict):
        pass

    def on_train_end(self, model, history):
        pass

    def telemetry(self, model) -> dict:
        """Entries for ``model.last_fit_telemetry``, asked after every
        callback's ``on_train_end``."""
        return {}


class ModelCheckpoint(Callback):
    """Periodic step-tagged checkpoints via ``Checkpointer``; closes the
    reference's restart-from-scratch gap (README.md:400).

    ``save_freq='epoch'`` saves every epoch end; an int saves every N
    optimizer steps. ``restore=True`` resumes from the latest checkpoint in
    the directory at train begin (no-op when the directory is empty), making
    crash-restart a relaunch of the identical command.

    ``async_save=True`` hands each save to the checkpointer's background
    writer: the train loop pays only a device-side snapshot, and the
    fetch/serialize/fsync/pointer-update overlap the following steps. The
    writer is flushed (``wait()``) at train end — and by the preemption
    path before exit 75 — so fit never returns with a write in flight.
    Sharded saves background the same way: the per-process shard write
    runs on a "dtpu-shard-writer" thread while the cross-host
    barrier+manifest commit is deferred to the next main-thread
    ``save()``/``wait()`` (collective-safe; see
    ``ShardedCheckpointer``). Time blocked on saves/flushes is attributed
    to the active fit's ``checkpoint_wait`` stall bucket
    (``model.last_fit_telemetry``).

    ``buddy=`` arms the diskless recovery tier (requires
    ``sharded=True``): a ``resilience.redundancy.BuddyRedundancy``, a
    ``BuddyStore``/path to one, or ``True`` to read the
    supervisor-exported ``DTPU_BUDDY_STORE``. Every
    ``buddy_refresh_every`` optimizer steps (the same bucket-crossing
    cadence rule as int ``save_freq``) the worker mirrors its state shard
    into the RAM store on a background writer; ``restore=True`` then
    picks the restore tier per recovery — buddy (RAM, zero disk reads)
    when the mirror set is complete and fresh, the sharded disk
    checkpoint otherwise, restart-from-scratch with neither — and emits
    ``restore_begin``/``restore_end``/``post_restore_step`` events so the
    supervisor's MTTR breakdown can attribute the recovery honestly
    (docs/RESILIENCE.md "Recovery tiers").
    """

    def __init__(self, directory, *, save_freq="epoch", keep: int = 3,
                 restore: bool = False, sharded: bool = False,
                 async_save: bool = False, buddy=None,
                 buddy_refresh_every: int = 1):
        # sharded=True switches to the per-process ShardedCheckpointer
        # (requires a directory shared across hosts; hosts only touch their
        # own shards — the right format for FSDP/TP-scale models).
        if sharded:
            self.ckpt = ShardedCheckpointer(directory, keep=keep,
                                            async_save=async_save)
        else:
            if buddy is not None:
                raise ValueError(
                    "buddy= needs sharded=True: the mirror encoding is the "
                    "sharded block layout, and the disk fallback tier is "
                    "the ShardedCheckpointer"
                )
            self.ckpt = Checkpointer(directory, keep=keep,
                                     async_save=async_save)
        if save_freq != "epoch" and not (
            isinstance(save_freq, int) and save_freq > 0
        ):
            raise ValueError("save_freq must be 'epoch' or a positive int")
        self.save_freq = save_freq
        self.restore = restore
        self._last_bucket = 0  # save_freq bucket already saved (int freq)
        # Lazy import: resilience.faults imports this module for the
        # Callback base, so a top-level import here would cycle.
        if buddy is None or isinstance(buddy, bool) and not buddy:
            self._buddy = None
        else:
            from ..resilience.redundancy import BuddyRedundancy

            if buddy is True:
                self._buddy = BuddyRedundancy.from_env()  # None when unset
            elif isinstance(buddy, BuddyRedundancy):
                self._buddy = buddy
            else:  # BuddyStore or path
                self._buddy = BuddyRedundancy(buddy)
        if int(buddy_refresh_every) < 1:
            raise ValueError(
                f"buddy_refresh_every must be >= 1, got {buddy_refresh_every}"
            )
        self.buddy_refresh_every = int(buddy_refresh_every)
        self._last_refresh_bucket = 0
        self._post_restore_pending = False  # emit one post_restore_step

    def _timed(self, model, fn):
        """Run a (possibly blocking) checkpoint operation, attributing the
        blocked wall time to the active fit's checkpoint_wait bucket —
        through the obs span tracer, so checkpoint attribution shares the
        train/serve code path (registry counter + XProf annotation)."""
        with obs_spans.span("checkpoint_wait", timer=model.stall_timer):
            return fn()

    def _select_tier(self):
        """(tier, step) for this recovery, agreed gang-wide: the chief's
        view of the (shared) store + checkpoint directory decides and is
        broadcast, so every process restores the same tier at the same
        step (a split decision would desynchronize the gang's collective
        schedules)."""
        from ..resilience.redundancy import select_restore_tier

        codes = {"buddy": 0, "disk": 1, "restart": 2}
        tier, step = select_restore_tier(self._buddy, self.ckpt)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            packed = np.array(
                [codes[tier], -1 if step is None else int(step)], np.int64
            )
            packed = multihost_utils.broadcast_one_to_all(packed)
            tier = {v: k for k, v in codes.items()}[int(packed[0])]
            step = None if int(packed[1]) < 0 else int(packed[1])
        return tier, step

    def _restore_tiered(self, model):
        """The buddy-aware restore: pick the tier, restore, and emit the
        MTTR telemetry events the supervisor's recovery breakdown reads
        (restore_begin / restore_end with tier + disk-block reads; a
        post_restore_step follows at the first completed optimizer
        step)."""
        from ..checkpoint import sharded as sharded_lib

        tier, step = self._select_tier()
        if tier == "restart":
            return  # neither tier has state: train from scratch
        rank = jax.process_index()
        attempt = os.environ.get("DTPU_ATTEMPT")
        devents.emit(evs.RESTORE_BEGIN, tier=tier, rank=rank,
                     attempt=int(attempt) if attempt else None)
        reads0 = dict(sharded_lib.read_stats)
        t0 = time.perf_counter()
        if tier == "buddy":
            step = self._timed(
                model, lambda: self._buddy.restore_into(model, step)
            )
        else:
            # restore_into re-runs its own corrupt-skip scan; the step it
            # lands on (possibly a fallback) is the one reported.
            step = self._timed(model, lambda: self.ckpt.restore_into(model))
        devents.emit(
            evs.RESTORE_END, tier=tier, step=int(step), rank=rank,
            seconds=round(time.perf_counter() - t0, 4),
            disk_block_reads=(sharded_lib.read_stats["block_reads"]
                              - reads0["block_reads"]),
            disk_block_bytes=(sharded_lib.read_stats["block_bytes"]
                              - reads0["block_bytes"]),
            attempt=int(attempt) if attempt else None,
        )
        model._resumed_step = step
        self._post_restore_pending = True
        if rank == 0:
            dlog.info(
                f"ModelCheckpoint: resumed from step {step} via the "
                f"{tier} tier"
            )

    def on_train_begin(self, model):
        if self.restore and self._buddy is not None:
            self._restore_tiered(model)
        elif self.restore:
            has_ckpt = self.ckpt.latest_step() is not None
            if jax.process_count() > 1:
                # Collective decision: without a shared filesystem only the
                # chief sees the (chief-only-written) checkpoints; every
                # process must agree on whether to restore or the gang's
                # collective schedules diverge. restore_into then broadcasts
                # the values.
                from jax.experimental import multihost_utils

                has_ckpt = bool(
                    multihost_utils.broadcast_one_to_all(np.bool_(has_ckpt))
                )
            if has_ckpt:
                step = self.ckpt.restore_into(model)
                # fit() reads this to skip already-completed epochs, so an
                # identical relaunch completes to `epochs` instead of
                # training `epochs` more (the crash-restart contract).
                model._resumed_step = step
                if jax.process_index() == 0:
                    dlog.info(f"ModelCheckpoint: resumed from step {step}")
        # Arm the int-save_freq cursor from the CURRENT step (0, a restored
        # cursor, or a prior fit's progress): saves fire when the step
        # counter CROSSES a save_freq boundary, not on `step % freq == 0` —
        # under compile(steps_per_execution=K) the counter advances K at a
        # time and exact multiples may never be observed. One step at a
        # time the two rules trigger identically.
        if isinstance(self.save_freq, int):
            self._last_bucket = model.step // self.save_freq
        # Same crossing rule for the buddy-refresh cadence: a refresh
        # fires when the step counter CROSSES a cadence boundary (multi-
        # step execution advances K at a time).
        if self._buddy is not None:
            self._last_refresh_bucket = model.step // self.buddy_refresh_every

    def on_batch_end(self, model, step, logs):
        if self._post_restore_pending:
            # First completed optimizer step after a tiered restore: the
            # recompile-time marker of the supervisor's MTTR breakdown.
            self._post_restore_pending = False
            devents.emit(evs.POST_RESTORE_STEP, step=int(step),
                         rank=jax.process_index())
        if self._buddy is not None:
            bucket = step // self.buddy_refresh_every
            if bucket > self._last_refresh_bucket:
                self._last_refresh_bucket = bucket
                # Async by default: snapshot now, mirror in the background
                # (the refresh degrades to a warning on failure, never
                # stops training).
                self._buddy.refresh(model, step)
        if not isinstance(self.save_freq, int):
            return
        bucket = step // self.save_freq
        if bucket > self._last_bucket:
            self._last_bucket = bucket
            self._timed(model, lambda: self.ckpt.save(model))

    def on_epoch_end(self, model, epoch, logs):
        if self.save_freq == "epoch":
            self._timed(model, lambda: self.ckpt.save(model))

    def on_train_end(self, model, history):
        # Flush the background writers before fit returns: callers read,
        # copy, or restore from the directory immediately after fit, and a
        # run that exits right after must leave a complete newest step
        # (and a committed newest mirror).
        self._timed(model, self.ckpt.wait)
        if self._buddy is not None:
            self._timed(model, self._buddy.wait)

    def telemetry(self, model):
        # Buddy-redundancy pricing: the measured (1+1/N)x of holding a
        # peer's shard mirror in host RAM, next to the state bytes it
        # insures (docs/RESILIENCE.md "Recovery tiers").
        if self._buddy is None:
            return {}
        return {"redundancy": self._buddy.report(model)}


def _metric_mode(monitor: str) -> str:
    """'max' for higher-is-better metric names, else 'min' — THE auto-mode
    rule, shared by every plateau-style callback so they can't disagree
    about the same monitor."""
    return "max" if ("acc" in monitor or monitor.endswith("auc")) else "min"


class EarlyStopping(Callback):
    """Stop training when a monitored metric stops improving.

    Decisions use the epoch-end logs, which are aggregated across replicas
    before any process sees them — so the stop is collective-safe.
    """

    def __init__(self, monitor: str = "loss", *, patience: int = 0,
                 min_delta: float = 0.0, mode: str = "auto",
                 restore_best: bool = False):
        self.monitor = monitor
        self.patience = int(patience)
        self.min_delta = abs(float(min_delta))
        if mode not in ("auto", "min", "max"):
            raise ValueError(f"mode must be auto/min/max, got {mode!r}")
        if mode == "auto":
            mode = _metric_mode(monitor)
        self.mode = mode
        self.restore_best = restore_best
        self._best = math.inf if mode == "min" else -math.inf
        self._wait = 0
        self._best_params = None
        self._best_state = None

    def _improved(self, value: float) -> bool:
        if self.mode == "min":
            return value < self._best - self.min_delta
        return value > self._best + self.min_delta

    def on_epoch_end(self, model, epoch, logs):
        if self.monitor not in logs:
            dlog.warning(
                f"EarlyStopping: metric {self.monitor!r} not in logs "
                f"{sorted(logs)}; skipping"
            )
            return
        value = float(logs[self.monitor])
        if self._improved(value):
            self._best = value
            self._wait = 0
            if self.restore_best:
                # Deep host copies: the jitted train step DONATES param/state
                # buffers, so stashing by reference would hold deleted arrays
                # after the next step. _to_host (not device_get) because
                # multi-host-sharded leaves (TP/FSDP/EP) are not fully
                # addressable and need a collective gather.
                from ..checkpoint.core import _to_host

                copy = lambda t: jax.tree_util.tree_map(_to_host, t)
                self._best_params = copy(model.params)
                self._best_state = copy(model.state)
        else:
            self._wait += 1
            if self._wait > self.patience:
                model.stop_training = True
                if jax.process_index() == 0:
                    dlog.info(
                        f"EarlyStopping: no {self.monitor} improvement for "
                        f"{self._wait} epochs (best {self._best:.4f})"
                    )

    def on_train_end(self, model, history):
        if self.restore_best and self._best_params is not None:
            model.params = model.strategy.put_params(
                self._best_params,
                hints=model._param_hints,
            )
            model.state = model.strategy.put_params(self._best_state)


class CSVLogger(Callback):
    """Append epoch logs to a CSV file (chief-only). Every row is flushed
    AND fsynced before training continues: a run killed mid-epoch (crash,
    preemption, supervisor liveness kill) leaves all completed epochs
    durable on disk — the crash-visible log the resilience post-mortem
    reads next to the event log."""

    def __init__(self, path):
        self.path = Path(path)
        self._keys = None

    def _append_durable(self, text: str):
        with open(self.path, "a") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())

    def on_epoch_end(self, model, epoch, logs):
        if jax.process_index() != 0:
            return
        if self._keys is None:
            self._keys = sorted(logs)
            self.path.parent.mkdir(parents=True, exist_ok=True)
            if not self.path.exists():
                self._append_durable("epoch," + ",".join(self._keys) + "\n")
        row = [str(epoch)] + [
            repr(float(logs.get(k, float("nan")))) for k in self._keys
        ]
        self._append_durable(",".join(row) + "\n")


class LearningRateScheduler(Callback):
    """Keras-shaped per-epoch LR schedule: ``schedule(epoch)`` or
    ``schedule(epoch, current_lr)`` -> new learning rate, applied through
    ``Model.set_learning_rate`` (no recompile — named optimizers carry
    their hyperparameters in the optimizer state). For per-STEP schedules
    prefer the jit-native ``optim.cosine_schedule``-style callables, which
    run inside the compiled update."""

    def __init__(self, schedule, verbose: int = 0):
        self.schedule = schedule
        self.verbose = int(verbose)
        # Explicit arity inspection, NOT try/except TypeError: the fallback
        # would also swallow TypeErrors raised inside a two-argument
        # schedule's body, masking the user's real bug (the R binding does
        # the same via length(formals(...))). Exactly one case is
        # genuinely ambiguous — a bare *args signature (an un-wrapped
        # decorator) hides the inner arity — and ONLY that case keeps a
        # one-time call-and-fallback probe; inspectable signatures never
        # get the masking fallback. Builtins/callables whose signature
        # can't be inspected default to the 1-arg form.
        import inspect

        try:
            kinds = [p.kind for p in
                     inspect.signature(schedule).parameters.values()]
            positional = sum(
                k in (inspect.Parameter.POSITIONAL_ONLY,
                      inspect.Parameter.POSITIONAL_OR_KEYWORD)
                for k in kinds
            )
            two_arg = positional >= 2
            ambiguous = (
                positional < 2
                and inspect.Parameter.VAR_POSITIONAL in kinds
            )
        except (TypeError, ValueError):
            two_arg, ambiguous = False, False
        self._two_arg = two_arg
        self._ambiguous = ambiguous

    def on_epoch_begin(self, model, epoch):
        if self._ambiguous:
            # Bare-*args wrapper: probe once with the richer 2-arg form,
            # memoize whichever arity the inner callable accepts.
            try:
                lr = self.schedule(epoch, model.get_learning_rate())
                self._two_arg = True
            except TypeError:
                lr = self.schedule(epoch)
            self._ambiguous = False
        elif self._two_arg:
            lr = self.schedule(epoch, model.get_learning_rate())
        else:
            lr = self.schedule(epoch)
        model.set_learning_rate(float(lr))
        if self.verbose and jax.process_index() == 0:
            dlog.info(f"LearningRateScheduler: epoch {epoch + 1} lr={lr:g}")


class ReduceLROnPlateau(Callback):
    """Multiply the LR by ``factor`` after ``patience`` epochs without
    ``monitor`` improving by at least ``min_delta``. Decisions come from
    epoch logs that are identical on every process (all-reduced metrics),
    so an SPMD gang reduces in lockstep. The applied LR lives in the
    optimizer state and therefore checkpoints/resumes with the run; the
    plateau counters are process-local and reset on restart (match Keras)."""

    def __init__(self, monitor: str = "loss", *, factor: float = 0.5,
                 patience: int = 3, min_delta: float = 1e-4,
                 min_lr: float = 0.0, cooldown: int = 0, mode: str = "auto",
                 verbose: int = 0):
        if not 0.0 < factor < 1.0:
            raise ValueError(f"factor must be in (0, 1), got {factor}")
        self.monitor = monitor
        self.factor = float(factor)
        self.patience = int(patience)
        self.min_delta = float(min_delta)
        self.min_lr = float(min_lr)
        self.cooldown = int(cooldown)
        if mode not in ("auto", "min", "max"):
            raise ValueError(f"mode must be auto/min/max, got {mode!r}")
        self.mode = _metric_mode(monitor) if mode == "auto" else mode
        self.verbose = int(verbose)
        self._best = math.inf
        self._wait = 0
        self._cooling = 0

    def on_train_begin(self, model):
        self._best = math.inf
        self._wait = 0
        self._cooling = 0

    def on_epoch_end(self, model, epoch, logs):
        cur = logs.get(self.monitor)
        if cur is None:
            dlog.warning(
                f"ReduceLROnPlateau: metric {self.monitor!r} not in logs "
                f"({sorted(logs)})"
            )
            return
        # Max-mode metrics are negated so the plateau test is always
        # minimization; the auto rule is _metric_mode, SHARED with
        # EarlyStopping so the two can't disagree about one monitor.
        sign = -1.0 if self.mode == "max" else 1.0
        val = sign * float(cur)
        # Best-tracking continues through cooldown (Keras semantics):
        # cooldown only suppresses the plateau counter, so a transient
        # improvement during cooldown can't later masquerade as progress
        # against a stale best.
        improved = val < self._best - self.min_delta
        if improved:
            self._best = val
        if self._cooling > 0:
            self._cooling -= 1
            self._wait = 0
            return
        if improved:
            self._wait = 0
            return
        self._wait += 1
        if self._wait < self.patience:
            return
        old = model.get_learning_rate()
        new = max(old * self.factor, self.min_lr)
        if new < old:
            model.set_learning_rate(new)
            if self.verbose and jax.process_index() == 0:
                dlog.info(
                    f"ReduceLROnPlateau: {self.monitor} plateaued "
                    f"{self.patience} epochs; lr {old:g} -> {new:g}"
                )
        self._wait = 0
        self._cooling = self.cooldown


class TensorBoard(Callback):
    """Write per-epoch scalars (loss, metrics, val_*) as TensorBoard event
    files, chief-only. Uses the installed TensorFlow's summary writer
    lazily — the framework itself has no TF dependency. The TF import is
    checked on the CHIEF at on_train_begin, not at construction: non-chief
    gang workers never write events, so a worker host without TF must not
    crash just for constructing the callback (ADVICE r4)."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self._writer = None

    def on_train_begin(self, model):
        if jax.process_index() != 0:
            return
        try:
            import tensorflow as tf
        except ImportError as e:  # pragma: no cover
            raise ImportError(
                "callbacks.TensorBoard needs the tensorflow package for "
                "event-file writing (CSVLogger is the dependency-free "
                "alternative)"
            ) from e

        self._writer = tf.summary.create_file_writer(self.log_dir)

    def on_epoch_end(self, model, epoch, logs):
        if self._writer is None:
            return
        import tensorflow as tf

        with self._writer.as_default():
            for key, value in logs.items():
                tf.summary.scalar(key, float(value), step=epoch)
            try:
                tf.summary.scalar("learning_rate",
                                  model.get_learning_rate(), step=epoch)
            except (KeyError, RuntimeError):
                pass
        self._writer.flush()

    def on_train_end(self, model, history):
        if self._writer is not None:
            self._writer.close()
            self._writer = None


class LambdaCallback(Callback):
    """Ad-hoc hooks without subclassing."""

    def __init__(self, on_train_begin=None, on_epoch_begin=None,
                 on_batch_end=None, on_epoch_end=None, on_train_end=None):
        self._hooks = {
            "on_train_begin": on_train_begin,
            "on_epoch_begin": on_epoch_begin,
            "on_batch_end": on_batch_end,
            "on_epoch_end": on_epoch_end,
            "on_train_end": on_train_end,
        }

    def __getattribute__(self, name):
        if name.startswith("on_"):
            hook = object.__getattribute__(self, "_hooks").get(name)
            if hook is not None:
                return hook
        return object.__getattribute__(self, name)


class ProfilerCallback(Callback):
    """Capture a ``jax.profiler`` trace over a step window; view in
    TensorBoard/XProf. The TPU-native answer to the reference's
    log-line-only observability (SURVEY.md §5 tracing)."""

    def __init__(self, logdir, *, start_step: int = 10, num_steps: int = 5):
        self.logdir = str(logdir)
        self.start_step = int(start_step)
        self.stop_step = int(start_step) + int(num_steps)
        self._active = False

    def on_batch_end(self, model, step, logs):
        if jax.process_index() != 0:  # chief-only, one trace per gang
            return
        if not self._active and step >= self.start_step and step < self.stop_step:
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif self._active and step >= self.stop_step:
            jax.profiler.stop_trace()
            self._active = False

    def on_train_end(self, model, history):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False


class SyncCheck(Callback):
    """Assert the synchronous-DP replica-identity invariant during training
    (the reference's only distributed-correctness signal, observed manually
    at /root/reference/README.md:226-232, as an automated in-training
    check). Verifies every replicated parameter is bit-identical across
    its replicas at the end of each ``every``-th epoch — catching
    non-deterministic math or a broken collective at the epoch it happens
    instead of at final-metrics divergence."""

    def __init__(self, every: int = 1, include_opt_state: bool = False):
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        self.every = int(every)
        self.include_opt_state = bool(include_opt_state)

    def on_epoch_end(self, model, epoch, logs):
        if (epoch + 1) % self.every:
            return
        from ..utils.sync_check import assert_replicas_identical

        try:
            assert_replicas_identical(model.params, "params")
            assert_replicas_identical(model.state, "state")
            if self.include_opt_state:
                assert_replicas_identical(model.opt_state, "opt_state")
        except AssertionError as e:
            # Divergence still fails the run (the invariant is hard), but
            # it ALSO lands in the resilience event log first: after the
            # supervisor's gang-kill + restart, the post-mortem names the
            # drifted parameter without trawling worker stderr.
            devents.emit(evs.SYNC_CHECK_FAILED, epoch=int(epoch),
                         step=int(model.step), error=str(e))
            raise
