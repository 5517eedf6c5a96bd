"""Supervised worker gangs for the slow fault tests.

``elastic_gang`` runs one elastic scenario (tests/test_elastic.py) and
``recovery_gang`` one diskless-recovery scenario (tests/test_redundancy.py):
each writes a worker script into ``tmp``, runs it under a real
``Supervisor`` on XLA:CPU (one device a process) and returns the result
with the run's event records.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ELASTIC_WORKER = """
import os, sys, time
sys.path.insert(0, os.environ["BENCH_REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import distributed_tpu as dtpu
from distributed_tpu.data.pipeline import Pipeline
from distributed_tpu.launch import report_result
from distributed_tpu.resilience import FaultInjector
from distributed_tpu.training.callbacks import LambdaCallback, ModelCheckpoint
from distributed_tpu.utils import events

spec = dtpu.cluster.initialize()
world = spec.num_processes
attempt = int(os.environ.get("DTPU_ATTEMPT", "1"))
GB = int(os.environ["BENCH_GB"])
STEPS = int(os.environ["BENCH_STEPS"])
record_loss = os.environ.get("BENCH_RECORD_LOSS") == "1"

x, y = dtpu.data.synthetic_images(256, (8, 8), 10, 0)
strategy = dtpu.DataParallel() if world > 1 else dtpu.SingleDevice()
with strategy.scope():
    m = dtpu.Model(dtpu.nn.Sequential([
        dtpu.nn.Flatten(),
        dtpu.nn.Dense(32, activation="relu"),
        dtpu.nn.Dense(10),
    ]))
    m.compile(optimizer=dtpu.optim.SGD(0.05),
              loss="sparse_categorical_crossentropy")
m.build((8, 8))

seen_first = []
def on_step(model, step, logs):
    if not seen_first:
        seen_first.append(step)
        events.emit("first_step", attempt=attempt, step=int(step),
                    world=world)
    if spec.index == 0:
        events.emit("step_mark", attempt=attempt, world=world,
                    step=int(step),
                    loss=(float(logs["loss"]) if record_loss else None))

cbs = [ModelCheckpoint(os.environ["BENCH_CKPT"], sharded=True,
                       save_freq=int(os.environ.get("BENCH_SAVE_FREQ", "2")),
                       restore=True),
       LambdaCallback(on_batch_end=on_step)]

# Capacity-regain trigger (grow direction): rank 0 flips the supervisor's
# capacity-probe file just before the injected transient kill, so the
# restart boundary sees the regained capacity.
cap_file = os.environ.get("BENCH_CAP_FLIP_FILE")
if cap_file and spec.index == 0:
    flip_at = int(os.environ.get("BENCH_CAP_FLIP_AT", "3"))
    def flip(model, step, logs):
        if step >= flip_at:
            with open(cap_file, "w") as f:
                f.write(os.environ.get("BENCH_CAP_FLIP_TO", "4"))
    cbs.append(LambdaCallback(on_batch_end=flip))

# Permanent-loss model: the fault stays armed while the world is ABOVE the
# surviving capacity (BENCH_FAULT_ABOVE) — every relaunch at the doomed
# size dies again, which is exactly what per-rank attribution must see.
# With a once-marker (grow direction) the fault is the usual transient one.
fault = FaultInjector.from_env()
if fault is not None and world > int(os.environ.get("BENCH_FAULT_ABOVE", "0")):
    cbs.append(fault)

with Pipeline(x, y, GB, seed=0, use_native=False,
              shard=(spec.index, world)) as p:
    m.fit(p, epochs=1, steps_per_epoch=STEPS, verbose=0, callbacks=cbs)

report_result({"world": world, "final_step": int(m.step)})
"""


def elastic_gang(tmp, *, world, min_workers, max_workers=None,
                 global_batch=64, steps=10, fault=None, fault_above=0,
                 probe_file=None, cap_flip_to=None, cap_flip_at=3,
                 record_loss=False, failure_threshold=2, max_restarts=3,
                 save_freq=2, timeout=600.0, grace=5.0):
    """One supervised elastic-gang scenario: N workers train the same tiny
    dense model from per-host-sharded pipelines with sharded checkpoints;
    faults and the capacity probe come from the arguments. Returns the
    SupervisedResult plus the run's event records."""
    from pathlib import Path

    from distributed_tpu.resilience import (
        ElasticPolicy, RestartPolicy, Supervisor,
    )
    from distributed_tpu.utils.events import EventLog

    tmp = Path(tmp)
    tmp.mkdir(parents=True, exist_ok=True)
    worker = tmp / "worker.py"
    worker.write_text(_ELASTIC_WORKER)
    log = EventLog(tmp / "events.jsonl")
    env_extra = {
        "BENCH_REPO": REPO,
        "BENCH_CKPT": str(tmp / "ckpt"),
        "BENCH_GB": str(global_batch),
        "BENCH_STEPS": str(steps),
        "BENCH_SAVE_FREQ": str(save_freq),
        "BENCH_FAULT_ABOVE": str(fault_above),
    }
    if record_loss:
        env_extra["BENCH_RECORD_LOSS"] = "1"
    if fault:
        env_extra["DTPU_FAULT"] = fault
        if fault_above == 0:
            env_extra["DTPU_FAULT_MARKER"] = str(tmp / "fault_once")
    probe = None
    if probe_file is not None:
        probe_path = Path(probe_file)

        def probe():
            return int(probe_path.read_text().strip())

        if cap_flip_to is not None:
            env_extra["BENCH_CAP_FLIP_FILE"] = str(probe_path)
            env_extra["BENCH_CAP_FLIP_AT"] = str(cap_flip_at)
            env_extra["BENCH_CAP_FLIP_TO"] = str(cap_flip_to)
    sup = Supervisor(
        [sys.executable, str(worker)], world,
        policy=RestartPolicy(max_restarts=max_restarts, backoff=0.01,
                             backoff_max=0.01),
        elastic=ElasticPolicy(
            min_workers=min_workers,
            max_workers=max_workers if max_workers is not None else world,
            failure_threshold=failure_threshold,
            probe=probe,
            divisor_of=global_batch,
        ),
        checkpoint_dir=tmp / "ckpt",
        event_log=log,
        env_extra=env_extra,
    )
    result = sup.run(timeout=timeout, grace=grace)
    return result, log.read()



_RECOVERY_WORKER = """
import os, sys, time
sys.path.insert(0, os.environ["BENCH_REPO"])
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import distributed_tpu as dtpu
from distributed_tpu.data.pipeline import Pipeline
from distributed_tpu.launch import report_result
from distributed_tpu.resilience import FaultInjector
from distributed_tpu.training.callbacks import LambdaCallback, ModelCheckpoint
from distributed_tpu.utils import events

spec = dtpu.cluster.initialize()
world = spec.num_processes
attempt = int(os.environ.get("DTPU_ATTEMPT", "1"))
GB = int(os.environ["BENCH_GB"])
STEPS = int(os.environ["BENCH_STEPS"])
WIDTH = int(os.environ["BENCH_WIDTH"])
refresh = int(os.environ.get("BENCH_REFRESH_EVERY", "1"))
record_loss = os.environ.get("BENCH_RECORD_LOSS") == "1"

x, y = dtpu.data.synthetic_images(256, (8, 8), 10, 0)
# FSDP so each worker's state shard is genuinely 1/N-sized (the (1+1/N)x
# redundancy story); single-process falls back to the whole tree.
strategy = (dtpu.FullyShardedDataParallel() if world > 1
            else dtpu.SingleDevice())
with strategy.scope():
    m = dtpu.Model(dtpu.nn.Sequential([
        dtpu.nn.Flatten(),
        dtpu.nn.Dense(WIDTH, activation="relu"),
        dtpu.nn.Dense(WIDTH, activation="relu"),
        dtpu.nn.Dense(10),
    ]))
    m.compile(optimizer=dtpu.optim.SGD(0.05, momentum=0.9),
              loss="sparse_categorical_crossentropy")
m.build((8, 8))

seen_first = []
def on_step(model, step, logs):
    if not seen_first:
        seen_first.append(step)
        events.emit("first_step", attempt=attempt, step=int(step),
                    world=world)
    if spec.index == 0 and record_loss:
        events.emit("step_mark", attempt=attempt, world=world,
                    step=int(step), loss=float(logs["loss"]))

# buddy=True arms the diskless tier from the supervisor-exported
# DTPU_BUDDY_STORE; refresh cadence 10**9 leaves the tier armed for
# restore-tier SELECTION (and its telemetry events) but never refreshed —
# the disk-tier baseline runs through the identical code path.
cbs = [ModelCheckpoint(os.environ["BENCH_CKPT"], sharded=True,
                       save_freq=int(os.environ.get("BENCH_SAVE_FREQ", "2")),
                       restore=True,
                       async_save=os.environ.get("BENCH_SYNC_SAVE") != "1",
                       buddy=True,
                       buddy_refresh_every=(refresh if refresh > 0
                                            else 10**9)),
       LambdaCallback(on_batch_end=on_step)]
fault = FaultInjector.from_env()
if fault is not None:
    cbs.append(fault)

with Pipeline(x, y, GB, seed=0, use_native=False,
              shard=(spec.index, world)) as p:
    m.fit(p, epochs=1, steps_per_epoch=STEPS, verbose=0, callbacks=cbs)

red = (m.last_fit_telemetry or {}).get("redundancy")
report_result({"world": world, "final_step": int(m.step),
               "redundancy": red})
"""


def recovery_gang(tmp, *, world=2, width=2560, steps=8,
                  fault="kill:at_step=5,rank=1", once=True,
                  refresh_every=1, save_freq=2, global_batch=32,
                  record_loss=False, sync_save=False, max_restarts=3,
                  timeout=600.0, grace=5.0):
    """One supervised diskless-recovery scenario: a fixed-size FSDP gang
    with sharded async checkpoints AND the buddy tier armed
    (``refresh_every=0`` arms selection but never refreshes — the disk-tier
    baseline), fault-injected per ``fault``. The supervisor owns a tmpfs
    buddy store and invalidates failed ranks' segments, so the relaunch's
    restore-tier selection sees exactly what a host loss leaves behind.
    Returns (SupervisedResult, events, store_root) — the caller removes
    ``store_root``."""
    from pathlib import Path

    from distributed_tpu.resilience import (
        RestartPolicy, Supervisor, ram_dir,
    )
    from distributed_tpu.utils.events import EventLog

    tmp = Path(tmp)
    tmp.mkdir(parents=True, exist_ok=True)
    worker = tmp / "worker.py"
    worker.write_text(_RECOVERY_WORKER)
    log = EventLog(tmp / "events.jsonl")
    store_root = ram_dir()
    env_extra = {
        "BENCH_REPO": REPO,
        "BENCH_CKPT": str(tmp / "ckpt"),
        "BENCH_GB": str(global_batch),
        "BENCH_STEPS": str(steps),
        "BENCH_WIDTH": str(width),
        "BENCH_SAVE_FREQ": str(save_freq),
        "BENCH_REFRESH_EVERY": str(refresh_every),
    }
    if record_loss:
        env_extra["BENCH_RECORD_LOSS"] = "1"
    if sync_save:
        env_extra["BENCH_SYNC_SAVE"] = "1"
    if fault:
        env_extra["DTPU_FAULT"] = fault
        if once:
            env_extra["DTPU_FAULT_MARKER"] = str(tmp / "fault_once")
    sup = Supervisor(
        [sys.executable, str(worker)], world,
        policy=RestartPolicy(max_restarts=max_restarts, backoff=0.01,
                             backoff_max=0.01),
        checkpoint_dir=tmp / "ckpt",
        buddy_store_dir=store_root,
        event_log=log,
        env_extra=env_extra,
    )
    result = sup.run(timeout=timeout, grace=grace)
    return result, log.read(), store_root
