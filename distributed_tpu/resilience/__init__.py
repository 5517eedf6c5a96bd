"""Resilience subsystem: supervised, restartable training runs.

The reference's only fault story is Spark barrier mode's restart-the-whole-
stage-and-lose-all-progress (/root/reference/README.md:400). This package
closes that gap with four cooperating pieces:

- :class:`Supervisor` — launches/monitors worker gangs (heartbeat liveness,
  exponential-backoff restarts, max-restart budget, structured event log).
- :class:`RestartPolicy` — the restart budget/backoff as a testable value.
- :class:`ElasticPolicy` — elastic gang re-formation: permanent worker loss
  (per-rank failure attribution, or a capacity probe) relaunches the same
  command at a new world size instead of burning the budget; capacity
  regained grows the gang back (see ``elastic.py``, docs/RESILIENCE.md
  "Elastic gangs").
- :class:`PreemptionHandler` — SIGTERM -> final checkpoint -> resume marker
  -> exit :data:`PREEMPTED_EXIT_CODE` (restart is budget-free).
- :class:`FaultInjector` — kill / hang / slow-heartbeat / corrupt-checkpoint
  injection so the machinery above is provable from tests.

Automatic resume rides the existing checkpoint contract: workers run with
``ModelCheckpoint(dir, restore=True)`` and a fixed seed; restore skips
corrupt latest checkpoints (``checkpoint.core``) and the batch stream
fast-forwards, so a supervised run converges bit-identically to an
uninterrupted one (modulo the replayed partial epoch). See
docs/RESILIENCE.md.
"""

from ..utils.events import EventLog, read_events
from .elastic import ElasticPolicy, FailureLedger
from .faults import FaultInjector, corrupt_latest_checkpoint
from .policy import RestartPolicy
from .preemption import (
    PREEMPTED_EXIT_CODE,
    PreemptionHandler,
    clear_resume_marker,
    read_resume_marker,
    write_resume_marker,
)
from .redundancy import (
    BuddyRedundancy,
    BuddyStore,
    mirror_holder,
    mirror_source,
    ram_dir,
    select_restore_tier,
)
from .supervisor import (
    SupervisedResult,
    Supervisor,
    recovery_rows,
    supervise,
)

__all__ = [
    "Supervisor",
    "SupervisedResult",
    "supervise",
    "recovery_rows",
    "RestartPolicy",
    "ElasticPolicy",
    "FailureLedger",
    "PreemptionHandler",
    "PREEMPTED_EXIT_CODE",
    "FaultInjector",
    "corrupt_latest_checkpoint",
    "BuddyRedundancy",
    "BuddyStore",
    "select_restore_tier",
    "mirror_holder",
    "mirror_source",
    "ram_dir",
    "EventLog",
    "read_events",
    "write_resume_marker",
    "read_resume_marker",
    "clear_resume_marker",
]
