"""Disaggregated serving fleet: router → prefill pool → decode pool.

One :class:`~distributed_tpu.serving.Engine` on one process is not a
production serving story (ROADMAP item 2). This package composes the
serving runtime (PR 6) and the elasticity/fault machinery (PR 7) into a
multi-replica tier:

- **Disaggregation** — prefill and decode run on SEPARATE replica pools;
  prompts become first tokens + packed KV blocks on the prefill side and
  are handed to a decode replica via the ``ShardedCheckpointer``
  block-layout idiom (``fleet.handoff``), with re-prefill as the
  documented fallback when transfer is unavailable.
- **Routing** — an SLO-aware front door (``fleet.router``): bounded
  queues, reject-on-predicted-SLO-breach, weighted per-tenant fair
  queuing.
- **Elasticity** — a queue-depth/SLO autoscaler (``fleet.autoscale``)
  generalizing ``ElasticPolicy``'s capacity ``probe()`` seam from
  failure-driven to load-driven; spin-up is cheap because replicas share
  compiled programs (``fleet.replica.EnginePrograms``).
- **Fault tolerance** — ``FaultInjector(mode="replica_kill",
  replica="decode-1")`` tears a named replica down mid-request; the
  router re-queues its in-flight sequences and surviving replicas finish
  them token-exact under greedy decode (zero lost requests — the
  scheduler's preemption-requeue semantics generalized across replicas).

    fleet = dtpu.fleet.ServingFleet(model, decode_replicas=4,
                                    prefill_replicas=1, max_slots=4,
                                    block_size=16, max_len=128)
    outs = fleet.run(requests, arrival_times=times, tenants=tenants)
    fleet.last_run_telemetry  # tokens/s, p50/p99 TTFT, per-request rows

tests/test_fleet.py pins token-exact serving with and without KV
transfer, bursty arrivals, and the kill-a-replica
recovery; docs/SERVING.md "Fleet" documents semantics and limits —
including the virtual-clock harness used on single-host boxes.
"""

from .autoscale import QueueAutoscaler
from .core import FleetResult, ServingFleet
from .gossip import PrefixGossipIndex
from .handoff import (
    HandoffIncompatible, KVHandoff, adopt_prefix, install_kv, pack_kv,
    pack_prefix,
)
from .replica import DecodeReplica, EnginePrograms, PrefillReplica
from .router import Admission, Router

__all__ = [
    "ServingFleet",
    "FleetResult",
    "Router",
    "Admission",
    "QueueAutoscaler",
    "EnginePrograms",
    "PrefillReplica",
    "DecodeReplica",
    "KVHandoff",
    "HandoffIncompatible",
    "PrefixGossipIndex",
    "pack_kv",
    "install_kv",
    "pack_prefix",
    "adopt_prefix",
]
