"""Replicated serving tier: struct-key-routed multi-process replicas.

Layers (bottom up):

* :mod:`repro.serving.transport` — picklable :class:`ServiceSpec`
  recipe + the ids-first wire format (featurize once client-side).
* :mod:`repro.serving.shared_cache` — :class:`SharedRowCache`, the
  cross-replica second-chance prediction cache in shared memory
  (bounded lock acquire + crc-validated slots, so a dying holder
  degrades to misses instead of wedging or corrupting the fleet).
* :mod:`repro.serving.replica` — :func:`start_replicas` /
  :class:`ReplicaTier`: N spawned processes, each a full
  service+server stack with adaptive flush deadlines; slots are
  respawnable in place.
* :mod:`repro.serving.router` — :class:`ReplicaClient`, the
  service-shaped client: consistent-hash routing on struct keys,
  retry with decorrelated-jitter backoff honoring replica
  ``retry_after_s`` hints, reroute on failure, per-request deadline
  budgets, and an optional analyzer-oracle fallback floor.
* :mod:`repro.serving.supervisor` — :class:`ReplicaSupervisor`:
  heartbeat liveness, in-slot respawn with crash-loop budgets, and
  signal-driven scale up/down.
* :mod:`repro.serving.faults` — seeded :class:`FaultPlan` /
  :class:`FaultyTransport`, the deterministic chaos harness behind
  the ``chaos_serve`` gate.
"""
from repro.serving.faults import FaultEvent, FaultPlan, FaultyTransport
from repro.serving.replica import ReplicaTier, TierHandle, start_replicas
from repro.serving.router import HashRing, QueueTransport, ReplicaClient
from repro.serving.shared_cache import SharedRowCache
from repro.serving.supervisor import (ReplicaSupervisor, RestartBudget,
                                      ScalePolicy)
from repro.serving.transport import ServiceSpec

__all__ = [
    "FaultEvent", "FaultPlan", "FaultyTransport", "HashRing",
    "QueueTransport", "ReplicaClient", "ReplicaSupervisor", "ReplicaTier",
    "RestartBudget", "ScalePolicy", "ServiceSpec", "SharedRowCache",
    "TierHandle", "start_replicas",
]
