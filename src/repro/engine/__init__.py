"""Continuous-batching serving engine with a slot-based KV cache.

The paper's serving argument made live: deterministic execution under a
fixed p99 deadline beats throughput-first designs (Table 4).  The engine
owns a fixed pool of KV-cache *slots* (static ``num_slots x max_seq``
shapes, so there is exactly one compiled decode step and its latency is
predictable), admits arriving requests into free slots, advances every
active slot with ONE fused slot-masked decode step per tick, and retires
finished slots for immediate reuse — no drain barrier between request
generations.

Modules:
- ``slots``:     slot pool bookkeeping (host side, no jax),
- ``scheduler``: admission frontend over `core.batching.AdmissionPolicy`
                 (the same decision procedure the virtual-time simulator
                 uses — property-tested identical),
- ``dispatch``:  the dispatch core — the per-lane tick loop, slot/block
                 accounting, stash/exact-resume and fault plumbing —
                 plus the ``ExecutorBackend`` seam (single-device or
                 tensor-parallel sharded executors),
- ``engine``:    policy + reporting over a backend, plus the sequential
                 reference decoder the parity tests compare against
                 bit-for-bit,
- ``router``:    the replica tier — N engines load-balanced by projected
                 slot occupancy behind the same admission policy.

``Engine(block_size=...)`` switches the positional KV leaves to a paged
layout: fixed-size physical blocks behind a per-slot block table
(``slots.BlockPool`` holds the free list / refcounts / prefix-hash
registry), with identical-prompt prefixes shared copy-on-extend and
admission priced in worst-case blocks instead of free slots alone.

Overload robustness (``faults`` + ``serve(preemption=...,
fault_plan=...)``): SLO-class admission with per-class slot quotas,
slot preemption with bit-for-bit exact resume, and a seeded
deterministic fault-injection harness with bounded per-slot recovery —
see "Overload & failure semantics" in ``docs/serving.md``.
"""
from repro.engine.dispatch import (DispatchCore, ExecutorBackend,
                                   ShardedExecutor, SingleDeviceExecutor)
from repro.engine.engine import (Engine, EngineReport, EngineRequest,
                                 RequestResult, WarmupReport,
                                 reference_outputs, synthetic_requests)
from repro.engine.faults import FAULT_KINDS, Fault, FaultPlan
from repro.engine.router import ReplicaRouter, RouterReport
from repro.engine.scheduler import SlotScheduler
from repro.engine.slots import (BlockPool, RequestTooLong, SlotPool,
                                SlotState)

__all__ = [
    "BlockPool", "DispatchCore", "Engine", "EngineReport", "EngineRequest",
    "ExecutorBackend", "FAULT_KINDS", "Fault", "FaultPlan",
    "ReplicaRouter", "RequestResult", "RequestTooLong", "RouterReport",
    "ShardedExecutor", "SingleDeviceExecutor", "SlotPool", "SlotScheduler",
    "SlotState", "WarmupReport", "reference_outputs", "synthetic_requests",
]
