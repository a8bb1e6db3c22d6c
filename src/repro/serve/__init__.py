"""Continuous-batching serving subsystem.

The engine holds a fixed number of KV **slots**: a slot-batched cache
preallocated once at the full decode horizon (``models.model.forward``'s
``cache_len`` plumbing — no ``jnp.pad`` regrow, no recompiles as batch
composition changes). Requests are admitted into free slots (per-request
prefill + in-place slot insert), decoded in in-graph multi-token chunks
with per-slot positions and in-graph temperature sampling, and retired
as they finish — new requests join mid-flight without disturbing the
streams already decoding.

``PagedServeEngine`` swaps the dense slot stripes for a paged KV cache
(``repro.serve.pages``): fixed-size physical pages mapped through
per-slot block tables, refcounted prefix sharing with copy-on-write,
lazy allocation as positions advance, and zero-fill-free page
recycling — memory scales with live tokens instead of
``slots x horizon``, and the avoided admission stores are the serve
path's write-allocate-evasion story.

The analytical stack is wired in: the scheduler picks its decode chunk
size from the port model's tier-resolved per-step cost
(``repro.serve.planner``, via ``portmodel.compare`` /
``Report.tier_bound_seconds``), and the per-step KV traffic — dense
updates, paged gathers, CoW copies, recycled admissions — is priced
through ``wa``/``memtier`` so every delta is reported per machine
(``repro.serve.kv_traffic``).

Both engines accept ``mesh=``/``rules=``: with a device mesh the
params and the KV cache (dense stripes or page pools) are laid out by
the logical-axis rules (``kvheads`` -> TP), the step functions trace
with ``sc()`` constraints live, and the planner prices the per-shard
KV stream plus the per-step activation all-reduce
(``kv_traffic.collective_traffic``). ``mesh=None`` is the bit-exact
single-device path. ``ReplicaRouter`` (``repro.serve.router``) scales
*traffic* instead: N replicas behind a round-robin / least-loaded
admission controller with per-replica queues and backpressure.

The overlapped runtime threads through all of it: ``pipeline=N`` on
either engine double-buffers the decode dispatch (round N+1 enqueued
while round N executes, token streams byte-identical to serial),
``repro.serve.staging`` prefetches queued prompts to the device so
admission skips the H2D copy, and ``repro.serve.plandb`` persists an
offline planner sweep (both backends, chunk x tile x tp x flavor) so
admission planning at startup is an O(1) bit-identical DB hit.

The fault-tolerance layer rides on top: ``repro.serve.faults`` is the
seeded deterministic fault injector (``FaultyEngine`` wraps either
engine and injects step/admission failures on a schedule), and
``repro.serve.health`` is the consumer — per-replica health state
machines scored against the planner's per-round budget, request
rescue by prompt+prefix replay (priced via
``kv_traffic.rescue_traffic``), deadlines, and priced
keep/replan/shed degradation behind ``FaultTolerantRouter``.
"""

from repro.serve.decode import make_chunked_decode_step
from repro.serve.engine import PagedServeEngine, Request, ServeEngine
from repro.serve.faults import (FaultSpec, FaultyEngine, TransientFault,
                                chaos_schedule, poison_slot)
from repro.serve.health import (FaultTolerantRouter, HealthConfig,
                                NoHealthyReplica, ReplicaHealth,
                                deadline_for, priced_degradation)
from repro.serve.kv_traffic import (collective_traffic, cow_fork_traffic,
                                    decode_read_traffic, kv_update_traffic,
                                    page_admission_traffic,
                                    page_gather_traffic, rescue_traffic)
from repro.serve.pages import PagePool, PoolExhausted, paged_cache_pspecs
from repro.serve.plandb import (PlanDB, backend_disagreements,
                                plandb_install, plandb_installed,
                                sweep_plans)
from repro.serve.planner import (ChunkPlan, decode_step_hlo,
                                 kv_read_seconds, plan_chunk_size,
                                 plan_stats, planned_round_seconds,
                                 reset_plan_stats)
from repro.serve.router import QueueFull, ReplicaRouter
from repro.serve.staging import PromptStager

__all__ = [
    "ChunkPlan",
    "FaultSpec",
    "FaultTolerantRouter",
    "FaultyEngine",
    "HealthConfig",
    "NoHealthyReplica",
    "PagePool",
    "PagedServeEngine",
    "PlanDB",
    "PoolExhausted",
    "PromptStager",
    "QueueFull",
    "ReplicaHealth",
    "ReplicaRouter",
    "Request",
    "ServeEngine",
    "TransientFault",
    "backend_disagreements",
    "chaos_schedule",
    "collective_traffic",
    "cow_fork_traffic",
    "deadline_for",
    "decode_read_traffic",
    "decode_step_hlo",
    "kv_read_seconds",
    "kv_update_traffic",
    "make_chunked_decode_step",
    "page_admission_traffic",
    "page_gather_traffic",
    "paged_cache_pspecs",
    "plan_chunk_size",
    "plan_stats",
    "plandb_install",
    "plandb_installed",
    "planned_round_seconds",
    "poison_slot",
    "priced_degradation",
    "rescue_traffic",
    "reset_plan_stats",
    "sweep_plans",
]
