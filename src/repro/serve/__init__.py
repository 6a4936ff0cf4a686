"""Continuous-batching LLM serving engine on the SOT-MRAM memory system.

Closes the loop between request arrivals and the bank-level simulator.
There is one serving loop, the event loop of ``fleet``: N replicas behind a
pluggable router (round-robin / least-loaded / prefix-affinity), with
optional prefill/decode disaggregation (KV-page streaming priced as a
cross-replica traffic class) and a TTFT-SLO autoscaler.  A single
accelerator is the 1-replica fleet: ``closed_loop_serving`` runs it with
``FleetConfig()``.  Each replica steps an iteration-level continuous-batching
scheduler (``scheduler``) over a paged KV-cache allocator that maps
fixed-size KV pages onto GLB banks and spills cold pages to DRAM
(``kv_pages``, a struct-of-arrays page table); the lowering (``lower``)
emits one technology-neutral event block per traffic class per step
(``BlockEmitter``; ``ScalarEmitter`` is the bit-identical per-request
reference), prices them per GLB technology (``TechPricer``) through
``repro.sim``'s TraceBuilder, and scores the FIFO replay — TTFT/TPOT
p50/p99, bank-conflict rate, GLB page residency.  One replica slice per
resource range keeps pricing a single segmented-bincount pass.  The sweep
engine (``sweep``) evaluates QPS x capacity x technology grids off one
shared request draw: one loop run per (qps, capacity) under the
technology-invariant clock is gathered once into a technology-neutral
column run (``replay.NeutralRun``), priced vectorially per technology, and
every certified technology's replay is scored in one batched segmented
scan (``replay.score_shared_batch``, numpy/jax/pallas backends,
bit-identical reports); technologies whose schedule-invariance certificate
fails fall back to their own loop.  ``repro.dse.serving`` uses it to find
the SLO-knee capacity.  See docs/serving.md and docs/perf.md.
"""

from repro.serve.fleet import (
    Fleet,
    FleetConfig,
    FleetReport,
    UnknownRouterPolicyError,
    closed_loop_serving,
    fleet_serving,
    summarize_fleet,
)
from repro.serve.kv_pages import PagedKVAllocator
from repro.serve.lower import (
    BlockEmitter,
    ScalarEmitter,
    ServeModel,
    ServeReport,
    StepBlocks,
    TechPricer,
    summarize_report,
)
from repro.serve.replay import (
    NeutralRun,
    TechPricing,
    score_shared_batch,
)
from repro.serve.scheduler import (
    ContinuousBatchScheduler,
    RequestState,
    ServeEngineConfig,
    StepPlan,
)
from repro.serve.sweep import (
    ServingGridSpec,
    SweepRow,
    sweep_serving_grid,
)

__all__ = [
    "BlockEmitter",
    "ContinuousBatchScheduler",
    "Fleet",
    "FleetConfig",
    "FleetReport",
    "NeutralRun",
    "PagedKVAllocator",
    "RequestState",
    "ScalarEmitter",
    "ServeEngineConfig",
    "ServeModel",
    "ServeReport",
    "ServingGridSpec",
    "StepBlocks",
    "StepPlan",
    "SweepRow",
    "TechPricer",
    "TechPricing",
    "UnknownRouterPolicyError",
    "closed_loop_serving",
    "fleet_serving",
    "score_shared_batch",
    "summarize_fleet",
    "summarize_report",
    "sweep_serving_grid",
]
