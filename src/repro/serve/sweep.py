"""Shared-grid QPS x capacity x technology sweep over the serving loop.

Every grid point runs the one serving loop, the fleet event loop of
:mod:`repro.serve.fleet`; a single-accelerator grid is the 1-replica fleet
(``ServingGridSpec.fleet`` left at ``FleetConfig()``), whose rows carry no
``fleet`` extras.  Evaluating a serving design grid point by point re-runs
the scheduler, the page allocator, and the lowering for every (qps,
capacity, technology) triple, even though most of that work is identical
across the grid:

* the **request population** is load-invariant up to a scale factor —
  NumPy's ``Generator.exponential(scale)`` is exactly ``scale *
  standard_exponential()``, so one ``draw_request_shape`` draw yields every
  QPS point's arrival times bit-identically (``arrivals_at_qps``);
* the **schedule and lowered event blocks** are technology-invariant
  whenever no step is paced by GLB bank congestion: of the per-step
  feedback ``dt = max(cadence, prefill, glb, dram)``, the decode cadence,
  prefill time, and DRAM busy term (total spill accesses x access time — no
  per-channel max) are all DRAM-side quantities shared by every technology;
  only the per-bank GLB busy time differs.

The engine exploits both: per (qps, capacity) it runs the scheduler +
allocator + block lowering **once**, with
``max(cadence, prefill, dram)`` as the step clock, then prices the neutral
:class:`~repro.serve.lower.StepBlocks` per technology (bank = hash %
n_banks, service/energy scaled).  While pricing it checks the *exactness
certificate*: if every step's priced per-bank GLB busy time stays within
the shared step duration, the loop run with that technology's own clock
would have produced byte-for-byte the same schedule, so the shared result
is exact — not an approximation.  A technology that violates the
certificate (congestion would have stretched its steps) falls back to its
own loop, so ``sweep_serving_grid`` always returns exact rows; ``shared``
on each row records which path produced it.

Scoring is batched: per (qps, capacity) the shared run's step blocks are
flattened **once** into technology-neutral trace columns
(:class:`repro.serve.replay.NeutralRun`), priced per technology with a few
vectorized multiplies, and every *certified* technology is replayed in a
single :func:`repro.sim.engine.replay_schedule_batch` call — the
write-combining mask, the time sort, and the segmented max-plus scan are
shared or batched instead of recomputed per technology.  ``backend`` picks
the scan implementation: ``"numpy"`` (``np.maximum.accumulate``), ``"jax"``
(one fused jitted XLA program around ``jax.lax.cummax``), ``"pallas"`` (the
chunked ``repro.kernels.segmented_replay`` kernel, which ``"auto"`` never
picks), or ``"auto"`` (jax when JAX runs on an accelerator, numpy on a CPU
or without JAX — :func:`repro.sim.engine.resolve_backend`).  All backends
produce bit-identical rows — pinned by ``tests/test_replay_kernel.py``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.workload import NLP_TABLE_V, NLPModelSpec
from repro.faults import FaultConfig, derate_system, fault_model_for
from repro.obs import core as obs
from repro.sim.engine import SimConfig, resolve_backend
from repro.sim.trace import ServingConfig, arrivals_at_qps, draw_request_shape
from repro.spec import build_system, tech_group
from repro.serve.lower import ServeModel, ServeReport, serving_run_meta
from repro.serve.fleet import Fleet, FleetConfig, FleetReport, run_fleet
from repro.serve.replay import NeutralRun, score_shared_batch
from repro.serve.scheduler import ServeEngineConfig


@dataclasses.dataclass(frozen=True)
class ServingGridSpec:
    """The serving design grid: offered load x GLB capacity x technology."""

    qps: tuple[float, ...] = (100.0, 200.0, 400.0, 800.0)
    capacities_mb: tuple[float, ...] = (32.0, 64.0)
    technologies: tuple[str, ...] = tech_group("serving")
    model: str = "gpt2"
    serving: ServingConfig = ServingConfig()
    engine: ServeEngineConfig = ServeEngineConfig()
    # Fleet axis: replicas/router/disaggregation/autoscaler.  The default
    # (1 replica, knobs off) is a single accelerator.
    fleet: FleetConfig = FleetConfig()
    # Fault axis: a FaultConfig makes every row *iso-reliability* — each
    # technology is priced on its reliability-derated twin (MRAM pays
    # ECC/write-verify, trivial-reliability SRAM pays nothing) with seeded
    # write-retry/bank-offline injection; None reproduces today's rows.
    faults: FaultConfig | None = None

    @classmethod
    def from_scenario(cls, scenario) -> "ServingGridSpec":
        """The full QPS x capacity x technology grid of a serving
        :class:`repro.spec.Scenario`."""
        return cls(
            qps=tuple(scenario.qps),
            capacities_mb=tuple(scenario.capacities_mb),
            technologies=scenario.resolve_technologies(),
            model=scenario.workloads[0],
            serving=scenario.serving_config(),
            engine=scenario.engine_config(),
            fleet=scenario.fleet_config(),
            faults=scenario.fault_config(),
        )

    def resolve_model(self) -> NLPModelSpec:
        specs = {s.name: s for s in NLP_TABLE_V}
        if self.model not in specs:
            raise KeyError(f"unknown NLP spec {self.model!r}; have {sorted(specs)}")
        return specs[self.model]


@dataclasses.dataclass
class SweepRow:
    """One grid point's exact outcome."""

    technology: str
    capacity_mb: float
    qps: float
    shared: bool  # True: scored off the shared schedule (certificate held)
    report: ServeReport
    # Fleet-mode extras (None on single-accelerator grids): the full
    # FleetReport wrapping ``report``, with cost-per-token and replica axes.
    fleet: FleetReport | None = None


def sweep_serving_grid(
    spec: ServingGridSpec,
    mode: str = "shared",
    backend: str = "auto",
    n_dram_channels: int = 8,
    n_prefetch_channels: int = 4,
    lowering: str = "block",
    timing: dict | None = None,
    recorder=None,
) -> list[SweepRow]:
    """Evaluate the whole grid; rows ordered (capacity, qps, technology).

    ``mode="shared"`` (default) reuses one schedule per (qps, capacity)
    across technologies with the exactness certificate + per-technology
    fallback to the technology's own loop; ``mode="exact"`` runs every
    triple through its own loop (the reference path the certificate is
    validated against).

    ``backend`` selects the replay-scan implementation (``"auto"`` picks
    jax on an accelerator and numpy on CPU — see
    :func:`repro.sim.engine.resolve_backend`); every backend yields
    bit-identical rows, so this is purely a performance knob.

    Pass a dict as ``timing`` to receive the wall-clock split:
    ``loop_s`` (scheduler + allocator + lowering + per-tech pricing) vs
    ``score_s`` (trace build + batched replay + report) — the benchmark
    harness uses it to separate the serving-loop speedup from the shared
    replay cost.
    With :mod:`repro.obs` enabled the grid runs inside a ``sweep`` span and
    the same split is recorded as spans at the same boundaries (``loop``,
    ``score`` and the phases below them; see ``docs/observability.md``).

    ``recorder`` (a :class:`repro.obs.TimelineRecorder`) records the *first*
    grid point only — its serving loop and its first technology's replay —
    because one timeline per (capacity, qps, technology) triple would bury
    the interesting tracks; sweep timelines exist to inspect one
    representative schedule.  Hooks are read-only: rows are bit-identical
    with the recorder on or off.
    """
    if mode not in ("shared", "exact"):
        raise ValueError(f"unknown sweep mode {mode!r}")
    backend = resolve_backend(backend)
    if timing is None:
        timing = {}
    timing.setdefault("loop_s", 0.0)
    timing.setdefault("score_s", 0.0)
    nlp = spec.resolve_model()
    rng = np.random.default_rng(spec.serving.seed)
    interarrival_std, prompts, decodes = draw_request_shape(spec.serving, rng)

    rows: list[SweepRow] = []
    rec_pending = recorder  # consumed by the first grid point
    with obs.span("sweep"):
        for cap in spec.capacities_mb:
            for qps in spec.qps:
                cfg = dataclasses.replace(spec.serving, arrival_rate_rps=qps)
                rec, rec_pending = rec_pending, None
                rows.extend(_grid_point(
                    spec, nlp, cfg, cap, qps, mode, backend,
                    interarrival_std, prompts, decodes,
                    n_dram_channels, n_prefetch_channels, lowering,
                    timing, rec,
                ))
    return rows


def _grid_point(
    spec: ServingGridSpec,
    nlp: NLPModelSpec,
    cfg: ServingConfig,
    cap: float,
    qps: float,
    mode: str,
    backend: str,
    interarrival_std: np.ndarray,
    prompts: np.ndarray,
    decodes: np.ndarray,
    n_dram_channels: int,
    n_prefetch_channels: int,
    lowering: str,
    timing: dict,
    rec,
) -> list[SweepRow]:
    """One (capacity, qps) point of the grid, all technologies.

    ``mode="exact"`` runs each technology's own fleet loop
    (:func:`~repro.serve.fleet.run_fleet`).  Shared mode runs one fleet
    loop under the technology-invariant clock: router decisions (backlog
    counts), handoff delivery times, and autoscale actions (sched-clock
    TTFT p99) are all functions of the step durations, and the shared
    clock's terms (decode cadence, prefill time, DRAM busy) are
    technology-invariant.  So one run fixes the entire event interleaving,
    and the per-step per-bank certificate — over the replica-sliced
    resource space, with transfer blocks carrying ``+inf`` step budgets —
    proves per technology that the exact loop would have produced
    byte-for-byte the same schedule.  Certified technologies replay in one
    batch; violators fall back to their own exact loop.

    Spans (:mod:`repro.obs`): ``loop`` (``schedule``: scheduler, allocator,
    lowering; ``price``: neutral columns and per-technology pricing) and
    ``score`` (``trace``, then :func:`score_shared_batch`), at the same
    boundaries as ``timing``; ``fallback`` around each uncertified
    technology's own loop.

    The exact loop appends events step-major while the shared path gathers
    them class-major.  Per-resource order is unchanged (within a step reads
    precede writes either way, and replicas own disjoint resource slices),
    so every replayed metric — TTFT/TPOT, finish times, queue depths — is
    bitwise identical; only whole-trace float reductions (aggregate energy,
    byte totals) may differ in the last ulp between a certified-shared row
    and the exact loop's.
    """
    ref_system = build_system(spec.technologies[0], cap)
    # The DRAM model, and with it the decode cadence, is shared by every
    # technology on the grid.
    sim_config = SimConfig(
        coalesce_window_ns=(
            4 * ServeModel(ref_system, nlp, cfg, spec.engine).interval_ns),
        backend=backend, kind_stats=False,
    )

    def own_loop(tech: str, recorder=None) -> FleetReport:
        # The loop derates the registry build itself.  Rows never pay
        # fleet_serving's fault-free baseline rerun: the grid carries the
        # fault-free comparison point (run it with faults=None).
        _, fr = run_fleet(
            build_system(tech, cap), nlp, cfg, spec.engine, spec.fleet,
            sim_config, n_dram_channels, n_prefetch_channels, lowering,
            timing, recorder, spec.faults,
        )
        return fr

    results: list[tuple[str, bool, FleetReport]] = []
    if mode == "exact":
        for tech in spec.technologies:
            results.append((tech, False, own_loop(tech, rec)))
            rec = None
        return _rows(spec, cap, qps, results)

    # Replica failures strike at schedule-independent absolute times, so the
    # shared interleaving carries the same outage/requeue sequence as the
    # exact fleet whenever the certificate holds.
    t0 = time.perf_counter()
    with obs.span("loop"):
        with obs.span("schedule"):
            arrivals = arrivals_at_qps(interarrival_std, qps)
            dram = ref_system.dram
            t_dram_acc_ns = (
                dram.access_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
            )
            fleet = Fleet(ref_system, nlp, cfg, spec.engine, spec.fleet,
                          lowering=lowering, recorder=rec,
                          faults=spec.faults)

            def shared_dt(replica, blocks):
                decode_ns = (replica.model.interval_ns if blocks.has_decode
                             else 0.0)
                # Same accumulation order as TechPricer.price_step, so the
                # value is bit-identical to the exact loop's dram_ns term.
                dram_acc = 0.0
                if blocks.dram_rd_acc.size:
                    dram_acc += float(blocks.dram_rd_acc.sum())
                if blocks.dram_wr_acc.size:
                    dram_acc += float(blocks.dram_wr_acc.sum())
                return max(decode_ns, blocks.prefill_ns,
                           dram_acc * t_dram_acc_ns)

            fleet.run(arrivals, prompts, decodes, shared_dt)
        with obs.span("price"):
            model0 = fleet.replicas[0].model
            run = NeutralRun(fleet.blocks_list, fleet.dts_array, model0,
                             n_dram_channels, n_prefetch_channels,
                             n_replicas=fleet.capacity)
            # Iso-reliability pricing: each technology prices its derated
            # twin with its own fault model (a fresh model per tech — the
            # retry stream restarts at offset 0 exactly as the exact loop's
            # does), so certified shared rows stay bitwise equal to exact.
            tech_systems = [
                derate_system(build_system(tech, cap), spec.faults)
                for tech in spec.technologies
            ]
            fms = [fault_model_for(system, spec.faults,
                                   n_replicas=fleet.capacity)
                   for system in tech_systems]
            pricings = [run.price(system, fm)
                        for system, fm in zip(tech_systems, fms)]
    timing["loop_s"] += time.perf_counter() - t0

    t0 = time.perf_counter()
    with obs.span("score"):
        mean_alive = fleet.mean_alive()
        certified = [(tech, p, fm) for tech, p, fm in
                     zip(spec.technologies, pricings, fms) if p.certified]
        shared: dict[str, FleetReport] = {}
        if certified:
            with obs.span("trace"):
                traces = [
                    run.build_trace(p, serving_run_meta(
                        nlp, cfg, spec.engine, p.system, model0, fleet.stats,
                        lowering, schedule="shared", **fleet.fleet_meta()),
                        leakage_scale=mean_alive)
                    for _, p, _ in certified
                ]
            reports = score_shared_batch(
                traces, [p.system for _, p, _ in certified], sim_config,
                # The recorder taps the first technology's replay only when
                # that technology is certified (first certified trace ==
                # first technology then).
                recorder=(rec if pricings[0].certified else None),
                requests=fleet.logical,
                finished=fleet.finished_logical,
                arrival_by_rid=fleet.arrival_by_rid,
                offered_qps=cfg.arrival_rate_rps,
                pages_spilled=fleet.pages_spilled(),
                pages_allocated=fleet.pages_allocated(),
                stats=fleet.stats,
            )
            shared = {
                tech: fleet.finalize(
                    rep, p.system,
                    fault_stats=fm.stats() if fm is not None else None)
                for (tech, p, fm), rep in zip(certified, reports)
            }
    timing["score_s"] += time.perf_counter() - t0

    for tech in spec.technologies:
        if tech in shared:
            results.append((tech, True, shared[tech]))
            continue
        # Congestion would have stretched this technology's steps: run its
        # own loop.  The shared loop already recorded this grid point's
        # lifecycles, so the fallback records nothing.
        with obs.span("fallback"):
            results.append((tech, False, own_loop(tech)))
    return _rows(spec, cap, qps, results)


def _rows(spec: ServingGridSpec, cap: float, qps: float,
          results: list) -> list[SweepRow]:
    # A single accelerator's rows carry no fleet extras.
    return [SweepRow(tech, cap, qps, shared, fr.report,
                     fleet=None if spec.fleet.trivial else fr)
            for tech, shared, fr in results]
