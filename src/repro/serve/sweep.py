"""Shared-grid QPS x capacity x technology sweep over the serving closed loop.

Evaluating a serving design grid point by point re-runs the scheduler, the
page allocator, and the lowering for every (qps, capacity, technology)
triple, even though most of that work is identical across the grid:

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
the shared step duration, the full closed loop with that technology would
have produced byte-for-byte the same schedule, so the shared result is
exact — not an approximation.  A
technology that violates the certificate (congestion would have stretched
its steps) falls back to its own closed loop, so ``sweep_serving_grid``
always returns closed-loop-exact rows; ``shared`` on each row records which
path produced it.

Scoring is batched: per (qps, capacity) the shared run's step blocks are
flattened **once** into technology-neutral trace columns
(:class:`repro.serve.replay.NeutralRun`), priced per technology with a few
vectorized multiplies, and every *certified* technology is replayed in a
single :func:`repro.sim.engine.replay_schedule_batch` call — the
write-combining mask, the time sort, and the segmented max-plus scan are
shared or batched instead of recomputed per technology.  ``backend`` picks
the scan implementation: ``"numpy"`` (``np.maximum.accumulate``), ``"jax"``
(one fused jitted XLA program around ``jax.lax.cummax``), ``"pallas"`` (the
chunked ``repro.kernels.segmented_replay`` kernel), or ``"auto"`` (jax when
importable, else numpy).  All backends produce bit-identical rows — pinned
by ``tests/test_replay_kernel.py``.
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
from repro.serve.lower import (
    BlockEmitter,
    RunStats,
    ScalarEmitter,
    ServeModel,
    ServeReport,
    closed_loop_serving,
    drive_serving_loop,
    serving_run_meta,
)
from repro.serve.fleet import Fleet, FleetConfig, FleetReport, fleet_serving
from repro.serve.replay import NeutralRun, score_shared_batch
from repro.serve.scheduler import ContinuousBatchScheduler, ServeEngineConfig


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
    # (1 replica, knobs off) routes through the original single-accelerator
    # shared path bit-identically.
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
    """One grid point's closed-loop-exact outcome."""

    technology: str
    capacity_mb: float
    qps: float
    shared: bool  # True: scored off the shared schedule (certificate held)
    report: ServeReport
    # Fleet-mode extras (None on single-accelerator grids): the full
    # FleetReport wrapping ``report``, with cost-per-token and replica axes.
    fleet: FleetReport | None = None


def _shared_run(model: ServeModel, sched: ContinuousBatchScheduler,
                lowering: str, t_dram_acc_ns: float, recorder=None):
    """Drive the loop once with the technology-invariant clock.

    The step feedback's DRAM term is ``total accesses x access time`` — no
    per-channel max — so it is identical for every technology and can be
    folded into the shared clock exactly.  Only the per-bank GLB busy time
    is technology-dependent; it is what the certificate checks per tech.
    ``recorder`` taps the shared loop's request lifecycles and residency
    counters (read-only, no effect on the schedule).
    """
    emitter = (BlockEmitter if lowering == "block" else ScalarEmitter)(model)
    stats = RunStats()
    blocks_list, dts = [], []

    def shared_dt(blocks):
        decode_ns = model.interval_ns if blocks.has_decode else 0.0
        # Same accumulation order as TechPricer.price_step, so the value is
        # bit-identical to the closed loop's dram_ns term.
        dram_acc = 0.0
        if blocks.dram_rd_acc.size:
            dram_acc += float(blocks.dram_rd_acc.sum())
        if blocks.dram_wr_acc.size:
            dram_acc += float(blocks.dram_wr_acc.sum())
        return max(decode_ns, blocks.prefill_ns, dram_acc * t_dram_acc_ns)

    for blocks, dt in drive_serving_loop(sched, emitter, shared_dt,
                                         model.alloc, recorder=recorder):
        stats.account(blocks, dt)
        blocks_list.append(blocks)
        dts.append(dt)
    return blocks_list, np.asarray(dts), stats


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
    closed-loop fallback; ``mode="exact"`` runs every triple through its own
    closed loop (the reference path the certificate is validated against).

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
    fleet_mode = not spec.fleet.trivial
    with obs.span("sweep"):
        for cap in spec.capacities_mb:
            for qps in spec.qps:
                cfg = dataclasses.replace(spec.serving, arrival_rate_rps=qps)
                rec, rec_pending = rec_pending, None
                if fleet_mode:
                    rows.extend(_fleet_grid_point(
                        spec, nlp, cfg, cap, qps, mode, backend,
                        interarrival_std, prompts, decodes,
                        n_dram_channels, n_prefetch_channels, lowering,
                        timing, rec,
                    ))
                elif mode == "exact":
                    rows.extend(_exact_grid_point(
                        spec, nlp, cfg, cap, qps, backend,
                        n_dram_channels, n_prefetch_channels, lowering,
                        timing, rec,
                    ))
                else:
                    rows.extend(_shared_grid_point(
                        spec, nlp, cfg, cap, qps, backend,
                        interarrival_std, prompts, decodes,
                        n_dram_channels, n_prefetch_channels, lowering,
                        timing, rec,
                    ))
    return rows


def _exact_grid_point(spec, nlp, cfg, cap, qps, backend, n_dram_channels,
                      n_prefetch_channels, lowering, timing, rec
                      ) -> list[SweepRow]:
    """One (capacity, qps) point, every technology on its own closed loop."""
    out = []
    for tech in spec.technologies:
        system = build_system(tech, cap)
        # sim_config=None reproduces the closed loop's own default
        # (4x-cadence coalescing, no kind stats); only a non-default replay
        # backend needs an explicit config.
        _, rep = closed_loop_serving(
            system, nlp, cfg, spec.engine,
            sim_config=(None if backend == "numpy" else
                        _sim_config(system, nlp, cfg, spec.engine, backend)),
            n_dram_channels=n_dram_channels,
            n_prefetch_channels=n_prefetch_channels,
            lowering=lowering,
            timing=timing,
            recorder=rec,
            faults=spec.faults,
        )
        rec = None
        out.append(SweepRow(tech, cap, qps, False, rep))
    return out


def _shared_grid_point(spec, nlp, cfg, cap, qps, backend, interarrival_std,
                       prompts, decodes, n_dram_channels, n_prefetch_channels,
                       lowering, timing, rec) -> list[SweepRow]:
    """One (capacity, qps) point off one shared schedule, all technologies.

    Spans (:mod:`repro.obs`): ``loop`` (``schedule``: scheduler, allocator,
    lowering; ``price``: neutral columns and per-technology pricing) and
    ``score`` (``trace``, then :func:`score_shared_batch`), at the same
    boundaries as ``timing``; ``fallback`` around each uncertified
    technology's own closed loop.
    """
    # One scheduler + allocator + lowering pass per (qps, capacity).
    t0 = time.perf_counter()
    with obs.span("loop"):
        with obs.span("schedule"):
            arrivals = arrivals_at_qps(interarrival_std, qps)
            ref_system = build_system(spec.technologies[0], cap)
            dram = ref_system.dram  # shared by every technology on the grid
            t_dram_acc_ns = (
                dram.access_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
            )
            model = ServeModel(ref_system, nlp, cfg, spec.engine)
            sched = ContinuousBatchScheduler(arrivals, prompts, decodes,
                                             spec.engine)
            blocks_list, dts, stats = _shared_run(model, sched, lowering,
                                                  t_dram_acc_ns, recorder=rec)
        with obs.span("price"):
            # Flatten the run's blocks once (class-major neutral columns),
            # then price every technology off the same columns.  The
            # shared clock already carries the (tech-invariant) DRAM busy
            # term; only the per-bank GLB busy time can push a technology
            # off the shared schedule — the pricing certificate checks
            # every step.
            run = NeutralRun(blocks_list, dts, model,
                             n_dram_channels, n_prefetch_channels)
            # Iso-reliability pricing: each technology prices its derated
            # twin with its own fault model (a fresh model per tech — the
            # retry stream restarts at offset 0 exactly as the exact loop's
            # does), so certified shared rows stay bitwise equal to exact.
            tech_systems = [
                derate_system(build_system(tech, cap), spec.faults)
                for tech in spec.technologies
            ]
            pricings = [
                run.price(system, fault_model_for(system, spec.faults))
                for system in tech_systems
            ]
    timing["loop_s"] += time.perf_counter() - t0
    sim_config = SimConfig(
        coalesce_window_ns=4 * model.interval_ns, backend=backend,
        kind_stats=False,
    )

    # All certified technologies replay in one batched pass.
    t0 = time.perf_counter()
    with obs.span("score"):
        certified = [(tech, p) for tech, p in
                     zip(spec.technologies, pricings) if p.certified]
        shared_reports: dict[str, ServeReport] = {}
        if certified:
            with obs.span("trace"):
                traces = [
                    run.build_trace(p, serving_run_meta(
                        nlp, cfg, spec.engine, p.system, model, stats,
                        lowering, schedule="shared"))
                    for _, p in certified
                ]
            reports = score_shared_batch(
                traces, [p.system for _, p in certified], sched, model,
                stats, sim_config,
                # The recorder taps the first technology's replay only when
                # that technology is certified (first certified trace ==
                # first technology then).
                recorder=(rec if pricings[0].certified else None),
            )
            shared_reports = {
                tech: rep for (tech, _), rep in zip(certified, reports)
            }
    timing["score_s"] += time.perf_counter() - t0

    out = []
    for tech, pricing in zip(spec.technologies, pricings):
        if pricing.certified:
            out.append(SweepRow(tech, cap, qps, True, shared_reports[tech]))
            continue
        # Congestion would have stretched this technology's steps: replay
        # its own closed loop (still block-lowered).  The shared loop
        # already recorded this grid point's lifecycles, so the fallback
        # only taps the replay.  The closed loop derates the base system
        # itself, so it gets the registry build, not the already-derated
        # pricing system.
        with obs.span("fallback"):
            _, rep = closed_loop_serving(
                build_system(tech, cap), nlp, cfg, spec.engine,
                sim_config=sim_config,
                n_dram_channels=n_dram_channels,
                n_prefetch_channels=n_prefetch_channels,
                lowering=lowering,
                timing=timing,
                faults=spec.faults,
            )
        out.append(SweepRow(tech, cap, qps, False, rep))
    return out


def _sim_config(system, nlp, cfg, engine, backend) -> SimConfig:
    model = ServeModel(system, nlp, cfg, engine)
    return SimConfig(coalesce_window_ns=4 * model.interval_ns, backend=backend,
                     kind_stats=False)


def _fleet_grid_point(
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
    """One (capacity, qps) point of a *fleet* grid, all technologies.

    The shared-schedule argument extends to fleets unchanged: router
    decisions (backlog counts), handoff delivery times, and autoscale
    actions (sched-clock TTFT p99) are all functions of the step durations,
    and the shared clock's terms (decode cadence, prefill time, DRAM busy)
    are technology-invariant.  So one fleet run under the shared clock fixes
    the entire event interleaving, and the per-step per-bank certificate —
    now over the replica-sliced resource space, with transfer blocks
    carrying ``+inf`` step budgets — proves per technology that the exact
    fleet would have produced byte-for-byte the same schedule.  Certified
    technologies replay in one batch; violators fall back to their own
    exact fleet loop.

    One caveat the single-accelerator grid does not have: when two replicas
    step at the *same* timestamp, the exact fleet appends their events
    step-major while the shared path gathers them class-major.  Per-resource
    order is unchanged (replicas own disjoint resource slices), so every
    replayed metric — TTFT/TPOT, finish times, queue depths — is still
    bitwise identical; only whole-trace float reductions (aggregate energy,
    byte totals) may differ in the last ulp between the certified-shared row
    and a hand-run exact fleet.
    """
    # Sweep rows never pay the fault-free baseline rerun (the grid itself
    # carries the fault-free comparison point: run it with faults=None).
    faults = (dataclasses.replace(spec.faults, baseline_inflation=False)
              if spec.faults is not None else None)
    if mode == "exact":
        out = []
        for tech in spec.technologies:
            system = build_system(tech, cap)
            _, fr = fleet_serving(
                system, nlp, cfg, spec.engine, spec.fleet,
                sim_config=(None if backend == "numpy" else
                            _sim_config(system, nlp, cfg, spec.engine,
                                        backend)),
                n_dram_channels=n_dram_channels,
                n_prefetch_channels=n_prefetch_channels,
                lowering=lowering, timing=timing, recorder=rec,
                faults=faults,
            )
            rec = None
            out.append(SweepRow(tech, cap, qps, False, fr.report, fleet=fr))
        return out

    # One fleet loop under the technology-invariant clock (replica failures
    # strike at schedule-independent absolute times, so the shared
    # interleaving carries the same outage/requeue sequence as the exact
    # fleet whenever the certificate holds).
    t0 = time.perf_counter()
    with obs.span("loop"):
        with obs.span("schedule"):
            arrivals = arrivals_at_qps(interarrival_std, qps)
            ref_system = build_system(spec.technologies[0], cap)
            dram = ref_system.dram  # shared by every technology on the grid
            t_dram_acc_ns = (
                dram.access_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
            )
            fleet = Fleet(ref_system, nlp, cfg, spec.engine, spec.fleet,
                          lowering=lowering, recorder=rec, faults=faults)

            def shared_dt(replica, blocks):
                decode_ns = (replica.model.interval_ns if blocks.has_decode
                             else 0.0)
                # Same accumulation order as TechPricer.price_step, so the
                # value is bit-identical to the exact fleet's dram_ns term.
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
            tech_systems = [derate_system(build_system(tech, cap), faults)
                            for tech in spec.technologies]
            fms = [fault_model_for(system, faults, n_replicas=fleet.capacity)
                   for system in tech_systems]
            pricings = [run.price(system, fm)
                        for system, fm in zip(tech_systems, fms)]
    timing["loop_s"] += time.perf_counter() - t0
    sim_config = SimConfig(
        coalesce_window_ns=4 * model0.interval_ns, backend=backend,
        kind_stats=False,
    )

    t0 = time.perf_counter()
    with obs.span("score"):
        mean_alive = fleet.mean_alive()
        certified = [(tech, p) for tech, p in
                     zip(spec.technologies, pricings) if p.certified]
        shared_fleet: dict[str, FleetReport] = {}
        if certified:
            with obs.span("trace"):
                traces = [
                    run.build_trace(p, serving_run_meta(
                        nlp, cfg, spec.engine, p.system, model0, fleet.stats,
                        lowering, schedule="shared", **fleet.fleet_meta()),
                        leakage_scale=mean_alive)
                    for _, p in certified
                ]
            reports = score_shared_batch(
                traces, [p.system for _, p in certified], None, None,
                fleet.stats, sim_config,
                recorder=(rec if pricings[0].certified else None),
                requests=fleet.logical,
                finished=fleet.finished_logical,
                arrival_by_rid=fleet.arrival_by_rid,
                offered_qps=cfg.arrival_rate_rps,
                pages_spilled=fleet.pages_spilled(),
                pages_allocated=fleet.pages_allocated(),
            )
            fm_by_tech = dict(zip(spec.technologies, fms))
            shared_fleet = {
                tech: fleet.finalize(
                    rep, p.system,
                    fault_stats=(fm_by_tech[tech].stats()
                                 if fm_by_tech[tech] is not None else None))
                for (tech, p), rep in zip(certified, reports)
            }
    timing["score_s"] += time.perf_counter() - t0

    out = []
    for tech, pricing in zip(spec.technologies, pricings):
        if pricing.certified:
            fr = shared_fleet[tech]
            out.append(SweepRow(tech, cap, qps, True, fr.report, fleet=fr))
        else:
            # Congestion would have re-interleaved this technology's fleet:
            # run its own exact fleet loop (off the registry build — the
            # exact loop derates the base system itself).
            with obs.span("fallback"):
                _, fr = fleet_serving(
                    build_system(tech, cap), nlp, cfg, spec.engine,
                    spec.fleet, sim_config=sim_config,
                    n_dram_channels=n_dram_channels,
                    n_prefetch_channels=n_prefetch_channels,
                    lowering=lowering, timing=timing,
                    faults=faults,
                )
            out.append(SweepRow(tech, cap, qps, False, fr.report, fleet=fr))
    return out
