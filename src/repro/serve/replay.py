"""Batched technology pricing + single-pass replay for the serving sweep.

The shared-schedule sweep (``repro.serve.sweep``) previously priced and
replayed each technology separately: per technology it re-concatenated every
step's lowered blocks, appended them to a fresh :class:`TraceBuilder`, ran
the FIFO replay (sort + coalesce + segmented scan), and distilled a report —
even though across technologies the event *stream* is identical and only
bank placements, service times, and energies differ.

This module batches all of that:

* :class:`NeutralRun` flattens one shared run's ``StepBlocks`` **once** into
  technology-neutral columns (issue times, kinds, coalescing lines, tags,
  per-class hash/access arrays), laid out class-major — GLB reads, GLB
  writes, DRAM reads, DRAM writes, prefetch — with fresh-line ids numbered
  from the same counter start as :class:`~repro.serve.lower.TechPricer`.
* :meth:`NeutralRun.price` prices those columns for one concrete memory
  system: a handful of vectorized multiplies per class (``bank = hash %
  n_banks``, service/energy scaled) plus the schedule-invariance
  certificate's segmented bincount, producing a :class:`TechPricing` whose
  resource/service/energy columns slot straight into a :class:`Trace` view.
* :func:`score_shared_batch` replays **all** certified technologies in one
  :func:`repro.sim.engine.replay_schedule_batch` call — the write-combining
  mask is computed once (it depends only on the shared time/kind/line
  columns), the per-row scan runs through the numpy / ``jax.lax.cummax`` /
  Pallas backend, and each row is distilled into a :class:`ServeReport`
  operand-for-operand like ``simulate_trace`` + ``score_requests``.

Bit-exactness is the contract, not an aspiration: every float operation
(pricing multiplies, coalesced-energy sums, masked metric sums, percentile
calls) happens on the same values in the same order as the per-technology
path, so the sweep report is bitwise identical whichever path — or replay
backend — produced it (pinned by ``tests/test_replay_kernel.py`` and
``tests/test_obs.py``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.memory_system import HybridMemorySystem
from repro.obs import core as obs
from repro.sim.engine import (
    _EXPOSED_LUT,
    BatchedReplaySchedule,
    KindStats,
    SimConfig,
    SimResult,
    coalesce_dropped_indices,
    replay_schedule_batch,
)
from repro.sim.trace import (
    KIND_DRAM_RD,
    KIND_DRAM_WR,
    KIND_GLB_RD,
    KIND_GLB_WR,
    KIND_NAMES,
    KIND_PREFETCH_RD,
    KIND_PREFETCH_WR,
    Trace,
)
from repro.serve.lower import (
    ServeModel,
    ServeReport,
    assemble_report,
    replay_token_times,
    score_requests,
)

_CLASSES = ("glb_rd", "glb_wr", "dram_rd", "dram_wr", "pref")


@dataclasses.dataclass
class TechPricing:
    """One technology's pricing of a :class:`NeutralRun`.

    ``resource``/``service``/``energy`` are full-length trace columns (the
    neutral run supplies the shared ``t_issue``/``kind``/``line``/``tag``
    columns); ``certified`` is the schedule-invariance certificate — True iff
    no step's per-bank GLB busy time exceeds its shared duration, i.e. the
    shared schedule is closed-loop-exact for this technology.
    """

    system: HybridMemorySystem
    n_glb_banks: int
    resource: np.ndarray  # int32 (n,)
    service: np.ndarray  # float64 (n,)
    energy: np.ndarray  # float64 (n,)
    certified: bool


class NeutralRun:
    """Technology-neutral flattening of one shared-schedule serving run.

    Columns are class-major (GLB reads, GLB writes, DRAM reads, DRAM writes,
    prefetch); the shared ``line`` column numbers fresh lines from a counter
    that starts past the reserved KV-append namespace and advances through
    the classes in that order.  Flattening
    happens once per (qps, capacity); every technology prices the same
    columns.
    """

    def __init__(
        self,
        blocks: list,
        dts: np.ndarray,
        model: ServeModel,
        n_dram_channels: int = 8,
        n_prefetch_channels: int = 4,
        n_replicas: int | None = None,
    ):
        S = len(blocks)
        self.S = S
        self.dts = np.asarray(dts, np.float64)
        self.n_dram_channels = n_dram_channels
        self.n_prefetch_channels = n_prefetch_channels
        ts = np.fromiter((blk.t_ns for blk in blocks), np.float64, S)
        reps = np.fromiter((blk.replica for blk in blocks), np.int64, S)
        if n_replicas is None:
            n_replicas = int(reps.max(initial=0)) + 1
        self.n_replicas = max(1, int(n_replicas))
        self._fleet = self.n_replicas > 1

        def gather(field, dtype):
            if S == 0:
                return np.empty(0, dtype), np.empty(0, np.int64)
            parts = [getattr(blk, field) for blk in blocks]
            sizes = np.fromiter((p.shape[0] for p in parts), np.int64, S)
            return np.concatenate(parts), sizes

        self.hash_rd, n_rd = gather("glb_rd_hash", np.int64)
        self.acc_rd, _ = gather("glb_rd_acc", np.float64)
        self.hash_wr, n_wr = gather("glb_wr_hash", np.int64)
        self.acc_wr, _ = gather("glb_wr_acc", np.float64)
        wr_line, _ = gather("glb_wr_line", np.int64)
        wr_tag, _ = gather("glb_wr_tag", np.int64)
        self.hash_dr, n_dr = gather("dram_rd_hash", np.int64)
        self.acc_dr, _ = gather("dram_rd_acc", np.float64)
        self.hash_dw, n_dw = gather("dram_wr_hash", np.int64)
        self.acc_dw, _ = gather("dram_wr_acc", np.float64)
        self.ch_pf, n_pf = gather("pref_ch", np.int64)
        self.acc_pf, _ = gather("pref_acc", np.float64)

        sizes = (self.hash_rd.size, self.hash_wr.size, self.hash_dr.size,
                 self.hash_dw.size, self.ch_pf.size)
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        self.sl = {
            name: slice(int(bounds[i]), int(bounds[i + 1]))
            for i, name in enumerate(_CLASSES)
        }
        n = int(bounds[-1])
        self.n = n

        # Per-step index per GLB class: the certificate's segmented-bincount
        # keys (shared across technologies up to the `* n_banks` factor).
        ar = np.arange(S)
        self.step_rd = ar.repeat(n_rd)
        self.step_wr = ar.repeat(n_wr)
        # Per-event replica index per class (fleet resource offsets); only
        # materialized when the run actually spans multiple replicas.
        if self._fleet:
            self.rep_rd = reps.repeat(n_rd)
            self.rep_wr = reps.repeat(n_wr)
            self.rep_dr = reps.repeat(n_dr)
            self.rep_dw = reps.repeat(n_dw)
            self.rep_pf = reps.repeat(n_pf)

        # -- shared trace columns -------------------------------------------
        self.t_issue = np.empty(n, np.float64)
        self.kind = np.empty(n, np.int8)
        self.tag = np.full(n, -1, np.int64)
        for name, sizes_c, kind_c in (
            ("glb_rd", n_rd, KIND_GLB_RD),
            ("glb_wr", n_wr, KIND_GLB_WR),
            ("dram_rd", n_dr, KIND_DRAM_RD),
            ("dram_wr", n_dw, KIND_DRAM_WR),
            ("pref", n_pf, KIND_PREFETCH_RD),
        ):
            sl = self.sl[name]
            self.t_issue[sl] = ts.repeat(sizes_c)
            self.kind[sl] = kind_c
        self.tag[self.sl["glb_wr"]] = wr_tag

        # Fresh-line numbering: as in TechPricer, the counter starts past the
        # reserved KV-append namespace; it advances through each class in
        # order (GLB writes consume ids only for their fresh, line < 0,
        # events).
        line = np.empty(n, np.int64)
        c = model.cfg.n_requests * model.n_layers
        sl = self.sl["glb_rd"]
        line[sl] = np.arange(c, c + self.hash_rd.size)
        c += self.hash_rd.size
        fresh = wr_line < 0
        nf = int(fresh.sum())
        if nf:
            wr_line = wr_line.copy()
            wr_line[fresh] = np.arange(c, c + nf)
            c += nf
        line[self.sl["glb_wr"]] = wr_line
        for name, size in (("dram_rd", self.hash_dr.size),
                           ("dram_wr", self.hash_dw.size),
                           ("pref", self.ch_pf.size)):
            line[self.sl[name]] = np.arange(c, c + size)
            c += size
        self.line = line

    def price(self, system: HybridMemorySystem,
              fault_model=None) -> TechPricing:
        """Price the neutral columns for one memory system + certificate.

        Same formulas (and float operation order) as
        ``TechPricer.price_step``: ``bank = hash % n_banks``,
        service/energy scaled by the technology's latency/energy table, DRAM
        channels folded from the bank hash, prefetch channels shared.

        ``fault_model`` (a per-technology :class:`repro.faults.FaultModel`)
        injects the same seeded write-retry accesses and bank-offline remaps
        as the exact loop: the counter RNG is keyed on the within-class event
        index / (bank, time window), both of which this class-major layout
        preserves, so shared-mode rows are bitwise equal to exact-mode rows
        whenever the certificate holds.
        """
        glb = system.glb
        nb = max(1, int(glb.banks))
        R = self.n_replicas
        nb_tot = nb * R
        dram = system.dram
        t_dram_acc_ns = dram.access_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
        t_dram_acc_ch_ns = t_dram_acc_ns * self.n_dram_channels
        e_dram_pj = dram.energy_pj_per_access()

        bank_rd = self.hash_rd % nb
        bank_wr = self.hash_wr % nb
        acc_wr = self.acc_wr
        if fault_model is not None:
            rep_rd = self.rep_rd if self._fleet else 0
            rep_wr = self.rep_wr if self._fleet else 0
            bank_rd = fault_model.remap_banks(
                bank_rd, self.t_issue[self.sl["glb_rd"]], rep_rd)
            bank_wr = fault_model.remap_banks(
                bank_wr, self.t_issue[self.sl["glb_wr"]], rep_wr)
            acc_wr = fault_model.write_acc_at(acc_wr, 0)
        svc_rd = self.acc_rd * glb.read_latency_ns
        svc_wr = acc_wr * glb.write_latency_ns
        if self._fleet:
            bank_rd = bank_rd + self.rep_rd * nb
            bank_wr = bank_wr + self.rep_wr * nb

        # Schedule-invariance certificate (one segmented bincount over every
        # step): no step's per-bank GLB busy may exceed the shared step
        # duration.  Fleet transfer blocks carry ``inf`` durations in
        # ``dts`` — they never pace the clock, so they cannot decertify.
        busy = np.zeros(self.S * nb_tot)
        if bank_rd.size:
            busy += np.bincount(self.step_rd * nb_tot + bank_rd,
                                weights=svc_rd, minlength=self.S * nb_tot)
        if bank_wr.size:
            busy += np.bincount(self.step_wr * nb_tot + bank_wr,
                                weights=svc_wr, minlength=self.S * nb_tot)
        certified = bool(
            np.all(busy.reshape(self.S, nb_tot).max(axis=1) <= self.dts)
        )

        res = np.empty(self.n, np.int32)
        svc = np.empty(self.n, np.float64)
        en = np.empty(self.n, np.float64)
        sl = self.sl["glb_rd"]
        res[sl] = bank_rd
        svc[sl] = svc_rd
        en[sl] = self.acc_rd * glb.read_energy_pj_per_access
        sl = self.sl["glb_wr"]
        res[sl] = bank_wr
        svc[sl] = svc_wr
        en[sl] = acc_wr * glb.write_energy_pj_per_access
        for name, hashes, acc, rep in (
            ("dram_rd", self.hash_dr, self.acc_dr, "rep_dr"),
            ("dram_wr", self.hash_dw, self.acc_dw, "rep_dw"),
        ):
            sl = self.sl[name]
            ch = (hashes % nb) % self.n_dram_channels
            if self._fleet:
                ch = ch + getattr(self, rep) * self.n_dram_channels
            res[sl] = nb_tot + ch
            svc[sl] = acc * t_dram_acc_ch_ns
            en[sl] = acc * e_dram_pj
        sl = self.sl["pref"]
        ch = self.ch_pf % self.n_prefetch_channels
        if self._fleet:
            ch = ch + self.rep_pf * self.n_prefetch_channels
        res[sl] = nb_tot + self.n_dram_channels * R + ch
        svc[sl] = self.acc_pf * t_dram_acc_ns * self.n_prefetch_channels
        en[sl] = self.acc_pf * e_dram_pj

        return TechPricing(system=system, n_glb_banks=nb_tot, resource=res,
                           service=svc, energy=en, certified=certified)

    def build_trace(self, pricing: TechPricing, meta: dict,
                    leakage_scale: float = 1.0) -> Trace:
        """Assemble one technology's :class:`Trace` from column views.

        ``leakage_scale`` multiplies the per-chip GLB leakage (a fleet leaks
        on every alive replica); 1.0 leaves the single-chip value bit-exact.
        """
        leakage = pricing.system.glb.leakage_w
        if leakage_scale != 1.0:
            leakage = leakage * leakage_scale
        return Trace(
            t_issue_ns=self.t_issue,
            resource=pricing.resource,
            service_ns=pricing.service,
            energy_pj=pricing.energy,
            kind=self.kind,
            line=self.line,
            n_glb_banks=pricing.n_glb_banks,
            n_dram_channels=self.n_dram_channels * self.n_replicas,
            n_prefetch_channels=self.n_prefetch_channels * self.n_replicas,
            compute_time_s=0.0,
            leakage_w=leakage,
            meta=meta,
            tag=self.tag,
        )


def _distill_row(
    batch: BatchedReplaySchedule,
    r: int,
    trace: Trace,
    kind_k: np.ndarray,
    energy_k: np.ndarray,
    n_total: int,
    coalesced: int,
    coalesced_e: float,
    config: SimConfig,
) -> SimResult:
    """One row's metrics, operand-for-operand ``simulate_trace``."""
    res_s = batch.resource[r]
    t_s = batch.t_issue_ns[r]
    svc_s = batch.service_ns[r]
    kind_s = batch.kind[r]
    finish = batch.finish_ns[r]
    wait = batch.wait_ns[r]
    depth = batch.queue_depth[r]

    exposed = _EXPOSED_LUT[kind_s]
    hidden = ~exposed
    latency_ns = (
        float(finish[exposed].max() - t_s[exposed].min()) if exposed.any() else 0.0
    )
    hidden_ns = (
        float(finish[hidden].max() - t_s[hidden].min()) if hidden.any() else 0.0
    )
    runtime_s = max(trace.compute_time_s, latency_ns * 1e-9, hidden_ns * 1e-9)

    is_dram_kind = (kind_k == KIND_DRAM_RD) | (kind_k == KIND_DRAM_WR) | (
        kind_k == KIND_PREFETCH_RD) | (kind_k == KIND_PREFETCH_WR)
    dram_e = float(energy_k[is_dram_kind].sum()) * 1e-12
    glb_e = float(energy_k[~is_dram_kind].sum()) * 1e-12
    leak_e = trace.leakage_w * runtime_s

    total_lat = wait + svc_s
    exp_lat = total_lat[exposed] if exposed.any() else np.zeros(1)
    eps = 1e-3
    exp_p50, exp_p99 = np.percentile(exp_lat, (50, 99))
    n_glb = trace.n_glb_banks
    glb_mask = res_s < n_glb
    dram_mask = (res_s >= n_glb) & (res_s < n_glb + trace.n_dram_channels)
    glb_busy = float(svc_s[glb_mask].sum())
    dram_busy = float(svc_s[dram_mask].sum())

    per_kind: dict[str, KindStats] = {}
    for kv, name in KIND_NAMES.items() if config.kind_stats else ():
        m = kind_s == kv
        if not m.any():
            continue
        lat = total_lat[m]
        p50, p99 = np.percentile(lat, (50, 99))
        per_kind[name] = KindStats(
            n_events=int(m.sum()),
            busy_ns=float(svc_s[m].sum()),
            mean_latency_ns=float(lat.mean()),
            p50_latency_ns=float(p50),
            p99_latency_ns=float(p99),
        )

    return SimResult(
        latency_s=latency_ns * 1e-9,
        runtime_s=runtime_s,
        energy_j=dram_e + glb_e + leak_e,
        dram_energy_j=dram_e,
        glb_energy_j=glb_e,
        leakage_energy_j=leak_e,
        hidden_stream_s=hidden_ns * 1e-9,
        compute_time_s=trace.compute_time_s,
        bank_conflict_rate=float((wait > eps).mean()),
        mean_wait_ns=float(wait.mean()),
        p50_latency_ns=float(exp_p50),
        p99_latency_ns=float(exp_p99),
        mean_queue_depth=float(depth.mean()),
        max_queue_depth=int(depth.max()),
        glb_utilization=glb_busy / (n_glb * latency_ns) if latency_ns > 0 else 0.0,
        dram_utilization=(
            dram_busy / (trace.n_dram_channels * latency_ns)
            if latency_ns > 0 else 0.0
        ),
        n_events=n_total,
        n_simulated=int(kind_k.shape[0]),
        coalesced_writes=coalesced,
        coalesced_energy_pj=coalesced_e,
        per_kind=per_kind,
    )


def score_shared_batch(
    traces: list,
    systems: list,
    sim_config: SimConfig,
    recorder=None,
    **logical,
) -> list[ServeReport]:
    """Score N technology-priced traces of one shared run in one replay.

    All traces must share their ``t_issue``/``kind``/``line``/``tag`` columns
    (they come from one :class:`NeutralRun`), so the write-combining mask is
    computed once; the per-technology resource/service columns are stacked
    into a single :func:`replay_schedule_batch` call, and each row distilled
    into a :class:`ServeReport` bit-identical to
    :func:`~repro.serve.lower.score_requests` on that trace alone.
    ``systems`` pairs each trace with the memory system that priced it.
    ``recorder`` taps the first trace's replay (matching the sweep's
    first-grid-point recording contract).  ``logical`` are
    :func:`~repro.serve.lower.assemble_report`'s request keywords, the run's
    logical request population.
    """
    if not traces:
        return []
    t0 = traces[0]
    n_total = len(t0)
    if n_total == 0:
        return [
            score_requests(tr, system=system, sim_config=sim_config,
                           recorder=(recorder if i == 0 else None),
                           **logical)
            for i, (tr, system) in enumerate(zip(traces, systems))
        ]

    with obs.span("coalesce"):
        dropped = np.empty(0, np.int64)
        kept = np.arange(n_total, dtype=np.int64)
        if sim_config.coalesce_window_ns > 0:
            dropped = coalesce_dropped_indices(
                t0.t_issue_ns, t0.kind, t0.line, sim_config.coalesce_window_ns
            )
            keep = np.ones(n_total, bool)
            keep[dropped] = False
            kept = np.flatnonzero(keep)

        t_k = t0.t_issue_ns[kept]
        kind_k = t0.kind[kept]
        res_k = np.stack([tr.resource[kept] for tr in traces])
        svc_k = np.stack([tr.service_ns[kept] for tr in traces])
    batch = replay_schedule_batch(t_k, res_k, svc_k, kind_k,
                                  backend=sim_config.backend)
    if recorder is not None:
        recorder.record_replay(batch.row(0), t0)

    with obs.span("distill"):
        reports = []
        for r, (trace, system) in enumerate(zip(traces, systems)):
            energy_k = trace.energy_pj[kept]
            coalesced_e = float(trace.energy_pj[dropped].sum())
            result = _distill_row(batch, r, trace, kind_k, energy_k, n_total,
                                  int(dropped.size), coalesced_e, sim_config)
            # Per-request token completions from the replay's tagged events,
            # exactly as in ``score_requests``.
            orig_idx = kept[batch.order[r]]
            ttft, tpot = replay_token_times(trace.tag[orig_idx],
                                            batch.finish_ns[r],
                                            logical["arrival_by_rid"])
            reports.append(assemble_report(result, ttft, tpot, trace, system,
                                           **logical))
    return reports
