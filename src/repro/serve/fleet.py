"""The serving loop: N replicas, routing, disaggregation, autoscaling.

This module holds the one serving loop of the repository.  A *fleet* is
``n_replicas`` replicas, each with its own GLB capacity, paged-KV allocator,
and bank queues, fed by a front-end router with pluggable policies and
(optionally) split into prefill and decode pools with cross-replica KV
streaming, plus a QPS-driven autoscaler that adds/drains replicas against
the TTFT SLO.  A single accelerator is the 1-replica fleet with every knob
off (``FleetConfig()``): :func:`closed_loop_serving` and the sweep's
single-accelerator points run exactly this loop.  The lowering, pricing and
scoring it calls live in :mod:`repro.serve.lower`.

Design invariants:

* **One resource space, one replay.**  Every replica's events carry its
  ``StepBlocks.replica`` index; :class:`~repro.serve.lower.TechPricer` (and
  the sweep's :class:`~repro.serve.replay.NeutralRun`) offset each event's
  bank/channel by ``replica * per_replica_count``, so pricing a whole fleet
  step stays one segmented-bincount pass and the entire fleet is scored by
  a single FIFO replay.
* **Event-driven global loop.**  Arrivals are routed, KV handoffs
  delivered, and replicas stepped in global-time order (arrival routing
  wins ties), which guarantees that when a replica plans a step at time
  ``t`` every arrival ``<= t`` destined for it has already been routed.
  With one replica that is the single-accelerator closed loop: one
  scheduler stepping from its first arrival, idling to the next arrival
  when it has no work.  Its outputs are golden-pinned to literal digests
  (``tests/test_fleet.py``, ``tests/test_serving_golden.py``).  Event
  order depends only on step durations, which extends the sweep's
  schedule-invariance certificate to fleets.
* **Disaggregation as a traffic class.**  A disaggregated request runs as
  two scheduler halves: a prefill-half (``decode=0``) on a prefill replica
  and a decode-half (born ``prefilled=prompt``) injected into a decode
  replica once the KV transfer lands.  The transfer itself is lowered as
  bank-level events — GLB/DRAM *reads* of the request's pages on the
  source replica, fresh-line *writes* on the destination — priced by the
  same bank simulator as every other class, while the handoff latency is
  paced by the interconnect (``bytes / transfer_gb_s``).  Transfer blocks
  never pace the step clock (their ``dts`` entry is ``+inf``), so they
  cannot decertify a shared schedule; they contend in the replay instead.
* **Autoscaling on the scheduler clock.**  At fixed simulated-time
  intervals the autoscaler compares the recent sched-clock TTFT p99
  against the SLO: above it, a replica is added (drains are cancelled
  first); below ``autoscale_low_frac`` of it, the highest-index scalable
  replica drains (the router stops feeding it; it finishes its work, then
  deactivates).  Decisions depend only on the technology-invariant shared
  clock, so the certificate also certifies routing/scaling invariance.

Fleet-level cost is reported as **cost-per-token** = ``mean alive replicas
x per-chip GLB area (mm^2) x energy per generated token (J)`` — the
"chips x area x energy" index the DSE knee search minimizes per
technology (see ``docs/serving.md`` for the exact definition).
"""

from __future__ import annotations

import dataclasses
import difflib
import heapq
import math
import time

import numpy as np

from repro.core.memory_system import HybridMemorySystem
from repro.core.workload import NLPModelSpec
from repro.faults import FaultConfig, derate_system, replica_fail_times_ns
from repro.obs import core as obs
from repro.sim.engine import SimConfig
from repro.sim.trace import ServingConfig, Trace, draw_requests
from repro.serve.lower import (
    _MAX_STEPS,
    BlockEmitter,
    RunStats,
    ScalarEmitter,
    ServeModel,
    ServeReport,
    StepBlocks,
    TechPricer,
    score_requests,
    serving_run_meta,
    summarize_report,
)
from repro.serve.scheduler import (
    ContinuousBatchScheduler,
    RequestState,
    ServeEngineConfig,
)

ROUTER_POLICIES = ("round_robin", "least_loaded", "prefix_affinity")


class UnknownRouterPolicyError(ValueError, KeyError):
    """Raised for a router policy name the fleet does not know.

    Mirrors ``repro.spec.UnknownTechnologyError``: carries a difflib
    near-miss suggestion so CLI/scenario typos fail with a pointer.
    """

    def __init__(self, name: str):
        hint = ""
        close = difflib.get_close_matches(name, ROUTER_POLICIES, n=3,
                                          cutoff=0.5)
        if close:
            hint = f" — did you mean {', '.join(map(repr, close))}?"
        super().__init__(
            f"unknown router policy {name!r}; known: "
            f"{', '.join(ROUTER_POLICIES)}{hint}"
        )


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs of the replica fleet (router, disaggregation, autoscaler).

    The default is a 1-replica fleet with every knob off — the single
    accelerator of :func:`closed_loop_serving` (and what a pre-fleet
    scenario JSON without a ``fleet`` block resolves to).
    """

    n_replicas: int = 1
    router: str = "round_robin"
    # Prefill/decode disaggregation: the first ``n_prefill_replicas``
    # replicas only prefill; finished prompts stream their KV pages to a
    # decode replica over a ``transfer_gb_s`` interconnect.
    disaggregation: bool = False
    n_prefill_replicas: int = 1
    transfer_gb_s: float = 64.0
    # QPS-driven autoscaler against the TTFT SLO (scheduler clock).
    autoscale: bool = False
    min_replicas: int = 1
    max_replicas: int = 8
    autoscale_window_ms: float = 5.0
    autoscale_ttft_slo_ms: float = 50.0
    autoscale_low_frac: float = 0.3
    # Synthetic conversation-group count for prefix-affinity routing
    # (placeholder until the multi-turn conversation model lands).
    affinity_groups: int = 8

    def validate(self) -> None:
        if self.router not in ROUTER_POLICIES:
            raise UnknownRouterPolicyError(self.router)
        if self.n_replicas < 1:
            raise ValueError("n_replicas must be >= 1")
        if self.disaggregation:
            if self.n_replicas < 2:
                raise ValueError("disaggregation needs n_replicas >= 2")
            if not (1 <= self.n_prefill_replicas < self.n_replicas):
                raise ValueError(
                    "n_prefill_replicas must leave at least one decode "
                    "replica (1 <= n_prefill_replicas < n_replicas)"
                )
        if self.transfer_gb_s <= 0:
            raise ValueError("transfer_gb_s must be positive")
        if self.autoscale:
            if self.min_replicas < 1:
                raise ValueError("min_replicas must be >= 1")
            if self.max_replicas < self.n_replicas:
                raise ValueError("max_replicas must be >= n_replicas")
            if self.autoscale_window_ms <= 0:
                raise ValueError("autoscale_window_ms must be positive")
            if self.autoscale_ttft_slo_ms <= 0:
                raise ValueError("autoscale_ttft_slo_ms must be positive")
            if not (0.0 <= self.autoscale_low_frac < 1.0):
                raise ValueError("autoscale_low_frac must be in [0, 1)")
        if self.affinity_groups < 1:
            raise ValueError("affinity_groups must be >= 1")

    @property
    def capacity_replicas(self) -> int:
        """Resource-space size: the most replicas that can ever be alive."""
        return max(self.n_replicas,
                   self.max_replicas if self.autoscale else self.n_replicas)

    @property
    def trivial(self) -> bool:
        """True for a single accelerator: one replica, every knob off.

        It changes only what the outputs carry — no fleet trace meta, no
        ``SweepRow.fleet``, no fleet timeline tracks — never the code path.
        """
        return (self.n_replicas == 1 and not self.disaggregation
                and not self.autoscale)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, data: dict) -> "FleetConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(data) - known
        if unknown:
            raise ValueError(
                f"unknown fleet field(s): {', '.join(sorted(unknown))}"
            )
        cfg = cls(**data)
        cfg.validate()
        return cfg


@dataclasses.dataclass
class FleetReport:
    """Fleet outcome: the aggregate :class:`ServeReport` plus fleet axes.

    ``report`` carries the replay-scored SLO metrics over the whole fleet
    (fleet-level p99 TTFT/TPOT — one replay spans every replica's banks);
    the fields here add the replica dimension and the cost model.
    """

    report: ServeReport
    n_replicas: int  # configured initial size
    n_replicas_peak: int
    mean_alive_replicas: float
    router: str
    disaggregated: bool
    autoscaled: bool
    routed_per_replica: tuple
    completed_per_replica: tuple
    busy_frac_per_replica: tuple
    kv_xfer_transfers: int
    kv_xfer_bytes: float
    autoscale_events: tuple  # ((t_ns, alive_after), ...)
    tokens: int  # decode tokens generated fleet-wide
    area_mm2_per_chip: float
    energy_per_token_j: float
    cost_per_token: float  # mean_alive x area_mm2 x J/token
    # -- fault campaign outcome (all-zero when faults are off) --------------
    replica_failures: tuple = ()  # ((t_ns, replica_idx), ...)
    requeued_requests: int = 0
    reprefill_tokens: int = 0  # lost-KV tokens recomputed after failures
    fault_retry_accesses: float = 0.0  # write-verify retry accesses injected
    banks_remapped: int = 0  # GLB accesses shifted off offline banks
    goodput_tps: float = 0.0  # generated tokens / serving span (faults incl.)
    ttft_p99_inflation: float = 0.0  # faulted p99 TTFT / fault-free p99 TTFT


_EMPTY_I = np.empty(0, np.int64)
_EMPTY_F = np.empty(0, np.float64)


def _transfer_blocks(t_ns: float, replica: int, glb_h: np.ndarray,
                     glb_a: np.ndarray, dram_h: np.ndarray,
                     dram_a: np.ndarray, write: bool,
                     xfer_bytes: float) -> StepBlocks:
    """Lower one side of a KV handoff into a (read or write) event block."""
    n = glb_h.shape[0]
    return StepBlocks(
        t_ns=t_ns,
        prefill_ns=0.0,
        has_decode=False,
        glb_rd_hash=_EMPTY_I if write else glb_h,
        glb_rd_acc=_EMPTY_F if write else glb_a,
        glb_wr_hash=glb_h if write else _EMPTY_I,
        glb_wr_acc=glb_a if write else _EMPTY_F,
        glb_wr_line=np.full(n, -1, np.int64) if write else _EMPTY_I,
        glb_wr_tag=np.full(n, -1, np.int64) if write else _EMPTY_I,
        dram_rd_hash=_EMPTY_I if write else dram_h,
        dram_rd_acc=_EMPTY_F if write else dram_a,
        dram_wr_hash=dram_h if write else _EMPTY_I,
        dram_wr_acc=dram_a if write else _EMPTY_F,
        pref_ch=_EMPTY_I,
        pref_acc=_EMPTY_F,
        kv_rd_bytes_glb=0.0,
        kv_rd_bytes_dram=0.0,
        residency=1.0,
        replica=replica,
        kv_xfer_bytes=xfer_bytes,
    )


class _Replica:
    """One accelerator's slice of the fleet: scheduler + allocator + clock."""

    def __init__(self, idx: int, role: str, model: ServeModel, emitter,
                 ecfg: ServeEngineConfig, activated_ns: float):
        self.idx = idx
        self.role = role  # "both" | "prefill" | "decode"
        self.model = model
        self.emitter = emitter
        self.sched = ContinuousBatchScheduler([], [], [], ecfg)
        self.t: float | None = None  # local clock (end of last step)
        self.alive = True
        self.draining = False
        self.busy_ns = 0.0
        self.n_steps = 0
        self.routed = 0
        self.completed = 0
        self.activated_ns = activated_ns

    def accepts(self, role: str) -> bool:
        return self.alive and not self.draining and self.role in ("both", role)

    def next_action_ns(self) -> float:
        """When this replica next needs to step (inf if it has no work)."""
        if self.sched.active:
            # Active work always plans a non-empty step at the local clock.
            return self.t if self.t is not None else 0.0
        nxt = self.sched.next_arrival_ns()
        if not math.isfinite(nxt):
            return math.inf
        return nxt if self.t is None else max(self.t, nxt)


class Fleet:
    """Event-driven fleet simulator over per-replica closed loops.

    Construction wires the replicas; :meth:`run` executes the global loop.
    The step clock is supplied by the caller: ``step_time(replica, blocks)``
    returns the step duration (the exact path prices the blocks against a
    shared :class:`TechPricer`; the sweep's shared path uses the
    technology-invariant terms only), and ``price_block(blocks)`` — if given
    — is invoked on transfer blocks so their events reach the trace builder
    without pacing any clock.
    """

    def __init__(
        self,
        system: HybridMemorySystem,
        spec: NLPModelSpec,
        cfg: ServingConfig,
        engine_cfg: ServeEngineConfig,
        fleet_cfg: FleetConfig = FleetConfig(),
        lowering: str = "block",
        recorder=None,
        faults: FaultConfig | None = None,
    ):
        fleet_cfg.validate()
        self.faults = faults
        self.system = system
        self.spec = spec
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.fcfg = fleet_cfg
        if lowering not in ("block", "scalar"):
            raise ValueError(f"unknown lowering {lowering!r}")
        self.lowering = lowering
        self.recorder = recorder
        # Fleet timeline tracks (replica spans, alive and backlog counters);
        # a single accelerator records the plain per-step timeline instead.
        self._fleet_rec = None if fleet_cfg.trivial else recorder
        self.capacity = fleet_cfg.capacity_replicas

        self.replicas: list[_Replica] = []
        self.blocks_list: list[StepBlocks] = []
        self.dts: list[float] = []
        self.stats = RunStats()
        self.logical: list[RequestState] = []
        self.finished_logical: list[RequestState] = []
        self.arrival_by_rid: dict[int, float] = {}
        self.handoffs: list = []  # heap of (ready_ns, seq, prefill_half)
        self._hand_seq = 0
        self._rr = 0  # round-robin cursor
        self._ttft_samples: list[float] = []
        self._alive_events: list[tuple[float, int]] = []
        self.autoscale_events: list[tuple[float, int]] = []
        self.kv_xfer_transfers = 0
        self.kv_xfer_bytes = 0.0
        self.total_steps = 0
        self.t0 = 0.0
        # -- fault campaign state (inert with faults=None) -------------------
        self.retries: list = []  # heap of (t_ready_ns, seq, RequestState)
        self._retry_seq = 0
        self._retry_attempt: dict[int, int] = {}
        self._fail_times: list[float] = []  # per capacity slot, inf = never
        self.replica_failures: list[tuple[float, int]] = []
        self.requeued_requests = 0
        self.reprefill_tokens = 0
        self.prefail_tokens = 0  # tokens streamed to clients before a failure

    # -- replica lifecycle ---------------------------------------------------
    def _activate(self, t_ns: float, role: str) -> _Replica | None:
        """Bring one replica online (reviving a drained slot if possible)."""
        for r in self.replicas:  # recycle a deactivated slot's bank space
            if not r.alive and r.role in ("both", role):
                r.alive = True
                r.draining = False
                r.activated_ns = t_ns
                self._alive_events.append((t_ns, 1))
                self._sample_alive(t_ns)
                return r
        if len(self.replicas) >= self.capacity:
            return None
        idx = len(self.replicas)
        model = ServeModel(self.system, self.spec, self.cfg, self.ecfg,
                           replica_id=idx)
        emitter = (BlockEmitter if self.lowering == "block"
                   else ScalarEmitter)(model)
        rep = _Replica(idx, role, model, emitter, self.ecfg, t_ns)
        self.replicas.append(rep)
        self._alive_events.append((t_ns, 1))
        self._sample_alive(t_ns)
        return rep

    def _deactivate(self, r: _Replica, t_ns: float) -> None:
        r.alive = False
        r.draining = False
        self._alive_events.append((t_ns, -1))
        self._sample_alive(t_ns)

    def _sample_alive(self, t_ns: float) -> None:
        if self._fleet_rec is not None:
            self._fleet_rec.counter("alive_replicas", t_ns,
                                    float(self._alive_count()))

    def _alive_count(self) -> int:
        return sum(1 for r in self.replicas if r.alive)

    # -- routing -------------------------------------------------------------
    def _pool(self, role: str) -> list[_Replica]:
        pool = [r for r in self.replicas if r.accepts(role)]
        if not pool:  # every candidate draining: fall back to alive ones
            pool = [r for r in self.replicas
                    if r.alive and r.role in ("both", role)]
        return pool

    def _pick(self, rid: int, pool: list[_Replica]) -> _Replica:
        policy = self.fcfg.router
        if policy == "round_robin":
            r = pool[self._rr % len(pool)]
            self._rr += 1
            return r
        if policy == "least_loaded":
            return min(pool, key=lambda rep: (rep.sched.backlog(), rep.idx))
        # prefix_affinity: a stable synthetic conversation-group id keeps a
        # group's requests (and so their shared prefixes) on one replica.
        gid = rid % self.fcfg.affinity_groups
        return pool[gid % len(pool)]

    def _route_arrival(self, req: RequestState) -> None:
        if self.fcfg.disaggregation:
            target = self._pick(req.rid, self._pool("prefill"))
            half = RequestState(rid=req.rid, arrival_ns=req.arrival_ns,
                                prompt=req.prompt, decode=0)
            target.sched.add_request(half)
        else:
            target = self._pick(req.rid, self._pool("decode"))
            target.sched.add_request(req)
        target.routed += 1
        if self._fleet_rec is not None:
            backlog = sum(r.sched.backlog() for r in self.replicas if r.alive)
            self._fleet_rec.counter("router_backlog", req.arrival_ns, backlog)

    # -- disaggregated KV handoff ---------------------------------------------
    def _page_split_of(self, model: ServeModel, rid: int, n_tokens: int):
        """(hashes, accesses, resident) over the pages covering a context."""
        alloc, pt = model.alloc, model.ecfg.page_tokens
        slots = alloc.slots_of(rid)
        n_pages = slots.shape[0]
        toks = np.full(n_pages, pt, np.int64)
        if n_pages:
            toks[-1] = n_tokens - (n_pages - 1) * pt
        acc = toks * model._kv_acc_per_tok
        return (alloc.page_hash[slots].copy(), acc,
                alloc.page_resident[slots].copy())

    def _push_transfer(self, blk: StepBlocks) -> None:
        self.blocks_list.append(blk)
        self.dts.append(math.inf)  # transfers never pace the step clock
        if self.price_block is not None:
            self.price_block(blk)

    def _start_transfer(self, src: _Replica, req: RequestState,
                        t_ns: float) -> None:
        """Prefill finished: read the KV pages off the source replica's
        banks, free them, and schedule delivery after the wire time."""
        m = src.model
        page_h, acc, res = self._page_split_of(m, req.rid, req.prompt)
        spill = ~res
        self._push_transfer(_transfer_blocks(
            t_ns, src.idx, page_h[res], acc[res],
            page_h[spill], acc[spill] * m._glb_to_dram,
            write=False, xfer_bytes=0.0,
        ))
        m.alloc.free(req.rid)
        xfer_bytes = float(req.prompt * m.kv_token_bytes * m.n_layers)
        wire_ns = xfer_bytes / self.fcfg.transfer_gb_s  # B / (GB/s) == ns
        heapq.heappush(self.handoffs,
                       (t_ns + wire_ns, self._hand_seq, req, src.idx,
                        xfer_bytes))
        self._hand_seq += 1

    def _deliver_handoff(self) -> None:
        """Transfer landed: write the pages onto a decode replica's banks
        and inject the decode-half into its scheduler."""
        ready, _, req, src_idx, xfer_bytes = heapq.heappop(self.handoffs)
        dst = self._pick(req.rid, self._pool("decode"))
        m = dst.model
        m.alloc.ensure(req.rid, req.prompt, m.ecfg.page_tokens)
        page_h, acc, res = self._page_split_of(m, req.rid, req.prompt)
        spill = ~res
        self._push_transfer(_transfer_blocks(
            ready, dst.idx, page_h[res], acc[res],
            page_h[spill], acc[spill] * m._glb_to_dram,
            write=True, xfer_bytes=xfer_bytes,
        ))
        half = RequestState(rid=req.rid, arrival_ns=ready, prompt=req.prompt,
                            decode=req.decode, prefilled=req.prompt)
        dst.sched.add_request(half)
        dst.routed += 1
        self.kv_xfer_transfers += 1
        self.kv_xfer_bytes += xfer_bytes
        if self.recorder is not None:
            self.recorder.record_fleet_transfer(src_idx, dst.idx, ready,
                                                xfer_bytes,
                                                self.kv_xfer_bytes)

    # -- replica failure / graceful degradation --------------------------------
    def _can_fail(self, victim: _Replica) -> bool:
        """Never kill the last alive replica of any role pool the router
        needs — ``_pick`` over an empty pool has no answer, and the injected
        campaign models partial outages, not total loss."""
        roles = (("prefill", "decode") if self.fcfg.disaggregation
                 else ("both",))
        for role in roles:
            if victim.role not in ("both", role):
                continue
            survivors = sum(
                1 for r in self.replicas
                if r.alive and r is not victim and r.role in ("both", role)
            )
            if survivors == 0:
                return False
        return True

    def _fail_replica(self, r: _Replica, t_ns: float) -> None:
        """Kill one replica mid-run; requeue its lost work onto survivors.

        Tokens already decoded were streamed to clients, so a retried
        request keeps them: it re-enters the router (after a capped
        exponential backoff) as a fresh request whose prompt is the full
        lost context (original prompt + decoded tokens) and whose decode
        budget is the remainder.  The KV pages it had built (prefilled +
        decoded tokens) are gone and must be recomputed — that recompute
        burden is ``reprefill_tokens``.  The dead slot stays in the resource
        space; the autoscaler may later revive it, which models a
        replacement chip taking over the slot's banks.
        """
        self._fail_times[r.idx] = math.inf  # a slot fails at most once
        if not self._can_fail(r):
            return
        fc = self.faults
        sched = r.sched
        lost = list(sched.active) + sched.requests[sched._next:]
        sched.active = []
        sched._next = len(sched.requests)
        self._deactivate(r, t_ns)
        self.replica_failures.append((t_ns, r.idx))
        for q in lost:
            r.model.alloc.free(q.rid)
            attempt = self._retry_attempt.get(q.rid, 0)
            self._retry_attempt[q.rid] = attempt + 1
            delay_ns = min(
                fc.requeue_backoff_us * (2.0 ** attempt),
                fc.requeue_backoff_cap_us,
            ) * 1e3
            self.prefail_tokens += q.decoded
            self.reprefill_tokens += q.prefilled + q.decoded
            retry = RequestState(rid=q.rid, arrival_ns=t_ns + delay_ns,
                                 prompt=q.prompt + q.decoded,
                                 decode=q.decode - q.decoded)
            heapq.heappush(self.retries,
                           (retry.arrival_ns, self._retry_seq, retry))
            self._retry_seq += 1
            self.requeued_requests += 1
        if self.recorder is not None:
            self.recorder.record_fault("replica_failure", t_ns, r.idx,
                                       len(lost))

    # -- autoscaler ------------------------------------------------------------
    def _scalable_role(self) -> str:
        return "decode" if self.fcfg.disaggregation else "both"

    def _autoscale(self, t_ns: float) -> None:
        fc = self.fcfg
        samples, self._ttft_samples = self._ttft_samples, []
        if not samples:
            return
        p99 = float(np.percentile(np.asarray(samples), 99))
        slo_ns = fc.autoscale_ttft_slo_ms * 1e6
        role = self._scalable_role()
        if p99 > slo_ns:
            draining = [r for r in self.replicas
                        if r.alive and r.draining and r.role in ("both", role)]
            if draining:  # cancel a drain before paying for a new chip
                draining[0].draining = False
                self.autoscale_events.append((t_ns, self._alive_count()))
            elif self._alive_count() < fc.max_replicas:
                if self._activate(t_ns, role) is not None:
                    self.autoscale_events.append((t_ns, self._alive_count()))
        elif p99 < fc.autoscale_low_frac * slo_ns:
            floor = fc.min_replicas
            if fc.disaggregation:
                floor = max(floor, fc.n_prefill_replicas + 1)
            active = [r for r in self.replicas
                      if r.alive and not r.draining
                      and r.role in ("both", role)]
            if self._alive_count() > floor and len(active) > 1:
                victim = max(active, key=lambda r: r.idx)
                victim.draining = True
                if victim.sched.done:
                    self._deactivate(victim, t_ns)
                self.autoscale_events.append((t_ns, self._alive_count()))

    # -- the global loop -------------------------------------------------------
    def _step(self, r: _Replica, now: float) -> None:
        plan = r.sched.plan_step(now)
        if plan.empty:  # pragma: no cover — next_action_ns guarantees work
            raise RuntimeError("fleet stepped a replica with no plannable work")
        blocks = r.emitter.emit(plan)
        dt = self.step_time(r, blocks)
        t_end = now + dt
        finished = r.sched.commit_step(plan, t_end)
        if self.fcfg.autoscale:
            for req in plan.decode:
                if req.decoded == 1:
                    self._ttft_samples.append(
                        t_end - self.arrival_by_rid.get(req.rid,
                                                        req.arrival_ns))
        for req in finished:
            if (self.fcfg.disaggregation and r.role == "prefill"
                    and req.decode == 0):
                self._start_transfer(r, req, t_end)
            else:
                r.model.alloc.free(req.rid)
                r.completed += 1
                self.finished_logical.append(req)
        r.t = t_end
        r.busy_ns += dt
        r.n_steps += 1
        self.blocks_list.append(blocks)
        self.dts.append(dt)
        self.stats.account(blocks, dt)
        if self._fleet_rec is not None:
            self._fleet_rec.record_fleet_step(r.idx, now, t_end, plan, blocks,
                                              r.model.alloc, finished)
        elif self.recorder is not None:
            self.recorder.record_step(now, t_end, plan, blocks, r.model.alloc,
                                      finished)
        self.total_steps += 1
        if self.total_steps > _MAX_STEPS:  # pragma: no cover
            raise RuntimeError(f"fleet loop exceeded {_MAX_STEPS} steps")
        if r.draining and r.sched.done:
            self._deactivate(r, t_end)

    def run(self, arrivals, prompts, decodes, step_time,
            price_block=None) -> None:
        """Execute the fleet to completion over one request population.

        Events are processed in global-time order with a fixed tie-break —
        arrival routing, then handoff delivery, then the earliest replica's
        step (autoscale checks slot in at their deadline ahead of any
        later work) — so replica interleaving is deterministic; with one
        replica this is the single-accelerator closed loop.
        """
        fc = self.fcfg
        self.step_time = step_time
        self.price_block = price_block
        self.logical = [
            RequestState(rid=i, arrival_ns=float(a), prompt=int(p),
                         decode=int(d))
            for i, (a, p, d) in enumerate(zip(arrivals, prompts, decodes))
        ]
        self.arrival_by_rid = {r.rid: r.arrival_ns for r in self.logical}
        route_order = sorted(self.logical, key=lambda r: r.arrival_ns)
        self.t0 = route_order[0].arrival_ns if route_order else 0.0

        for i in range(fc.n_replicas):
            role = "both"
            if fc.disaggregation:
                role = "prefill" if i < fc.n_prefill_replicas else "decode"
            self._activate(self.t0, role)

        if self.faults is not None and self.faults.has_replica_faults:
            self._fail_times = replica_fail_times_ns(self.faults, self.t0,
                                                     self.capacity)
        else:
            self._fail_times = [math.inf] * self.capacity

        window_ns = fc.autoscale_window_ms * 1e6
        next_check = self.t0 + window_ns
        ri = 0
        while True:
            t_route = (route_order[ri].arrival_ns
                       if ri < len(route_order) else math.inf)
            t_retry = self.retries[0][0] if self.retries else math.inf
            t_hand = self.handoffs[0][0] if self.handoffs else math.inf
            t_step, r_star = math.inf, None
            for r in self.replicas:
                if not r.alive:
                    continue
                ta = r.next_action_ns()
                if ta < t_step:
                    t_step, r_star = ta, r
            t_work = min(t_route, t_retry, t_hand, t_step)
            if not math.isfinite(t_work):
                break
            # Pending failures strike before any work at or after their
            # deadline (and before an autoscale check they precede) — a
            # replica cannot execute a step that ends after it died.
            t_fail, r_fail = math.inf, None
            for r in self.replicas:
                if r.alive and self._fail_times[r.idx] < t_fail:
                    t_fail, r_fail = self._fail_times[r.idx], r
            if (r_fail is not None and t_fail <= t_work
                    and (not fc.autoscale or t_fail <= next_check)):
                self._fail_replica(r_fail, t_fail)
                continue
            if fc.autoscale and next_check <= t_work:
                self._autoscale(next_check)
                next_check += window_ns
                continue
            if t_route <= t_retry and t_route <= t_hand and t_route <= t_step:
                self._route_arrival(route_order[ri])
                ri += 1
            elif t_retry <= t_hand and t_retry <= t_step:
                # Backoff elapsed: the lost request re-enters the router and
                # lands on a surviving (or replacement) replica.
                _, _, req = heapq.heappop(self.retries)
                self._route_arrival(req)
            elif t_hand <= t_step:
                self._deliver_handoff()
            else:
                self._step(r_star, t_step)

    # -- results ---------------------------------------------------------------
    @property
    def dts_array(self) -> np.ndarray:
        return np.asarray(self.dts, np.float64)

    def span_end_ns(self) -> float:
        if self.finished_logical:
            return max(r.finish_ns for r in self.finished_logical)
        return self.t0

    def mean_alive(self) -> float:
        """Time-averaged alive-replica count over the serving span."""
        t_end = self.span_end_ns()
        if t_end <= self.t0:
            return float(self._alive_count())
        events = sorted(self._alive_events)
        integral, count, prev = 0.0, 0, self.t0
        for t, delta in events:
            t_c = min(max(t, self.t0), t_end)
            integral += count * (t_c - prev)
            prev = t_c
            count += delta
        integral += count * (t_end - prev)
        return integral / (t_end - self.t0)

    def peak_alive(self) -> int:
        count = peak = 0
        for _, delta in sorted(self._alive_events):
            count += delta
            peak = max(peak, count)
        return peak

    def pages_spilled(self) -> int:
        return sum(r.model.alloc.spill_count for r in self.replicas)

    def pages_allocated(self) -> int:
        return sum(r.model.alloc.pages_created for r in self.replicas)

    def tokens(self) -> int:
        # Tokens streamed before a replica died were delivered too — a retry
        # only re-generates the remainder, so the pre-failure count is added
        # back (zero in a fault-free run).
        return (int(sum(r.decoded for r in self.finished_logical))
                + self.prefail_tokens)

    def fleet_meta(self) -> dict:
        """Trace-meta entries of the fleet axis; none for one accelerator."""
        if self.fcfg.trivial:
            return {}
        return {
            "n_replicas": self.fcfg.n_replicas,
            "capacity_replicas": self.capacity,
            "router": self.fcfg.router,
            "disaggregation": self.fcfg.disaggregation,
            "autoscale": self.fcfg.autoscale,
            "kv_xfer_transfers": self.kv_xfer_transfers,
        }

    def fault_meta(self) -> dict:
        return {
            "replica_failures": len(self.replica_failures),
            "requeued_requests": self.requeued_requests,
            "reprefill_tokens": self.reprefill_tokens,
        }

    def finalize(self, report: ServeReport, system: HybridMemorySystem,
                 fault_stats: dict | None = None) -> FleetReport:
        """Wrap the fleet-aggregate :class:`ServeReport` with replica axes
        and the chips x area x energy cost index."""
        span_ns = self.span_end_ns() - self.t0
        mean_alive = self.mean_alive()
        tokens = self.tokens()
        energy_per_token = report.sim.energy_j / tokens if tokens else 0.0
        area = system.glb.area_mm2
        busy_frac = tuple(
            round(r.busy_ns / span_ns, 6) if span_ns > 0 else 0.0
            for r in self.replicas
        )
        fault_stats = fault_stats or {}
        return FleetReport(
            report=report,
            n_replicas=self.fcfg.n_replicas,
            n_replicas_peak=self.peak_alive(),
            mean_alive_replicas=mean_alive,
            router=self.fcfg.router,
            disaggregated=self.fcfg.disaggregation,
            autoscaled=self.fcfg.autoscale,
            routed_per_replica=tuple(r.routed for r in self.replicas),
            completed_per_replica=tuple(r.completed for r in self.replicas),
            busy_frac_per_replica=busy_frac,
            kv_xfer_transfers=self.kv_xfer_transfers,
            kv_xfer_bytes=self.kv_xfer_bytes,
            autoscale_events=tuple(self.autoscale_events),
            tokens=tokens,
            area_mm2_per_chip=area,
            energy_per_token_j=energy_per_token,
            cost_per_token=mean_alive * area * energy_per_token,
            replica_failures=tuple(self.replica_failures),
            requeued_requests=self.requeued_requests,
            reprefill_tokens=self.reprefill_tokens,
            fault_retry_accesses=float(
                fault_stats.get("retry_accesses", 0.0)),
            banks_remapped=int(fault_stats.get("banks_remapped", 0)),
            goodput_tps=(tokens / (span_ns * 1e-9) if span_ns > 0 else 0.0),
        )


def run_fleet(
    system: HybridMemorySystem,
    spec: NLPModelSpec,
    cfg: ServingConfig,
    engine_cfg: ServeEngineConfig,
    fleet_cfg: FleetConfig,
    sim_config: SimConfig | None = None,
    n_dram_channels: int = 8,
    n_prefetch_channels: int = 4,
    lowering: str = "block",
    timing: dict | None = None,
    recorder=None,
    faults: FaultConfig | None = None,
) -> tuple[Trace, FleetReport]:
    """Run the serving loop to completion and score one replay.

    Every step's blocks are priced against a fleet-wide
    :class:`TechPricer` (per-replica bank slices in one resource space) and
    the priced busy times feed each replica's clock.  This is
    :func:`fleet_serving` without its fault-free baseline rerun, so
    ``ttft_p99_inflation`` stays 0; the sweep and
    :func:`closed_loop_serving` call it directly.

    ``timing`` receives the ``loop_s`` (scheduler + allocator + lowering +
    pricing) vs ``score_s`` (trace build + replay + report) wall-clock
    split; with :mod:`repro.obs` enabled the same two phases are the spans
    ``loop`` and ``score`` (``score/trace``, then the replay's own spans).
    """
    t_loop0 = time.perf_counter()
    with obs.span("loop"):
        if faults is not None:
            faults.validate()
            system = derate_system(system, faults)
        rng = np.random.default_rng(cfg.seed)
        arrivals, prompts, decodes = draw_requests(cfg, rng)

        fleet = Fleet(system, spec, cfg, engine_cfg, fleet_cfg,
                      lowering=lowering, recorder=recorder, faults=faults)
        # The pricer only reads run-level constants off the model (the
        # KV-append line namespace); replica 0's own model is built by run().
        seed_model = ServeModel(system, spec, cfg, engine_cfg)
        pricer = TechPricer(system, seed_model, n_dram_channels,
                            n_prefetch_channels, n_replicas=fleet.capacity,
                            faults=faults)

        def step_time(replica: _Replica, blocks: StepBlocks) -> float:
            glb_ns, dram_ns = pricer.price_step(blocks)
            decode_ns = (replica.model.interval_ns if blocks.has_decode
                         else 0.0)
            return max(decode_ns, blocks.prefill_ns, glb_ns, dram_ns)

        def price_block(blocks: StepBlocks) -> None:
            pricer.price_step(blocks)  # transfer events: priced, never pacing

        fleet.run(arrivals, prompts, decodes, step_time,
                  price_block=price_block)
    t_score0 = time.perf_counter()
    with obs.span("score"):
        model0 = fleet.replicas[0].model
        fleet_extra = fleet.fleet_meta()
        extra = dict(fleet_extra)
        if faults is not None:
            extra["faults"] = faults.to_dict()
            if pricer.fm is not None:
                extra["fault_stats"] = pricer.fm.stats()
            # Replica failures are fleet entries too: a single accelerator
            # cannot lose its only replica (``Fleet._can_fail``).
            if fleet_extra and faults.has_replica_faults:
                extra.update(fleet.fault_meta())
        with obs.span("trace"):
            trace = pricer.b.build(
                compute_time_s=0.0,
                meta=serving_run_meta(spec, cfg, engine_cfg, system, model0,
                                      fleet.stats, lowering, **extra),
            )
        mean_alive = fleet.mean_alive()
        if mean_alive != 1.0:
            # A fleet leaks on every alive chip; at 1.0 the multiply is
            # skipped so the single chip's leakage term stays bit-exact.
            trace.leakage_w = system.glb.leakage_w * mean_alive
        sim_config = sim_config or SimConfig(
            coalesce_window_ns=4 * model0.interval_ns, kind_stats=False
        )
        report = score_requests(
            trace,
            system=system,
            sim_config=sim_config,
            recorder=recorder,
            requests=fleet.logical,
            finished=fleet.finished_logical,
            arrival_by_rid=fleet.arrival_by_rid,
            offered_qps=cfg.arrival_rate_rps,
            pages_spilled=fleet.pages_spilled(),
            pages_allocated=fleet.pages_allocated(),
            stats=fleet.stats,
        )
        fr = fleet.finalize(
            report, system,
            fault_stats=(pricer.fm.stats() if pricer.fm is not None
                         else None),
        )
    if timing is not None:
        timing["loop_s"] = timing.get("loop_s", 0.0) + (t_score0 - t_loop0)
        timing["score_s"] = (
            timing.get("score_s", 0.0) + time.perf_counter() - t_score0
        )
    return trace, fr


def fleet_serving(
    system: HybridMemorySystem,
    spec: NLPModelSpec,
    cfg: ServingConfig = ServingConfig(),
    engine_cfg: ServeEngineConfig = ServeEngineConfig(),
    fleet_cfg: FleetConfig = FleetConfig(),
    sim_config: SimConfig | None = None,
    n_dram_channels: int = 8,
    n_prefetch_channels: int = 4,
    lowering: str = "block",
    timing: dict | None = None,
    recorder=None,
    faults: FaultConfig | None = None,
) -> tuple[Trace, FleetReport]:
    """Run the closed-loop fleet to completion and score one fleet replay.

    See :func:`run_fleet` for the loop, ``timing`` and the spans.  With the
    default 1-replica :class:`FleetConfig` the trace and report are those of
    :func:`closed_loop_serving` on the same inputs.

    ``faults`` arms the full campaign: reliability-derated pricing with
    seeded write-retry/bank-offline injection (as on one accelerator) plus
    replica failures — dead replicas drop their in-flight work, which is
    requeued onto survivors after a capped exponential backoff, their lost
    KV re-prefilled, while the router excludes them and the autoscaler (if
    on) brings replacements up.  ``faults=None`` is bit-identical to today.
    With ``faults.baseline_inflation`` one fault-free rerun of the same
    fleet, after the faulted run and outside its ``timing``, sets
    ``ttft_p99_inflation``.
    """
    trace, fr = run_fleet(system, spec, cfg, engine_cfg, fleet_cfg,
                          sim_config, n_dram_channels, n_prefetch_channels,
                          lowering, timing, recorder, faults)
    if faults is not None and faults.baseline_inflation:
        # The rerun anchors the degradation metric: how much the campaign
        # inflated the tail TTFT over the same offered load.
        _, base = run_fleet(system, spec, cfg, engine_cfg, fleet_cfg,
                            sim_config, n_dram_channels, n_prefetch_channels,
                            lowering)
        if base.report.ttft_p99_ms > 0:
            fr.ttft_p99_inflation = (
                fr.report.ttft_p99_ms / base.report.ttft_p99_ms
            )
    return trace, fr


def closed_loop_serving(
    system: HybridMemorySystem,
    spec: NLPModelSpec,
    cfg: ServingConfig = ServingConfig(),
    engine_cfg: ServeEngineConfig = ServeEngineConfig(),
    sim_config: SimConfig | None = None,
    n_dram_channels: int = 8,
    n_prefetch_channels: int = 4,
    lowering: str = "block",
    timing: dict | None = None,
    recorder=None,
    faults: FaultConfig | None = None,
) -> tuple[Trace, ServeReport]:
    """Serve on one accelerator to completion and score the replay.

    The single accelerator is the 1-replica fleet (:func:`run_fleet` with
    ``FleetConfig()``).  ``lowering`` picks the step-lowering
    implementation: ``"block"`` (the vectorized array program, default) or
    ``"scalar"`` (the per-request reference loop — bit-identical output,
    kept for equivalence testing and the ``benchmarks/serving_qps`` speedup
    baseline).  ``timing`` and the spans are :func:`run_fleet`'s.
    ``recorder`` (a :class:`repro.obs.TimelineRecorder`) taps the loop's
    request lifecycles/counters and the replay's bank timeline for Perfetto
    export; all recorder hooks are read-only, so the returned trace and
    report are bit-identical with the recorder on or off.

    ``faults`` arms deterministic fault injection: the GLB array is derated
    for ECC/write-verify (expectation level), and the priced event stream
    gains seeded write-retry accesses and bank-offline remap windows; the
    trace meta records the campaign as given.  Replica failures never fire
    on one accelerator.  The default ``None`` leaves the run bit-identical
    to a fault-free build.
    """
    trace, fr = run_fleet(system, spec, cfg, engine_cfg, FleetConfig(),
                          sim_config, n_dram_channels, n_prefetch_channels,
                          lowering, timing, recorder, faults)
    return trace, fr.report


def summarize_fleet(fr: FleetReport) -> str:
    """Human-readable fleet dump (extends ``summarize_report``)."""
    lines = [summarize_report(fr.report)]
    lines.append(
        f"fleet                : {fr.n_replicas} replicas "
        f"({fr.router}, peak {fr.n_replicas_peak}, "
        f"mean alive {fr.mean_alive_replicas:.2f})"
    )
    lines.append(
        f"routed/replica       : {list(fr.routed_per_replica)} "
        f"(busy frac {list(fr.busy_frac_per_replica)})"
    )
    if fr.disaggregated:
        lines.append(
            f"KV disaggregation    : {fr.kv_xfer_transfers} transfers, "
            f"{fr.kv_xfer_bytes / 1e6:.1f} MB streamed"
        )
    if fr.autoscaled:
        lines.append(
            f"autoscaler           : {len(fr.autoscale_events)} actions "
            f"-> {list(fr.autoscale_events)[:6]}"
        )
    if fr.replica_failures or fr.fault_retry_accesses or fr.banks_remapped:
        lines.append(
            f"fault campaign       : {len(fr.replica_failures)} replica "
            f"failures, {fr.requeued_requests} requeued, "
            f"{fr.reprefill_tokens} re-prefilled tokens, "
            f"{fr.fault_retry_accesses:.0f} write retries, "
            f"{fr.banks_remapped} bank remaps; goodput "
            f"{fr.goodput_tps:.0f} tok/s, p99 TTFT x"
            f"{fr.ttft_p99_inflation:.2f} vs fault-free"
        )
    lines.append(
        f"cost per token       : {fr.cost_per_token:.3e} "
        f"(chips {fr.mean_alive_replicas:.2f} x area "
        f"{fr.area_mm2_per_chip:.1f} mm^2 x "
        f"{fr.energy_per_token_j * 1e6:.2f} uJ/token)"
    )
    return "\n".join(lines)
