"""Serving lowering, pricing and scoring: scheduler steps to a scored replay.

The serving loop itself is the event loop of :mod:`repro.serve.fleet`
(``closed_loop_serving`` is its 1-replica case).  Each step it runs the
continuous-batching scheduler against the paged KV allocator, lowers the
step's memory traffic with an emitter from this module, prices it with a
:class:`TechPricer` into the existing :class:`repro.sim.trace.TraceBuilder`,
and feeds the *modelled* step duration (weight-stream cadence, per-bank GLB
service, exposed DRAM spill time — whichever dominates) back into the clock.
Queueing therefore compounds: a step slowed by bank conflicts or KV spill
delays every token behind it, which is exactly what the open-loop
``serving_trace`` cannot express.

The lowering is an array program: each scheduler step emits one event
*block* per traffic class (KV reads, KV appends, activations, spills,
weight stream) across all active requests x layers, with broadcasted
bank-hash/access/line/tag columns appended once per class — not one
1-element append per request/page/layer.  Blocks are *technology-neutral*
(:class:`StepBlocks` stores bank hashes and access counts); a
:class:`TechPricer` turns them into priced events for one concrete GLB
(``bank = hash % n_banks``, service/energy scaled by that technology), which
is what lets the sweep engine (``repro.serve.sweep``) reuse one lowered
schedule across technologies.  A scalar reference emitter
(``lowering="scalar"``) walks the same plans request by request and page by
page, producing a bit-identical event stream — the equivalence is pinned by
``tests/test_serve.py`` and benchmarked by ``benchmarks/serving_qps``.

Traffic formulas deliberately mirror ``serving_trace`` operand for operand
(per decode token and layer: context-length KV read, KV append to a stable
line, activation read/write pair, shared per-step weight stream; per prefill
token and layer: 6x/2x activation traffic plus the KV write), with one
difference: KV placement is per-page residency from the allocator instead of
a scalar ``spill_frac``.  At matched config and zero spill the two
generators agree on aggregate GLB/DRAM byte counts — pinned by
``tests/test_serve.py``.

Allocator transactions are step-batched: all of a step's page allocations
run first (prefill then decode, in plan order, against the previous step's
LRU stamps), then the decode touches commit as one vector store.

The final event stream is scored by ``sim.engine``'s FIFO replay
(:func:`score_requests`); per-token events are tagged with their request id
so TTFT/TPOT p50/p99 are measured from *replayed* finish times
(bank-accurate), not from the scheduler clock.  :func:`assemble_report`
turns a replay into the :class:`ServeReport`, for this scorer and for the
sweep's batched one alike.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.access_counts import MemoryParams
from repro.core.memory_system import HybridMemorySystem
from repro.core.workload import NLPModelSpec
from repro.faults import FaultConfig, fault_model_for
from repro.sim.engine import SimConfig, SimResult, simulate_trace
from repro.sim.trace import (
    KIND_DRAM_RD,
    KIND_DRAM_WR,
    KIND_GLB_RD,
    KIND_GLB_WR,
    KIND_PREFETCH_RD,
    MB,
    ServingConfig,
    Trace,
    TraceBuilder,
    _spec_weight_bytes,
    trace_byte_counts,
)
from repro.serve.kv_pages import PagedKVAllocator
from repro.serve.scheduler import ServeEngineConfig, StepPlan

_MAX_STEPS = 200_000


@dataclasses.dataclass
class ServeReport:
    """Closed-loop serving outcome: SLO metrics + memory-system congestion."""

    n_requests: int
    completed: int
    n_steps: int
    offered_qps: float
    achieved_qps: float
    span_s: float
    # Replay-scored (bank-accurate) SLO metrics, milliseconds.
    ttft_p50_ms: float
    ttft_p99_ms: float
    tpot_p50_ms: float
    tpot_p99_ms: float
    # Scheduler-clock metrics (the closed-loop feedback signal).
    sched_ttft_p99_ms: float
    sched_tpot_p99_ms: float
    # KV paging.
    residency_mean: float  # time-weighted fraction of KV pages GLB-resident
    pages_spilled: int
    pages_allocated: int
    kv_spill_read_frac: float  # fraction of KV read bytes served from DRAM
    # Congestion (from the replay).
    bank_conflict_rate: float
    mean_queue_depth: float
    bytes: dict
    sim: SimResult


@dataclasses.dataclass
class StepBlocks:
    """One step's lowered traffic: one array block per traffic class.

    Technology-neutral units: GLB placements are bank *hashes* (physical
    bank = ``hash % n_banks``, DRAM spill channel = ``bank %
    n_dram_channels``), GLB traffic is counted in 256 B bus beats and DRAM
    traffic in 64 B bursts.  ``glb_wr_line`` uses ``-1`` for
    never-coalescible fresh lines; KV-append lines are ``rid * n_layers +
    layer`` (the pricer reserves that namespace).  Service times, energies,
    and resource ids are applied later by :class:`TechPricer`.
    """

    t_ns: float
    prefill_ns: float
    has_decode: bool
    glb_rd_hash: np.ndarray
    glb_rd_acc: np.ndarray
    glb_wr_hash: np.ndarray
    glb_wr_acc: np.ndarray
    glb_wr_line: np.ndarray
    glb_wr_tag: np.ndarray
    dram_rd_hash: np.ndarray
    dram_rd_acc: np.ndarray
    dram_wr_hash: np.ndarray
    dram_wr_acc: np.ndarray
    pref_ch: np.ndarray
    pref_acc: np.ndarray
    # Per-step bookkeeping the report aggregates.
    kv_rd_bytes_glb: float
    kv_rd_bytes_dram: float
    residency: float
    # Fleet axis: which replica's banks/channels these events land on.  The
    # pricer offsets every resource id by ``replica * per-replica-count``, so
    # a whole fleet run is still one segmented-bincount pricing pass; 0 keeps
    # the single-accelerator layout bit-identical.
    replica: int = 0
    # Cross-replica KV-transfer payload carried by this block (disaggregated
    # prefill->decode streaming); 0 for ordinary scheduler steps.
    kv_xfer_bytes: float = 0.0


def _cat(parts, dtype):
    # Emitters append parts of the correct dtype by construction, so the
    # multi-part path can concatenate without per-part conversion.
    if not parts:
        return np.empty(0, dtype)
    if len(parts) == 1:
        return np.asarray(parts[0], dtype)
    return np.concatenate(parts)


class ServeModel:
    """Shared constants of one serving run (model x config x engine knobs).

    Everything here is technology-*independent* given the GLB capacity: the
    decode cadence and weight-stream times derive from the DRAM model, the
    page geometry from the model spec, and the allocator stores bank hashes
    rather than physical banks.
    """

    def __init__(
        self,
        system: HybridMemorySystem,
        spec: NLPModelSpec,
        cfg: ServingConfig,
        engine_cfg: ServeEngineConfig,
        replica_id: int = 0,
    ):
        self.spec, self.cfg, self.ecfg = spec, cfg, engine_cfg
        self.replica = int(replica_id)
        dram = system.dram
        self.dram_access_bytes = dram.access_bytes
        self.n_layers = max(1, spec.enc_layers + spec.dec_layers)
        self.d = spec.d_model
        self.kv_token_bytes = 2 * self.d * cfg.d_w
        self.glb_acc_bytes = int(MB * MemoryParams().mbpa_glb)
        self.weight_bytes = _spec_weight_bytes(spec, cfg.d_w)
        self.t_ws_ns = self.weight_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
        if engine_cfg.token_interval_ns is not None:
            if engine_cfg.token_interval_ns <= 0:
                raise ValueError("token_interval_ns must be positive")
            self.interval_ns = engine_cfg.token_interval_ns
        else:
            self.interval_ns = max(engine_cfg.headroom * self.t_ws_ns, 1e3)
        page_bytes = engine_cfg.page_tokens * self.kv_token_bytes * self.n_layers
        self.alloc = PagedKVAllocator(
            glb_bytes=system.glb.capacity_mb * MB * engine_cfg.kv_reserve_frac,
            page_bytes=page_bytes,
            n_banks=max(1, int(system.glb.banks)),
            replica_id=self.replica,
        )
        self._l = np.arange(self.n_layers)
        # Shared per-decode-step weight-stream slice (continuous batching).
        self._dec_pref_acc = self.weight_bytes / self.n_layers / dram.access_bytes
        self._w_acc = max(1.0, self.kv_token_bytes / self.glb_acc_bytes)
        self._act_acc = max(1.0, 2.0 * self.d * cfg.d_w / self.glb_acc_bytes)
        # Folded per-token constants (identical operation order in both
        # emitters keeps the scalar/block event streams bit-identical).
        self._kv_acc_per_tok = self.kv_token_bytes * self.n_layers / self.glb_acc_bytes
        self._glb_to_dram = self.glb_acc_bytes / dram.access_bytes
        self._l17 = self._l * 17
        self._l17p3 = self._l17 + 3
        self._l17p5 = self._l17 + 5


class BlockEmitter:
    """Vectorized lowering: one block per traffic class per step.

    Constant-valued columns (activation/append access counts, fresh-line and
    untagged sentinels, the shared weight-stream slice) are served from a
    read-only fill cache keyed by (value, length) — the per-step cost is a
    handful of gathers, masks, and concatenations over the decode batch.
    """

    def __init__(self, model: ServeModel):
        self.m = model
        self._fills: dict = {}
        L = model.n_layers
        self._pref_dec = self._full(model._dec_pref_acc, L)

    def _full(self, value, size: int) -> np.ndarray:
        """Cached constant array (never mutated downstream)."""
        key = (value, size)
        out = self._fills.get(key)
        if out is None:
            dtype = np.int64 if isinstance(value, int) else np.float64
            out = self._fills[key] = np.full(size, value, dtype)
        return out

    def emit(self, plan: StepPlan) -> StepBlocks:
        m = self.m
        alloc, L, pt = m.alloc, m.n_layers, m.ecfg.page_tokens
        alloc.tick()
        glb_rd_h, glb_rd_a = [], []
        glb_wr_h, glb_wr_a, glb_wr_l, glb_wr_t = [], [], [], []
        dram_rd_h, dram_rd_a, dram_wr_h, dram_wr_a = [], [], [], []
        pref_c, pref_a = [], []
        prefill_ns = 0.0

        # -- prefill chunks (rare; a few requests per step at most) ----------
        pf_kv_glb_h, pf_kv_glb_a = [], []
        for r, toks in plan.prefill:
            rid = r.rid
            act_rd = 6.0 * toks * m.d * m.cfg.d_w / m.glb_acc_bytes
            act_wr = 2.0 * toks * m.d * m.cfg.d_w / m.glb_acc_bytes
            h = rid * 131 + m._l17
            glb_rd_h.append(h)
            glb_rd_a.append(self._full(act_rd, L))
            glb_wr_h.append(h + 1)
            glb_wr_a.append(self._full(act_wr, L))
            glb_wr_l.append(self._full(-1, L))
            glb_wr_t.append(self._full(-1, L))

            # KV writes land on the pages covering the new tokens.
            start = r.prefilled
            alloc.ensure(rid, start + toks, pt)
            slots = alloc.slots_of(rid)
            lo, hi = start // pt, -(-(start + toks) // pt)
            idx = np.arange(lo, hi)
            t_in_page = (np.minimum((idx + 1) * pt, start + toks)
                         - np.maximum(idx * pt, start))
            acc = t_in_page * m._kv_acc_per_tok
            page_h = alloc.page_hash[slots[lo:hi]]
            res = alloc.page_resident[slots[lo:hi]]
            pf_kv_glb_h.append(page_h[res])
            pf_kv_glb_a.append(acc[res])
            dram_wr_h.append(page_h[~res])
            dram_wr_a.append(acc[~res] * m._glb_to_dram)

            # Per-request weight-stream slice (prefill re-streams the
            # weights, like serving_trace's per-arrival prefill burst).
            frac = toks / r.prompt
            pref = m.weight_bytes * frac / L / m.dram_access_bytes
            pref_c.append(m._l)
            pref_a.append(self._full(pref, L))
            prefill_ns = max(prefill_ns, m.t_ws_ns * (frac + toks / 2048.0))
        # Prefill KV page writes follow all prefill activation writes (class
        # order is fixed so the scalar reference can reproduce it exactly).
        for h, a in zip(pf_kv_glb_h, pf_kv_glb_a):
            glb_wr_h.append(h)
            glb_wr_a.append(a)
            glb_wr_l.append(self._full(-1, h.shape[0]))
            glb_wr_t.append(self._full(-1, h.shape[0]))

        # -- decode batch (the hot path) -------------------------------------
        kv_glb_bytes = kv_dram_bytes = 0.0
        rids, ctx = plan.decode_arrays
        if rids.size:
            # KV reads: one event per page of each context; resident pages on
            # their GLB bank, spilled pages on the exposed DRAM path.
            slots, toks, _, app = alloc.decode_step(rids, ctx, pt)
            page_h = alloc.page_hash[slots]
            res = alloc.page_resident[slots]
            kv_acc = toks * m._kv_acc_per_tok
            if res.all():
                glb_rd_h.append(page_h)
                glb_rd_a.append(kv_acc)
                kv_glb_bytes = float(kv_acc.sum()) * m.glb_acc_bytes
            else:
                spill = ~res
                glb_rd_h.append(page_h[res])
                glb_rd_a.append(kv_acc[res])
                dram_rd_h.append(page_h[spill])
                dram_rd_a.append(kv_acc[spill] * m._glb_to_dram)
                kv_glb_bytes = float(kv_acc[res].sum()) * m.glb_acc_bytes
                kv_dram_bytes = float(kv_acc[spill].sum()) * m.glb_acc_bytes

            # KV append: stable line per (request, layer) -> coalescible.
            app_h = alloc.page_hash[app]
            app_res = alloc.page_resident[app]
            n_res = int(app_res.sum())
            glb_wr_h.append(np.repeat(app_h[app_res], L))
            glb_wr_a.append(self._full(m._w_acc, n_res * L))
            glb_wr_l.append(((rids[app_res] * L)[:, None] + m._l).ravel())
            glb_wr_t.append(self._full(-1, n_res * L))
            if n_res < app_res.size:
                dram_wr_h.append(np.repeat(app_h[~app_res], L))
                dram_wr_a.append(self._full(
                    m._w_acc * m._glb_to_dram, (app_res.size - n_res) * L
                ))

            # Activation read/write per layer; the last layer's write is the
            # token-completion marker, tagged with the request id so the
            # replay yields per-token finish times.
            act_base = rids * 131
            glb_rd_h.append((act_base[:, None] + m._l17p3).ravel())
            glb_rd_a.append(self._full(m._act_acc, rids.size * L))
            glb_wr_h.append((act_base[:, None] + m._l17p5).ravel())
            glb_wr_a.append(self._full(m._act_acc, rids.size * L))
            glb_wr_l.append(self._full(-1, rids.size * L))
            tag = np.full(rids.size * L, -1, np.int64)
            tag[L - 1 :: L] = rids
            glb_wr_t.append(tag)

            # One shared weight stream per decode step (continuous batching).
            pref_c.append(m._l)
            pref_a.append(self._pref_dec)

        return StepBlocks(
            t_ns=plan.t_start_ns,
            prefill_ns=prefill_ns,
            has_decode=bool(rids.size),
            glb_rd_hash=_cat(glb_rd_h, np.int64),
            glb_rd_acc=_cat(glb_rd_a, np.float64),
            glb_wr_hash=_cat(glb_wr_h, np.int64),
            glb_wr_acc=_cat(glb_wr_a, np.float64),
            glb_wr_line=_cat(glb_wr_l, np.int64),
            glb_wr_tag=_cat(glb_wr_t, np.int64),
            dram_rd_hash=_cat(dram_rd_h, np.int64),
            dram_rd_acc=_cat(dram_rd_a, np.float64),
            dram_wr_hash=_cat(dram_wr_h, np.int64),
            dram_wr_acc=_cat(dram_wr_a, np.float64),
            pref_ch=_cat(pref_c, np.int64),
            pref_acc=_cat(pref_a, np.float64),
            kv_rd_bytes_glb=kv_glb_bytes,
            kv_rd_bytes_dram=kv_dram_bytes,
            residency=alloc.residency(),
            replica=m.replica,
        )


class ScalarEmitter:
    """Scalar reference lowering: the pre-vectorization hot path, kept as
    the equivalence baseline and the ``benchmarks/serving_qps`` speedup
    denominator.  Each request is walked separately, each KV page becomes a
    1-element array append, each per-layer group its own ``np.full`` chunk
    — hundreds of tiny allocations per step, concatenated class by class at
    the end, exactly like the per-request ``buf.*.append`` lowering this PR
    replaces.  Produces blocks bit-identical to :class:`BlockEmitter` (same
    class-internal order, same float operation order)."""

    def __init__(self, model: ServeModel):
        self.m = model

    def emit(self, plan: StepPlan) -> StepBlocks:
        m = self.m
        alloc, L, pt = m.alloc, m.n_layers, m.ecfg.page_tokens
        alloc.tick()
        glb_rd_h, glb_rd_a = [], []
        glb_wr_h, glb_wr_a, glb_wr_l, glb_wr_t = [], [], [], []
        dram_rd_h, dram_rd_a, dram_wr_h, dram_wr_a = [], [], [], []
        pref_c, pref_a = [], []
        prefill_ns = 0.0

        pf_kv = []  # deferred prefill KV page writes (class order contract)
        for r, toks in plan.prefill:
            rid = r.rid
            act_rd = 6.0 * toks * m.d * m.cfg.d_w / m.glb_acc_bytes
            act_wr = 2.0 * toks * m.d * m.cfg.d_w / m.glb_acc_bytes
            h = rid * 131 + m._l17
            glb_rd_h.append(h)
            glb_rd_a.append(np.full(L, act_rd))
            glb_wr_h.append(h + 1)
            glb_wr_a.append(np.full(L, act_wr))
            glb_wr_l.append(np.full(L, -1, np.int64))
            glb_wr_t.append(np.full(L, -1, np.int64))
            start = r.prefilled
            alloc.ensure(rid, start + toks, pt)
            slots = alloc.slots_of(rid)
            for idx in range(start // pt, -(-(start + toks) // pt)):
                t_in_page = (min((idx + 1) * pt, start + toks)
                             - max(idx * pt, start))
                acc = t_in_page * m._kv_acc_per_tok
                slot = int(slots[idx])
                if alloc.page_resident[slot]:
                    pf_kv.append((int(alloc.page_hash[slot]), acc))
                else:
                    dram_wr_h.append(np.array([alloc.page_hash[slot]]))
                    dram_wr_a.append(np.array([acc * m._glb_to_dram]))
            frac = toks / r.prompt
            pref = m.weight_bytes * frac / L / m.dram_access_bytes
            pref_c.append(m._l)
            pref_a.append(np.full(L, pref))
            prefill_ns = max(prefill_ns, m.t_ws_ns * (frac + toks / 2048.0))
        for h, acc in pf_kv:
            glb_wr_h.append(np.array([h]))
            glb_wr_a.append(np.array([acc]))
            glb_wr_l.append(np.array([-1], np.int64))
            glb_wr_t.append(np.array([-1], np.int64))

        kv_glb_bytes = kv_dram_bytes = 0.0
        for r in plan.decode:
            alloc.ensure(r.rid, r.prompt + r.decoded + 1, pt)
        for r in plan.decode:
            alloc.touch(r.rid)
        # KV reads (all requests), then KV appends, then activations — the
        # same class-internal order the block emitter's concatenation yields.
        for r in plan.decode:
            for h, t_in_page, resident in self._iter_pages(r):
                acc = t_in_page * m._kv_acc_per_tok
                if resident:
                    glb_rd_h.append(np.array([h]))
                    glb_rd_a.append(np.array([acc]))
                    kv_glb_bytes += acc * m.glb_acc_bytes
                else:
                    dram_rd_h.append(np.array([h]))
                    dram_rd_a.append(np.array([acc * m._glb_to_dram]))
                    kv_dram_bytes += acc * m.glb_acc_bytes
        for r in plan.decode:
            ctx = r.prompt + r.decoded
            slot = int(alloc.slots_of(r.rid)[ctx // pt])
            h = int(alloc.page_hash[slot])
            if alloc.page_resident[slot]:
                glb_wr_h.append(np.full(L, h))
                glb_wr_a.append(np.full(L, m._w_acc))
                glb_wr_l.append(r.rid * L + m._l)
                glb_wr_t.append(np.full(L, -1, np.int64))
            else:
                dram_wr_h.append(np.full(L, h))
                dram_wr_a.append(np.full(L, m._w_acc * m._glb_to_dram))
        for r in plan.decode:
            glb_rd_h.append(r.rid * 131 + m._l17p3)
            glb_rd_a.append(np.full(L, m._act_acc))
        for r in plan.decode:
            glb_wr_h.append(r.rid * 131 + m._l17p5)
            glb_wr_a.append(np.full(L, m._act_acc))
            glb_wr_l.append(np.full(L, -1, np.int64))
            tag = np.full(L, -1, np.int64)
            tag[-1] = r.rid
            glb_wr_t.append(tag)
        if plan.decode:
            pref_c.append(m._l)
            pref_a.append(np.full(L, m._dec_pref_acc))

        kv_stats = (kv_glb_bytes, kv_dram_bytes)
        return StepBlocks(
            t_ns=plan.t_start_ns,
            prefill_ns=prefill_ns,
            has_decode=bool(plan.decode),
            glb_rd_hash=_cat(glb_rd_h, np.int64),
            glb_rd_acc=_cat(glb_rd_a, np.float64),
            glb_wr_hash=_cat(glb_wr_h, np.int64),
            glb_wr_acc=_cat(glb_wr_a, np.float64),
            glb_wr_line=_cat(glb_wr_l, np.int64),
            glb_wr_tag=_cat(glb_wr_t, np.int64),
            dram_rd_hash=_cat(dram_rd_h, np.int64),
            dram_rd_acc=_cat(dram_rd_a, np.float64),
            dram_wr_hash=_cat(dram_wr_h, np.int64),
            dram_wr_acc=_cat(dram_wr_a, np.float64),
            pref_ch=_cat(pref_c, np.int64),
            pref_acc=_cat(pref_a, np.float64),
            kv_rd_bytes_glb=kv_stats[0],
            kv_rd_bytes_dram=kv_stats[1],
            residency=alloc.residency(),
            replica=m.replica,
        )

    def _iter_pages(self, r):
        """Walk the pages covering ``r``'s context one at a time."""
        m = self.m
        alloc, pt = m.alloc, m.ecfg.page_tokens
        slots = alloc.slots_of(r.rid)
        remaining = r.prompt + r.decoded
        idx = 0
        while remaining > 0:
            slot = int(slots[idx])
            t_in_page = min(pt, remaining)
            yield (int(alloc.page_hash[slot]), t_in_page,
                   bool(alloc.page_resident[slot]))
            remaining -= t_in_page
            idx += 1


class TechPricer:
    """Prices neutral step blocks for one concrete memory system.

    Applies the technology's bank count (``bank = hash % n_banks``), service
    latencies, and access energies, appends the events to a
    :class:`TraceBuilder`, and returns each step's (max per-bank GLB busy,
    DRAM busy) for the serving loop's clock.  The sweep's shared path prices
    a whole run at once instead (:meth:`repro.serve.replay.NeutralRun.price`,
    same formulas).

    ``n_replicas`` widens the resource space to a fleet: every replica owns
    its own contiguous slice of GLB banks and DRAM/prefetch channels, and a
    block's events land at ``replica * per_replica_count + local``.  Pricing
    stays one segmented-bincount pass over the whole fleet, and at
    ``n_replicas=1`` every offset is zero, so the single-accelerator event
    stream is bit-identical to before the fleet axis existed.

    ``faults`` (a :class:`repro.faults.FaultConfig`) arms deterministic
    injection: GLB writes gain seeded write-verify retry accesses and GLB
    banks struck by transient faults remap for one window — both drawn from
    the counter RNG keyed on the within-class event index / absolute time
    window, so this per-step path and ``NeutralRun.price`` inject
    identically.  ``None`` (the default) leaves every operand untouched.
    """

    def __init__(
        self,
        system: HybridMemorySystem,
        model: ServeModel,
        n_dram_channels: int = 8,
        n_prefetch_channels: int = 4,
        n_replicas: int = 1,
        faults: FaultConfig | None = None,
    ):
        self.system = system
        self.n_replicas = max(1, int(n_replicas))
        nb = max(1, int(system.glb.banks))
        self.b = TraceBuilder(
            system,
            n_dram_channels * self.n_replicas,
            n_prefetch_channels * self.n_replicas,
            n_glb_banks=nb * self.n_replicas,
        )
        self.nb = nb  # per-replica bank count (hash % nb stays local)
        self.nb_total = self.b.n_glb_banks
        self.n_dram_ch = n_dram_channels  # per replica
        self.n_pref_ch = n_prefetch_channels  # per replica
        dram = system.dram
        self.t_dram_acc_ns = dram.access_bytes / (dram.bandwidth_gb_s * 1e9) * 1e9
        self.t_dram_acc_ch_ns = self.t_dram_acc_ns * n_dram_channels
        self.e_dram_pj = dram.energy_pj_per_access()
        # Reserve the stable KV-append line namespace (one line per
        # (request, layer)); fresh lines start above it.
        n_kv_lines = model.cfg.n_requests * model.n_layers
        if n_kv_lines:
            self.b.fresh_lines(n_kv_lines)
        # None when faults are off or the GLB technology has no (or trivial)
        # reliability block — every injection branch below is then skipped,
        # keeping the zero-fault event stream bit-identical.
        self.fm = fault_model_for(system, faults, n_replicas=self.n_replicas)

    def price_step(self, blk: StepBlocks) -> tuple[float, float]:
        """Emit one step's events; returns (max per-bank GLB ns, DRAM ns).

        The busy maxima are computed over the block's own replica slice
        (other replicas' banks are untouched by one step), so the closed-loop
        feedback is per-replica even when the trace spans a fleet.
        """
        b, glb = self.b, self.system.glb
        bank_off = blk.replica * self.nb
        glb_ns = 0.0
        busy = None
        if blk.glb_rd_hash.size:
            bank = blk.glb_rd_hash % self.nb
            if self.fm is not None:
                bank = self.fm.remap_banks(bank, blk.t_ns, blk.replica)
            svc = blk.glb_rd_acc * glb.read_latency_ns
            b.add(blk.t_ns, bank + bank_off if bank_off else bank, svc,
                  blk.glb_rd_acc * glb.read_energy_pj_per_access,
                  KIND_GLB_RD, n=bank.size)
            busy = np.bincount(bank, weights=svc, minlength=self.nb)
        if blk.glb_wr_hash.size:
            bank = blk.glb_wr_hash % self.nb
            acc = blk.glb_wr_acc
            if self.fm is not None:
                bank = self.fm.remap_banks(bank, blk.t_ns, blk.replica)
                acc = self.fm.write_acc(acc)
            line = blk.glb_wr_line
            fresh = line < 0
            if fresh.any():
                line = line.copy()
                line[fresh] = self.b.fresh_lines(int(fresh.sum()))
            svc = acc * glb.write_latency_ns
            b.add(blk.t_ns, bank + bank_off if bank_off else bank, svc,
                  acc * glb.write_energy_pj_per_access,
                  KIND_GLB_WR, line=line, tag=blk.glb_wr_tag, n=bank.size)
            wr_busy = np.bincount(bank, weights=svc, minlength=self.nb)
            busy = wr_busy if busy is None else busy + wr_busy
        if busy is not None:
            glb_ns = float(busy.max())
        dram_acc_total = 0.0
        dram_off = blk.replica * self.n_dram_ch
        for hashes, acc, kind in (
            (blk.dram_rd_hash, blk.dram_rd_acc, KIND_DRAM_RD),
            (blk.dram_wr_hash, blk.dram_wr_acc, KIND_DRAM_WR),
        ):
            if hashes.size:
                ch = (hashes % self.nb) % self.n_dram_ch
                b.add(blk.t_ns, b.dram_resource(ch + dram_off if dram_off else ch),
                      acc * self.t_dram_acc_ch_ns, acc * self.e_dram_pj, kind,
                      n=ch.size)
                dram_acc_total += float(acc.sum())
        if blk.pref_ch.size:
            ch = blk.pref_ch % self.n_pref_ch
            pref_off = blk.replica * self.n_pref_ch
            b.add(blk.t_ns, b.prefetch_resource(ch + pref_off if pref_off else ch),
                  blk.pref_acc * self.t_dram_acc_ns * self.n_pref_ch,
                  blk.pref_acc * self.e_dram_pj, KIND_PREFETCH_RD, n=ch.size)
        return glb_ns, dram_acc_total * self.t_dram_acc_ns


@dataclasses.dataclass
class RunStats:
    """Per-run accumulators the report needs beyond the trace itself."""

    kv_rd_bytes_glb: float = 0.0
    kv_rd_bytes_dram: float = 0.0
    residency_wsum: float = 0.0
    dt_sum: float = 0.0
    n_steps: int = 0

    def account(self, blk: StepBlocks, dt: float) -> None:
        self.kv_rd_bytes_glb += blk.kv_rd_bytes_glb
        self.kv_rd_bytes_dram += blk.kv_rd_bytes_dram
        self.residency_wsum += blk.residency * dt
        self.dt_sum += dt
        self.n_steps += 1


def serving_run_meta(spec: NLPModelSpec, cfg: ServingConfig,
                     engine_cfg: ServeEngineConfig,
                     system: HybridMemorySystem, model: ServeModel,
                     stats: RunStats, lowering: str, **extra) -> dict:
    """Trace metadata of one serving run — single source for the fleet loop's
    own trace and the sweep engine's shared-schedule path."""
    return {
        "scenario": "serving_closed_loop",
        "model": spec.name,
        "n_requests": cfg.n_requests,
        "arrival_rate_rps": cfg.arrival_rate_rps,
        "token_interval_ns": model.interval_ns,
        "technology": system.glb.technology,
        "glb_mb": system.glb.capacity_mb,
        "n_steps": stats.n_steps,
        "page_tokens": engine_cfg.page_tokens,
        "max_batch": engine_cfg.max_batch,
        "lowering": lowering,
        **extra,
    }


def _percentiles_ms(x: np.ndarray) -> tuple[float, float]:
    if x.size == 0:
        return 0.0, 0.0
    return (
        float(np.percentile(x, 50)) * 1e-6,
        float(np.percentile(x, 99)) * 1e-6,
    )


def replay_token_times(
    tags: np.ndarray, finish_ns: np.ndarray, arrival_by_rid: dict
) -> tuple[np.ndarray, np.ndarray]:
    """Per-request (TTFT, TPOT) samples from tagged replay finish times.

    ``tags``/``finish_ns`` are parallel arrays over the replayed events
    (original tags gathered into replay order via ``orig_idx``; untagged
    events carry ``-1``).  Shared by :func:`score_requests` and the batched
    shared-schedule scorer — one implementation of the tag -> lexsort ->
    group reduction.
    """
    m = tags >= 0
    if not m.any():
        return np.empty(0), np.empty(0)
    tg, fin = tags[m], finish_ns[m]
    order = np.lexsort((fin, tg))
    tg, fin = tg[order], fin[order]
    first = np.flatnonzero(np.r_[True, tg[1:] != tg[:-1]])
    bounds = np.r_[first, tg.size]
    counts = np.diff(bounds)
    rids = tg[first]
    t_first = fin[first]
    t_last = fin[bounds[1:] - 1]
    arr = np.array([arrival_by_rid.get(int(r), np.nan) for r in rids])
    ttft = t_first - arr
    multi = counts > 1
    tpot = (t_last[multi] - t_first[multi]) / (counts[multi] - 1)
    return ttft, tpot


def assemble_report(
    result: SimResult,
    ttft: np.ndarray,
    tpot: np.ndarray,
    trace: Trace,
    system: HybridMemorySystem,
    *,
    requests: list,
    finished: list,
    arrival_by_rid: dict,
    offered_qps: float,
    pages_spilled: int,
    pages_allocated: int,
    stats: RunStats,
) -> ServeReport:
    """One run's :class:`ServeReport` from its replay and its requests.

    ``result`` and the per-request ``ttft``/``tpot`` samples come from the
    replay of ``trace``.  The keywords describe the *logical* request
    population (a disaggregated request runs as two scheduler halves but
    counts once): ``requests``/``finished`` are :class:`RequestState` lists
    and ``arrival_by_rid`` maps each request to its original arrival, from
    which the scheduler-clock TTFT/TPOT and the serving span are taken.
    """
    sched_ttft = np.array(
        [r.first_token_ns - arrival_by_rid.get(r.rid, r.arrival_ns)
         for r in finished]
    )
    sched_tpot = np.array(
        [
            (r.finish_ns - r.first_token_ns) / (r.decoded - 1)
            for r in finished
            if r.decoded > 1
        ]
    )
    finishes = [r.finish_ns for r in finished]
    arrivals = [arrival_by_rid.get(r.rid, r.arrival_ns) for r in requests]
    span_ns = (max(finishes) - min(arrivals)) if finishes else 0.0

    kv_rd_total = stats.kv_rd_bytes_glb + stats.kv_rd_bytes_dram
    ttft_p50, ttft_p99 = _percentiles_ms(ttft)
    tpot_p50, tpot_p99 = _percentiles_ms(tpot)
    return ServeReport(
        n_requests=len(requests),
        completed=len(finished),
        n_steps=stats.n_steps,
        offered_qps=offered_qps,
        achieved_qps=(len(finished) / (span_ns * 1e-9) if span_ns else 0.0),
        span_s=span_ns * 1e-9,
        ttft_p50_ms=ttft_p50,
        ttft_p99_ms=ttft_p99,
        tpot_p50_ms=tpot_p50,
        tpot_p99_ms=tpot_p99,
        sched_ttft_p99_ms=(
            float(np.percentile(sched_ttft, 99)) * 1e-6 if sched_ttft.size else 0.0
        ),
        sched_tpot_p99_ms=(
            float(np.percentile(sched_tpot, 99)) * 1e-6 if sched_tpot.size else 0.0
        ),
        residency_mean=(
            stats.residency_wsum / stats.dt_sum if stats.dt_sum else 1.0
        ),
        pages_spilled=pages_spilled,
        pages_allocated=pages_allocated,
        kv_spill_read_frac=(
            stats.kv_rd_bytes_dram / kv_rd_total if kv_rd_total else 0.0
        ),
        bank_conflict_rate=result.bank_conflict_rate,
        mean_queue_depth=result.mean_queue_depth,
        bytes=trace_byte_counts(trace, system),
        sim=result,
    )


def score_requests(
    trace: Trace,
    *,
    system: HybridMemorySystem,
    sim_config: SimConfig,
    recorder=None,
    **logical,
) -> ServeReport:
    """Replay a lowered serving trace and distill the :class:`ServeReport`.

    ``logical`` are :func:`assemble_report`'s request keywords.
    """
    result, schedule, orig_idx = simulate_trace(trace, sim_config,
                                                return_schedule=True,
                                                recorder=recorder)
    # Per-request token-completion times from the replay (tagged events).
    ttft, tpot = replay_token_times(trace.tag[orig_idx], schedule.finish_ns,
                                    logical["arrival_by_rid"])
    return assemble_report(result, ttft, tpot, trace, system, **logical)


def summarize_report(r: ServeReport) -> str:
    """Human-readable dump, mirroring ``repro.sim.validate.summarize``."""
    return "\n".join([
        f"requests             : {r.completed}/{r.n_requests} completed "
        f"in {r.n_steps} steps ({r.span_s * 1e3:.1f} ms span)",
        f"throughput           : offered {r.offered_qps:.1f} rps, "
        f"achieved {r.achieved_qps:.1f} rps",
        f"TTFT p50/p99         : {r.ttft_p50_ms:.2f} / {r.ttft_p99_ms:.2f} ms "
        f"(sched-clock p99 {r.sched_ttft_p99_ms:.2f} ms)",
        f"TPOT p50/p99         : {r.tpot_p50_ms:.3f} / {r.tpot_p99_ms:.3f} ms "
        f"(sched-clock p99 {r.sched_tpot_p99_ms:.3f} ms)",
        f"GLB page residency   : {r.residency_mean * 100:.1f}% "
        f"({r.pages_spilled} pages spilled, "
        f"{r.kv_spill_read_frac * 100:.1f}% of KV read bytes from DRAM)",
        f"bank conflict rate   : {r.bank_conflict_rate * 100:.2f}%",
        f"queue depth (mean)   : {r.mean_queue_depth:.2f}",
        f"bytes glb/dram       : {r.bytes['glb_bytes'] / 1e6:.1f} / "
        f"{r.bytes['dram_bytes'] / 1e6:.1f} MB",
    ])
