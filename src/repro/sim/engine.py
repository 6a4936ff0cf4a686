"""Vectorized event-level replay of a memory-system trace.

The core recurrence is per-resource FIFO service:

    start_i  = max(t_issue_i, finish_{i-1})        (same bank, issue order)
    finish_i = start_i + service_i

Rather than a Python per-event loop, the engine sorts events by
``(resource, t_issue)`` once and solves the recurrence in closed form:
within a bank segment, ``finish_i = S_i + max_{j<=i}(t_j - S_{j-1})`` where
``S`` is the in-segment cumulative service.  The running max is a single
``cummax`` over the whole array using a per-segment offset large enough that
earlier segments can never win — O(N log N) total, millions of events per
second.  The same offset trick turns per-bank queue-depth measurement into
one global ``searchsorted``.

A write-coalescing pre-pass merges repeated writes to the same ``line``
within a time window (the KV-append pattern in serving traces), modelling a
simple write-combining buffer in front of the banks.

``backend="jax"`` runs the scan with ``jax.lax.cummax`` instead of numpy;
``backend="pallas"`` routes it through the chunked associative-scan kernel
in ``repro.kernels.segmented_replay`` (interpret mode off-TPU).  Both are
**bit-identical** to the numpy path: the scan is comparisons only, and the
offset encode/decode are single elementwise IEEE ops — see
``repro.kernels.segmented_replay.ops`` for the exactness argument and
``tests/test_replay_kernel.py`` for the differential pin.

:func:`replay_schedule_batch` replays many pricings of one shared event
stream (the serving sweep's per-technology traces) in a single batched
pass — shared time sort, batched per-row segment bookkeeping, and one fused
device scan instead of per-technology host round-trips.

Both replays open one ``replay`` span (:mod:`repro.obs`) around the whole
call, and a ``sort`` span inside it around the sort and the segment
bookkeeping; the scan's own phases nest below (see
``repro.kernels.segmented_replay.ops``).
"""

from __future__ import annotations

import dataclasses
import difflib

import numpy as np

from repro.obs import core as obs
from repro.sim.trace import (
    EXPOSED_KINDS,
    KIND_DRAM_RD,
    KIND_DRAM_WR,
    KIND_GLB_WR,
    KIND_NAMES,
    KIND_PREFETCH_RD,
    KIND_PREFETCH_WR,
    Trace,
)


BACKENDS = ("numpy", "jax", "pallas")


class UnknownBackendError(ValueError):
    """Raised for a replay backend name outside :data:`BACKENDS`.

    A typo used to fall through every ``backend == ...`` branch and silently
    run numpy; now it fails loudly with a near-miss suggestion (same idiom
    as ``repro.spec.UnknownTechnologyError``).
    """

    def __init__(self, name: str, known: tuple[str, ...] = BACKENDS):
        near = difflib.get_close_matches(name, known, n=3, cutoff=0.5)
        hint = f"; did you mean {', '.join(repr(n) for n in near)}?" if near else ""
        super().__init__(
            f"unknown replay backend {name!r}{hint} "
            f"(available: {', '.join(known)})"
        )
        self.name = name
        self.suggestions = tuple(near)


def resolve_backend(backend: str) -> str:
    """Map ``"auto"`` to the fastest backend for this platform and validate
    everything else.

    On an accelerator (``jax.default_backend() != "cpu"``) that is the
    fused jax program; on CPU it is numpy — a serial
    ``np.maximum.accumulate`` beats XLA's O(n log n) associative-scan
    lowering plus transfer overhead there (measured in
    ``benchmarks/replay_bench.py``; every backend is bit-identical, so this
    is purely a performance choice).
    """
    if backend == "auto":
        try:
            import jax
        except ImportError:
            return "numpy"
        backend = "jax" if jax.default_backend() != "cpu" else "numpy"
    if backend not in BACKENDS:
        raise UnknownBackendError(backend)
    if backend != "numpy":
        from repro.device import ensure_compile_cache

        ensure_compile_cache()
    return backend


@dataclasses.dataclass(frozen=True)
class SimConfig:
    coalesce_window_ns: float = 0.0  # 0 disables the write-combining buffer
    backend: str = "numpy"  # "numpy" | "jax" | "pallas" | "auto"
    # Per-kind latency histograms cost several masked percentile passes; the
    # serving scorers (which only consume the headline metrics) switch them
    # off.  ``per_kind`` is {} when disabled.
    kind_stats: bool = True

    def __post_init__(self):
        # "auto" is resolved eagerly so every downstream branch sees a
        # concrete backend name; anything else must be a known backend.
        object.__setattr__(self, "backend", resolve_backend(self.backend))


_EXPOSED_LUT = np.zeros(8, bool)
_EXPOSED_LUT[list(EXPOSED_KINDS)] = True


@dataclasses.dataclass(frozen=True)
class KindStats:
    n_events: int
    busy_ns: float
    mean_latency_ns: float
    p50_latency_ns: float
    p99_latency_ns: float


@dataclasses.dataclass(frozen=True)
class SimResult:
    """Replay outcome: system metrics + congestion statistics."""

    # -- headline (comparable to evaluate_system) --
    latency_s: float  # exposed-path makespan (memory-system latency)
    runtime_s: float  # max(compute floor, exposed, hidden stream)
    energy_j: float
    dram_energy_j: float
    glb_energy_j: float
    leakage_energy_j: float
    hidden_stream_s: float
    compute_time_s: float
    # -- congestion metrics the analytic model cannot see --
    bank_conflict_rate: float  # fraction of events that waited in a queue
    mean_wait_ns: float
    p50_latency_ns: float  # wait + service, exposed events
    p99_latency_ns: float
    mean_queue_depth: float
    max_queue_depth: int
    glb_utilization: float  # busy / (banks * makespan)
    dram_utilization: float
    # -- bookkeeping --
    n_events: int
    n_simulated: int  # after coalescing
    coalesced_writes: int
    coalesced_energy_pj: float
    per_kind: dict[str, KindStats]


def _cummax(x: np.ndarray, backend: str) -> np.ndarray:
    if backend == "numpy":
        return np.maximum.accumulate(x)
    from repro.kernels.segmented_replay.ops import cummax

    scan = "pallas" if backend == "pallas" else "lax"
    return cummax(np.asarray(x)[None], scan=scan)[0]


def coalesce_dropped_indices(
    t_issue_ns: np.ndarray, kind: np.ndarray, line: np.ndarray,
    window_ns: float,
) -> np.ndarray:
    """Indices of writes absorbed by the combining buffer.

    The first write of each (line, window-bucket) group is kept (one
    physical write-back); later ones are dropped.  Depends only on issue
    times, kinds, and line ids — all technology-invariant in the serving
    sweep, which is why the batched replay computes this mask once and
    shares it across technologies.
    """
    is_write = (
        ((kind == KIND_GLB_WR) | (kind == KIND_DRAM_WR) | (kind == KIND_PREFETCH_WR))
        & (line >= 0)
    )
    idx = np.flatnonzero(is_write)
    if idx.size == 0:
        return idx
    bucket = (t_issue_ns[idx] // window_ns).astype(np.int64)
    lines = line[idx]
    # Combined-key radix sort when (line, bucket) packs into int64 —
    # identical permutation to the two-key lexsort (distinct pairs map to
    # distinct keys; ties keep input order under the stable sort).
    bspan = int(bucket.max()) - int(bucket.min()) + 1
    lmax = int(lines.max()) + 1
    if lmax * bspan < 2**62:
        key = lines * bspan + (bucket - bucket.min())
        order = np.argsort(key, kind="stable")
    else:  # pragma: no cover - astronomically sparse time axis
        order = np.lexsort((bucket, lines))
    ls, bs = lines[order], bucket[order]
    dup = np.zeros(idx.size, bool)
    dup[1:] = (ls[1:] == ls[:-1]) & (bs[1:] == bs[:-1])
    return idx[order][dup]


def _coalesce_writes(trace: Trace, window_ns: float):
    """Merge writes to the same line within one window bucket.

    Returns (keep_mask, n_dropped, dropped_energy_pj).
    """
    dropped = coalesce_dropped_indices(trace.t_issue_ns, trace.kind,
                                       trace.line, window_ns)
    keep = np.ones(len(trace), bool)
    keep[dropped] = False
    return keep, int(dropped.size), float(trace.energy_pj[dropped].sum())


@dataclasses.dataclass(frozen=True)
class ReplaySchedule:
    """Per-event outcome of the FIFO replay, in ``(resource, t_issue)`` order.

    Exposed for property tests and downstream analysis: ``simulate_trace``
    reduces this to a :class:`SimResult`.  Invariants (pinned in
    tests/test_properties.py): within one resource segment ``finish`` is
    non-decreasing, ``start >= t_issue``, ``finish = start + service``.
    """

    resource: np.ndarray
    t_issue_ns: np.ndarray
    service_ns: np.ndarray
    kind: np.ndarray
    start_ns: np.ndarray
    finish_ns: np.ndarray
    wait_ns: np.ndarray
    queue_depth: np.ndarray
    # Permutation mapping schedule rows back to the caller's event order
    # (row i of this schedule is input event order[i]); lets callers join
    # per-event outcomes with side arrays such as ``Trace.tag``.
    order: np.ndarray = None


def replay_schedule(
    t_issue: np.ndarray,
    resource: np.ndarray,
    service: np.ndarray,
    kind: np.ndarray,
    backend: str = "numpy",
) -> ReplaySchedule:
    """Solve the per-resource FIFO recurrence (segmented max-plus scan)."""
    if backend not in BACKENDS:
        raise UnknownBackendError(backend)
    n = t_issue.shape[0]
    if n == 0:
        e = np.empty(0, np.float64)
        return ReplaySchedule(
            resource=np.empty(0, resource.dtype), t_issue_ns=e, service_ns=e,
            kind=np.empty(0, kind.dtype), start_ns=e, finish_ns=e, wait_ns=e,
            queue_depth=np.empty(0, np.int64), order=np.empty(0, np.int64),
        )
    with obs.span("replay"):
        return _replay_1d(t_issue, resource, service, kind, backend, n)


def _replay_1d(t_issue, resource, service, kind, backend, n) -> ReplaySchedule:
    with obs.span("sort"):
        # Serving traces append steps in clock order, so ``t_issue`` is
        # already nondecreasing; a stable radix argsort on the (small-int)
        # resource ids then yields exactly ``lexsort((t_issue, resource))``
        # — same permutation, input order preserved within (resource,
        # t_issue) ties — at O(n) instead of a comparison sort on two
        # float/int key columns.
        if (t_issue.size > 1 and t_issue[0] <= t_issue[-1]
                and np.all(np.diff(t_issue) >= 0)):
            order = np.argsort(resource, kind="stable")
        else:
            order = np.lexsort((t_issue, resource))
        res_s = resource[order]
        t_s = t_issue[order]
        svc_s = service[order]
        kind_s = kind[order]

        new_seg = np.empty(n, bool)
        new_seg[0] = True
        new_seg[1:] = res_s[1:] != res_s[:-1]
        seg_id = np.cumsum(new_seg) - 1
        cs = np.cumsum(svc_s)
        seg_first = np.flatnonzero(new_seg)
        seg_len = np.diff(np.append(seg_first, n))
        seg_base = np.repeat(cs[seg_first] - svc_s[seg_first], seg_len)
        s_local = cs - seg_base  # inclusive in-segment cumulative service
        v = t_s - (s_local - svc_s)
    big = float(v.max() - v.min()) + 1.0
    running_max = _cummax(v + seg_id * big, backend) - seg_id * big
    finish = s_local + running_max
    start = finish - svc_s
    wait = start - t_s

    # --- queue depth: events in flight (same bank) at each issue -----------
    big2 = float(max(finish.max(), t_s.max()) - min(finish.min(), t_s.min())) + 1.0
    finish_aug = finish + seg_id * big2
    depth = np.arange(n) - np.searchsorted(finish_aug, t_s + seg_id * big2, side="left")

    return ReplaySchedule(
        resource=res_s,
        t_issue_ns=t_s,
        service_ns=svc_s,
        kind=kind_s,
        start_ns=start,
        finish_ns=finish,
        wait_ns=wait,
        queue_depth=depth,
        order=order,
    )


@dataclasses.dataclass(frozen=True)
class BatchedReplaySchedule:
    """R independent pricings of one event stream, replayed in one pass.

    Every array is ``(R, n)``; row ``r`` is bit-identical to
    ``replay_schedule`` on that row's 1-D inputs (pinned by
    ``tests/test_replay_kernel.py``).  :meth:`row` materializes one row as a
    plain :class:`ReplaySchedule` (e.g. for the timeline recorder).
    """

    resource: np.ndarray
    t_issue_ns: np.ndarray
    service_ns: np.ndarray
    kind: np.ndarray
    start_ns: np.ndarray
    finish_ns: np.ndarray
    wait_ns: np.ndarray
    queue_depth: np.ndarray
    order: np.ndarray

    def row(self, i: int) -> ReplaySchedule:
        return ReplaySchedule(
            resource=self.resource[i], t_issue_ns=self.t_issue_ns[i],
            service_ns=self.service_ns[i], kind=self.kind[i],
            start_ns=self.start_ns[i], finish_ns=self.finish_ns[i],
            wait_ns=self.wait_ns[i], queue_depth=self.queue_depth[i],
            order=self.order[i],
        )


def replay_schedule_batch(
    t_issue: np.ndarray,
    resource: np.ndarray,
    service: np.ndarray,
    kind: np.ndarray,
    backend: str = "numpy",
) -> BatchedReplaySchedule:
    """Replay ``R`` pricings of one shared event stream in a batched pass.

    ``t_issue`` and ``kind`` are shared ``(n,)`` columns (issue times and
    event kinds are technology-invariant); ``resource`` and ``service`` are
    ``(R, n)`` — one row per pricing.  Per-row results are bit-identical to
    ``replay_schedule`` on that row because every batched step is the exact
    per-row operation:

    * the time sort is shared: ``lexsort((t, res)) == ord1[argsort(res[ord1],
      stable)]`` with ``ord1 = argsort(t, stable)`` computed once (stable
      sorts compose), and the sorted-input radix fast path is per-row
      ``argsort(res, stable)`` exactly as in 1-D;
    * ``argsort``/``cumsum``/``maximum.accumulate`` along ``axis=1`` equal
      their per-row 1-D calls bit-for-bit (independent rows);
    * the segment base forward-fill ``maximum.accumulate(where(new_seg,
      cs - svc, -inf))`` propagates exact copies of the per-segment values
      (``cs`` is nondecreasing);
    * the scan stage runs only association-free ops (see
      ``repro.kernels.segmented_replay.ops``), so ``backend="jax"`` /
      ``"pallas"`` fuse it into one jitted device program while staying
      bitwise equal to numpy.
    """
    if backend not in BACKENDS:
        raise UnknownBackendError(backend)
    R, n = resource.shape
    if n == 0:
        e = np.empty((R, 0))
        return BatchedReplaySchedule(
            resource=np.empty((R, 0), resource.dtype), t_issue_ns=e,
            service_ns=e.copy(), kind=np.empty((R, 0), kind.dtype),
            start_ns=e.copy(), finish_ns=e.copy(), wait_ns=e.copy(),
            queue_depth=np.empty((R, 0), np.int64),
            order=np.empty((R, 0), np.int64),
        )
    with obs.span("replay"):
        return _replay_batch(t_issue, resource, service, kind, backend, R, n)


def _replay_batch(t_issue, resource, service, kind, backend, R, n
                  ) -> BatchedReplaySchedule:
    with obs.span("sort"):
        if (n > 1 and t_issue[0] <= t_issue[-1]
                and np.all(np.diff(t_issue) >= 0)):
            order = np.argsort(resource, axis=1, kind="stable")
        else:
            ord1 = np.argsort(t_issue, kind="stable")
            order = ord1[np.argsort(resource[:, ord1], axis=1, kind="stable")]
        res_s = np.take_along_axis(resource, order, axis=1)
        svc_s = np.take_along_axis(service, order, axis=1)
        t_s = t_issue[order]
        kind_s = kind[order]

        new_seg = np.empty((R, n), bool)
        new_seg[:, 0] = True
        new_seg[:, 1:] = res_s[:, 1:] != res_s[:, :-1]
        seg_id = np.cumsum(new_seg, axis=1) - 1
        cs = np.cumsum(svc_s, axis=1)
        seg_base = np.maximum.accumulate(
            np.where(new_seg, cs - svc_s, -np.inf), axis=1
        )
        s_local = cs - seg_base
        v = t_s - (s_local - svc_s)
        big = (v.max(axis=1) - v.min(axis=1)) + 1.0

    if backend == "numpy":
        from repro.kernels.segmented_replay.ref import replay_scan_np

        finish, start, wait, depth = replay_scan_np(
            v, seg_id, s_local, svc_s, t_s, big
        )
    else:
        from repro.kernels.segmented_replay.ops import replay_scan

        finish, start, wait, depth = replay_scan(
            v, seg_id, s_local, svc_s, t_s, big,
            scan="pallas" if backend == "pallas" else "lax",
        )

    return BatchedReplaySchedule(
        resource=res_s, t_issue_ns=t_s, service_ns=svc_s, kind=kind_s,
        start_ns=start, finish_ns=finish, wait_ns=wait, queue_depth=depth,
        order=order,
    )


def simulate_trace(
    trace: Trace, config: SimConfig = SimConfig(), return_schedule: bool = False,
    recorder=None,
):
    """Replay a trace; returns a :class:`SimResult`.

    With ``return_schedule=True`` returns ``(result, schedule, orig_idx)``
    where ``orig_idx[i]`` is the original trace index of schedule row ``i``
    (coalesced-away writes excluded) — the join key for per-event side
    arrays such as ``Trace.tag``.

    ``recorder`` (a :class:`repro.obs.TimelineRecorder`) taps the solved
    schedule for Perfetto export — per-bank busy intervals and queue depth.
    Recording is read-only: every metric is bit-identical with or without
    a recorder attached (pinned by ``tests/test_obs.py``).
    """
    n_total = len(trace)
    t_issue, resource = trace.t_issue_ns, trace.resource
    service, energy, kind = trace.service_ns, trace.energy_pj, trace.kind

    kept = np.arange(n_total, dtype=np.int64)
    coalesced, coalesced_e = 0, 0.0
    if config.coalesce_window_ns > 0 and n_total:
        keep, coalesced, coalesced_e = _coalesce_writes(trace, config.coalesce_window_ns)
        kept = np.flatnonzero(keep)
        t_issue, resource = t_issue[keep], resource[keep]
        service, energy, kind = service[keep], energy[keep], kind[keep]
    n = t_issue.shape[0]

    if n == 0:
        empty = KindStats(0, 0.0, 0.0, 0.0, 0.0)
        leak = trace.leakage_w * trace.compute_time_s
        result = SimResult(
            latency_s=0.0, runtime_s=trace.compute_time_s, energy_j=leak,
            dram_energy_j=0.0, glb_energy_j=0.0, leakage_energy_j=leak,
            hidden_stream_s=0.0, compute_time_s=trace.compute_time_s,
            bank_conflict_rate=0.0, mean_wait_ns=0.0, p50_latency_ns=0.0,
            p99_latency_ns=0.0, mean_queue_depth=0.0, max_queue_depth=0,
            glb_utilization=0.0, dram_utilization=0.0, n_events=n_total,
            n_simulated=0, coalesced_writes=coalesced,
            coalesced_energy_pj=coalesced_e, per_kind={"all": empty},
        )
        if return_schedule:
            empty_sched = replay_schedule(
                t_issue, resource, service, kind, config.backend
            )
            return result, empty_sched, kept
        return result

    # --- per-bank FIFO replay (sort + segmented max-plus scan) -------------
    sched = replay_schedule(t_issue, resource, service, kind, config.backend)
    if recorder is not None:
        recorder.record_replay(sched, trace)
    res_s, t_s = sched.resource, sched.t_issue_ns
    svc_s, kind_s = sched.service_ns, sched.kind
    finish, wait, depth = sched.finish_ns, sched.wait_ns, sched.queue_depth

    # --- metrics ------------------------------------------------------------
    exposed = _EXPOSED_LUT[kind_s]
    hidden = ~exposed
    latency_ns = float(finish[exposed].max() - t_s[exposed].min()) if exposed.any() else 0.0
    hidden_ns = float(finish[hidden].max() - t_s[hidden].min()) if hidden.any() else 0.0
    runtime_s = max(trace.compute_time_s, latency_ns * 1e-9, hidden_ns * 1e-9)

    is_dram_kind = (kind == KIND_DRAM_RD) | (kind == KIND_DRAM_WR) | (
        kind == KIND_PREFETCH_RD) | (kind == KIND_PREFETCH_WR)
    dram_e = float(energy[is_dram_kind].sum()) * 1e-12
    glb_e = float(energy[~is_dram_kind].sum()) * 1e-12
    leak_e = trace.leakage_w * runtime_s

    total_lat = wait + svc_s
    # p50/p99 are exposed-path metrics; a hidden-only trace reports 0 (like
    # latency_s) rather than silently switching population.
    exp_lat = total_lat[exposed] if exposed.any() else np.zeros(1)
    # Conflict threshold: the closed-form scan carries ~1e-4 ns float64
    # rounding at 1e10-ns time magnitudes; 1e-3 ns is still far below any
    # real service time, so only genuine queueing counts as a conflict.
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
        p50, p99 = np.percentile(lat, (50, 99))  # one partition, both qs
        per_kind[name] = KindStats(
            n_events=int(m.sum()),
            busy_ns=float(svc_s[m].sum()),
            mean_latency_ns=float(lat.mean()),
            p50_latency_ns=float(p50),
            p99_latency_ns=float(p99),
        )

    result = SimResult(
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
            dram_busy / (trace.n_dram_channels * latency_ns) if latency_ns > 0 else 0.0
        ),
        n_events=n_total,
        n_simulated=int(n),
        coalesced_writes=coalesced,
        coalesced_energy_pj=coalesced_e,
        per_kind=per_kind,
    )
    if return_schedule:
        return result, sched, kept[sched.order]
    return result
