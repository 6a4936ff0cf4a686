"""Deterministic fault injection for the serving pricers and the fleet.

Two mechanisms, deliberately split so nothing is double-counted and every
pricing path (``TechPricer.price_step``, ``NeutralRun.price``) stays
operand-identical:

1. **Expectation-level derating** (:func:`derate_system`): the *always-on*
   reliability machinery — the write-verify read after every write, the
   expected read-disturb corrective rewrite, and the ECC codec + check-bit
   overheads — folds into the :class:`~repro.core.memory_system.ArrayPPA`
   latency/energy/area/leakage once, when the faulted system is built.
   Every pricing path and ``trace_byte_counts`` then agree for free.  The
   derated array carries ``spec_name + "+rel"`` so spec-identity checks
   know it is a bespoke build.
2. **Discrete seeded injection** (:class:`FaultModel`): the *stochastic*
   residue — verify-failed writes that must be retried, and transient
   bank faults that take a bank offline for one remap window — drawn from
   the counter RNG keyed on ``(seed, stream, event index)``.  Retries
   scale the originating write event's access count (service *and* energy,
   so byte accounting stays self-consistent) rather than appending new
   events, which keeps fresh-line numbering, coalescing, and the sweep's
   schedule-invariance certificate untouched; bank faults remap the local
   bank to its neighbor for the window, keyed on the *global* (replica,
   bank) id and the absolute window index so the exact and shared paths
   draw identically whenever their schedules coincide.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.faults.config import FaultConfig
from repro.faults.reliability import ECC_SCHEMES, ReliabilitySpec
from repro.faults.rng import (
    STREAM_BANK_WINDOW,
    STREAM_REPLICA_LIFE,
    STREAM_WRITE_RETRY,
    counter_uniform,
)


def reliability_for(system) -> ReliabilitySpec | None:
    """The registered reliability block behind a system's GLB, if any.

    Resolves through the registry by ``spec_name`` (stripping the ``+rel``
    derating suffix); bespoke/unregistered arrays have no reliability data
    and inject nothing.
    """
    from repro.spec import UnknownTechnologyError, get_tech

    glb = system.glb
    name = getattr(glb, "spec_name", glb.technology).split("+", 1)[0]
    try:
        return get_tech(name).reliability
    except UnknownTechnologyError:
        return None


def derate_system(system, faults: FaultConfig | None):
    """The reliability-derated twin of ``system`` (or ``system`` itself).

    With ``faults=None``, a technology without reliability data, or a
    trivial (ideal/SRAM) reliability block, returns the input object
    unchanged — the zero-fault path stays bit-identical to the goldens.
    """
    if faults is None:
        return system
    rel = reliability_for(system)
    if rel is None or rel.is_trivial:
        return system
    glb = system.glb
    ecc = ECC_SCHEMES[rel.ecc]
    rd_lat, wr_lat = glb.read_latency_ns, glb.write_latency_ns
    rd_e = glb.read_energy_pj_per_access
    wr_e = glb.write_energy_pj_per_access
    if rel.write_error_rate > 0.0:
        # Write-verify: every write is followed by a verify read.
        wr_lat = wr_lat + rd_lat
        wr_e = wr_e + rd_e
    rdr = rel.read_disturb_rate * faults.read_disturb_scale
    if rdr > 0.0:
        # Expected corrective rewrite per disturbed read.
        rd_e = rd_e + rdr * wr_e
    if ecc.latency_overhead:
        rd_lat = rd_lat * (1.0 + ecc.latency_overhead)
        wr_lat = wr_lat * (1.0 + ecc.latency_overhead)
    if ecc.energy_overhead:
        rd_e = rd_e * (1.0 + ecc.energy_overhead)
        wr_e = wr_e * (1.0 + ecc.energy_overhead)
    glb = dataclasses.replace(
        glb,
        read_latency_ns=rd_lat,
        write_latency_ns=wr_lat,
        read_energy_pj_per_access=rd_e,
        write_energy_pj_per_access=wr_e,
        area_mm2=glb.area_mm2 * (1.0 + ecc.area_overhead),
        leakage_w=glb.leakage_w * (1.0 + ecc.area_overhead),
        spec_name=glb.spec_name + "+rel",
    )
    return dataclasses.replace(system, glb=glb)


class FaultModel:
    """Per-run, per-technology discrete fault injector (counter-RNG keyed).

    The write-retry stream is indexed by the within-class global GLB-write
    event index, which is identical across the scalar, batched, and
    neutral-run pricing paths (each concatenates per class in block order);
    :meth:`write_acc` keeps a running counter for the streaming path and
    :meth:`write_acc_at` takes explicit offsets for the batched ones.
    Bank-offline draws are stateless: pure functions of the global bank id
    and the absolute time window.
    """

    def __init__(self, faults: FaultConfig, reliability: ReliabilitySpec | None,
                 n_banks: int, n_replicas: int = 1):
        rel = reliability or ReliabilitySpec()
        self.faults = faults
        self.seed = faults.seed
        self.nb = max(1, int(n_banks))
        self.n_replicas = max(1, int(n_replicas))
        self.p_retry = rel.write_error_rate * faults.write_error_scale
        self.window_ns = faults.bank_window_us * 1e3
        self.p_offline = min(
            1.0, rel.bank_fault_rate_hz * faults.bank_fault_scale
            * self.window_ns * 1e-9,
        )
        self._wr_seen = 0  # streaming within-class GLB-write event counter
        # Aggregate stats (whole numbers in float64: order-independent sums).
        self.retry_accesses = 0.0
        self.banks_remapped = 0

    # -- write-verify retries ------------------------------------------------
    def write_acc(self, acc: np.ndarray) -> np.ndarray:
        """Streaming path: consume the next ``acc.size`` retry draws."""
        start = self._wr_seen
        self._wr_seen += acc.size
        return self.write_acc_at(acc, start)

    def write_acc_at(self, acc: np.ndarray, start: int) -> np.ndarray:
        """Retry-inflated access counts for GLB-write events at a given
        within-class global offset.  ``floor`` of the expectation is always
        paid; the fractional residue is a Bernoulli draw per event."""
        if self.p_retry <= 0.0 or acc.size == 0:
            return acc
        exp = acc * self.p_retry
        base = np.floor(exp)
        u = counter_uniform(self.seed, STREAM_WRITE_RETRY,
                            start + np.arange(acc.size))
        extra = base + (u < (exp - base))
        self.retry_accesses += float(extra.sum())
        return acc + extra

    # -- transient bank-offline windows --------------------------------------
    def remap_banks(self, bank: np.ndarray, t_ns, replica) -> np.ndarray:
        """Remap accesses to banks that are offline in their time window.

        A bank is offline in window ``w`` iff its ``(global bank, w)`` draw
        falls under ``bank_fault_rate * window``; its accesses shift one
        bank over (mod the replica's bank count).  Stateless per event —
        identical draws in the streaming and batched paths.
        """
        if self.p_offline <= 0.0 or bank.size == 0:
            return bank
        win = np.floor_divide(
            np.asarray(t_ns, np.float64), self.window_ns
        ).astype(np.int64)
        g = bank + np.asarray(replica, np.int64) * self.nb
        u = counter_uniform(self.seed, STREAM_BANK_WINDOW, g, win)
        off = u < self.p_offline
        n = int(np.count_nonzero(off))
        if n:
            self.banks_remapped += n
            bank = np.where(off, (bank + 1) % self.nb, bank)
        return bank

    def stats(self) -> dict:
        return {
            "retry_accesses": self.retry_accesses,
            "banks_remapped": self.banks_remapped,
        }


def fault_model_for(system, faults: FaultConfig | None,
                    n_replicas: int = 1) -> FaultModel | None:
    """A :class:`FaultModel` for one (system, campaign) pair, or ``None``."""
    if faults is None:
        return None
    return FaultModel(faults, reliability_for(system),
                      n_banks=system.glb.banks, n_replicas=n_replicas)


def replica_fail_times_ns(faults: FaultConfig, t0_ns: float,
                          n_slots: int) -> list[float]:
    """Deterministic failure time per replica slot (``inf`` = never fails).

    Explicit ``replica_fail_ms`` entries pin exact times (storm tests);
    remaining slots draw exponential lifetimes at ``replica_mtbf_s`` from
    the replica-life counter stream.  Draws are keyed on the slot index
    only, so the schedule (and the technology, in shared sweeps) cannot
    perturb them.
    """
    times = [math.inf] * n_slots
    if faults.replica_mtbf_s > 0.0:
        u = counter_uniform(faults.seed, STREAM_REPLICA_LIFE,
                            np.arange(n_slots))
        life_ns = -np.log1p(-u) * faults.replica_mtbf_s * 1e9
        times = [t0_ns + float(t) for t in life_ns]
    for slot, t_ms in faults.replica_fail_ms:
        if slot < n_slots:
            times[slot] = t0_ns + t_ms * 1e6
    return times
