"""repro.serve.fleet: replica-axis refactor — 1-replica bit-identity golden,
router policies, prefill/decode disaggregation, autoscaler, fleet sweep
shared-vs-exact, scenario forward-compat, cost-per-token knee."""

import dataclasses
import pathlib

import numpy as np
import pytest

from _golden_digest import report_digest, report_lines, trace_digest
from repro.core.memory_system import HybridMemorySystem, glb_array
from repro.core.workload import NLP_TABLE_V
from repro.dse.serving import ServingSLO, ServingSweepSpec, evaluate_serving_grid, slo_knee
from repro.faults import FaultConfig
from repro.serve import (
    FleetConfig,
    ServeEngineConfig,
    ServingGridSpec,
    UnknownRouterPolicyError,
    closed_loop_serving,
    fleet_serving,
    sweep_serving_grid,
)
from repro.serve.fleet import ROUTER_POLICIES
from repro.sim import ServingConfig
from repro.spec import Scenario, load_scenario

SCENARIOS = pathlib.Path(__file__).parent.parent / "examples" / "scenarios"


def _gpt2():
    return next(s for s in NLP_TABLE_V if s.name == "gpt2")


def _system(tech="sot_opt", cap=16.0):
    return HybridMemorySystem(glb=glb_array(tech, cap))


def _cfg(**kw):
    base = dict(n_requests=12, arrival_rate_rps=300.0, prompt_len=64,
                decode_len=32, seed=7)
    base.update(kw)
    return ServingConfig(**base)


def _ecfg(**kw):
    return ServeEngineConfig(max_batch=kw.pop("max_batch", 8), **kw)


def _trace_identical(a, b) -> bool:
    return all(
        np.array_equal(getattr(a, f.name), getattr(b, f.name))
        if isinstance(getattr(a, f.name), np.ndarray)
        else getattr(a, f.name) == getattr(b, f.name)
        for f in dataclasses.fields(a)
    )


# ---------------------------------------------------------------------------
# The single-accelerator closed loop is the R=1 fleet, pinned to literals
# ---------------------------------------------------------------------------

# (faults, trace sha256, report sha256) of closed_loop_serving on _system(),
# _gpt2(), _cfg(), _ecfg(); see tests/_golden_digest.py.
_CLOSED_LOOP_PINS = {
    "none": (
        None,
        "f16f8feedf293fa5afb7307531c1c2926bfab0e08c827826d2cf86e19e1dd7c1",
        "8cbc0ff4468e83f6d08b001b980bba621f58a909334376b98053cae971082440",
    ),
    # Injects write retries and bank-offline remap windows.
    "faulted": (
        FaultConfig(seed=3, write_error_scale=50.0, bank_fault_scale=1e6),
        "b851c68d3d01fb35adbad373cd102f9547c0dda4801e95c9a53056fade7be4a6",
        "7123388829d04801e5a443cd78bacf2d0872138a354e74df1690a9e61c5638b0",
    ),
}


def test_one_replica_fleet_bit_identical_to_closed_loop():
    system, spec = _system(), _gpt2()
    cfg, ecfg = _cfg(), _ecfg()
    _, trace_pin, report_pin = _CLOSED_LOOP_PINS["none"]
    tr_ref, rep_ref = closed_loop_serving(system, spec, cfg, ecfg)
    assert trace_digest(tr_ref) == trace_pin
    assert report_digest(rep_ref) == report_pin, report_lines(rep_ref)
    tr_one, fr_one = fleet_serving(system, spec, cfg, ecfg, FleetConfig())
    assert trace_digest(tr_one) == trace_pin
    assert report_digest(fr_one.report) == report_pin
    assert fr_one.n_replicas == 1 and fr_one.n_replicas_peak == 1
    assert fr_one.mean_alive_replicas == 1.0
    # 1 chip: cost-per-token degenerates to area x energy/token.
    assert fr_one.cost_per_token == pytest.approx(
        system.glb.area_mm2 * fr_one.energy_per_token_j)


def test_faulted_closed_loop_golden():
    faults, trace_pin, report_pin = _CLOSED_LOOP_PINS["faulted"]
    tr, rep = closed_loop_serving(_system(), _gpt2(), _cfg(), _ecfg(),
                                  faults=faults)
    stats = tr.meta["fault_stats"]
    assert stats["retry_accesses"] > 0 and stats["banks_remapped"] > 0
    # The trace meta records the caller's campaign unchanged.
    assert tr.meta["faults"] == faults.to_dict()
    assert trace_digest(tr) == trace_pin
    assert report_digest(rep) == report_pin, report_lines(rep)


def test_pre_fleet_scenario_json_runs_bit_identical():
    # Forward-compat golden: a scenario JSON written before the fleet layer
    # existed (no "fleet" key) must resolve to the trivial FleetConfig and
    # reproduce the closed loop bit for bit.
    sc = Scenario.from_dict({
        "name": "pre-fleet", "domain": "nlp", "workloads": ["gpt2"],
        "mode": "serving", "capacities_mb": [16], "technologies": ["sot_opt"],
        "qps": [300.0], "n_requests": 10, "prompt_len": 64, "decode_len": 32,
        "max_batch": 8, "seed": 4,
    })
    fcfg = sc.fleet_config()
    assert fcfg == FleetConfig() and fcfg.trivial
    system, spec = _system(), _gpt2()
    cfg, ecfg = sc.serving_config(), sc.engine_config()
    tr_ref, _ = closed_loop_serving(system, spec, cfg, ecfg)
    tr_one, _ = fleet_serving(system, spec, cfg, ecfg, fcfg)
    assert _trace_identical(tr_ref, tr_one)


# ---------------------------------------------------------------------------
# FleetConfig validation / serialization
# ---------------------------------------------------------------------------


def test_unknown_router_policy_suggests_near_miss():
    with pytest.raises(UnknownRouterPolicyError) as ei:
        FleetConfig(router="round_robbin").validate()
    assert "round_robin" in str(ei.value)
    # The error doubles as both lookup-exception flavors.
    assert isinstance(ei.value, ValueError) and isinstance(ei.value, KeyError)


@pytest.mark.parametrize("bad", [
    dict(n_replicas=0),
    dict(disaggregation=True),  # needs >= 2 replicas
    dict(n_replicas=3, disaggregation=True, n_prefill_replicas=3),
    dict(transfer_gb_s=0.0),
    dict(n_replicas=4, autoscale=True, max_replicas=2),
    dict(autoscale=True, autoscale_window_ms=0.0),
    dict(autoscale=True, autoscale_low_frac=1.0),
    dict(affinity_groups=0),
])
def test_fleet_config_rejects_bad_knobs(bad):
    with pytest.raises(ValueError):
        FleetConfig(**bad).validate()


def test_fleet_config_dict_roundtrip_and_unknown_key():
    fc = FleetConfig(n_replicas=4, router="least_loaded",
                     disaggregation=True, n_prefill_replicas=2)
    assert FleetConfig.from_dict(fc.to_dict()) == fc
    with pytest.raises(ValueError, match="unknown fleet field"):
        FleetConfig.from_dict({"n_replica": 4})
    assert FleetConfig(autoscale=True, max_replicas=6).capacity_replicas == 6
    assert not FleetConfig(n_replicas=2).trivial


# ---------------------------------------------------------------------------
# Router policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ROUTER_POLICIES)
def test_router_policy_completes_all_requests(policy):
    _, fr = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(),
                          FleetConfig(n_replicas=4, router=policy))
    assert fr.report.completed == fr.report.n_requests == 12
    assert sum(fr.routed_per_replica) == 12
    assert sum(fr.completed_per_replica) == 12
    assert fr.router == policy


def test_round_robin_routes_evenly():
    _, fr = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(),
                          FleetConfig(n_replicas=4, router="round_robin"))
    assert fr.routed_per_replica == (3, 3, 3, 3)


def test_prefix_affinity_pins_groups():
    # With affinity_groups == n_replicas, request rid lands on rid % n — a
    # group's requests (shared prefix) always hit the same replica.
    _, fr = fleet_serving(
        _system(), _gpt2(), _cfg(), _ecfg(),
        FleetConfig(n_replicas=4, router="prefix_affinity",
                    affinity_groups=4))
    assert fr.routed_per_replica == (3, 3, 3, 3)


# ---------------------------------------------------------------------------
# Prefill/decode disaggregation
# ---------------------------------------------------------------------------


def test_disaggregation_streams_every_prompt_and_completes():
    fc = FleetConfig(n_replicas=4, disaggregation=True,
                     n_prefill_replicas=1, router="least_loaded")
    _, fr = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), fc)
    assert fr.disaggregated
    assert fr.report.completed == fr.report.n_requests == 12
    # Every request's KV pages cross the interconnect exactly once.
    assert fr.kv_xfer_transfers == 12
    assert fr.kv_xfer_bytes > 0
    # The prefill replica routes every prompt but completes none (the
    # decode halves finish on decode replicas).
    assert fr.routed_per_replica[0] == 12
    assert fr.completed_per_replica[0] == 0
    assert sum(fr.completed_per_replica[1:]) == 12


def test_disaggregation_transfer_bytes_scale_with_bandwidth():
    # Same fleet at 1/8 the interconnect bandwidth: identical bytes moved,
    # strictly-later decode starts => TTFT p99 cannot improve.
    base = FleetConfig(n_replicas=3, disaggregation=True, transfer_gb_s=64.0)
    slow = dataclasses.replace(base, transfer_gb_s=8.0)
    _, fr_fast = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), base)
    _, fr_slow = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), slow)
    assert fr_fast.kv_xfer_bytes == fr_slow.kv_xfer_bytes
    assert fr_slow.report.ttft_p99_ms >= fr_fast.report.ttft_p99_ms


# ---------------------------------------------------------------------------
# Autoscaler
# ---------------------------------------------------------------------------


def test_autoscaler_scales_up_under_slo_pressure():
    # An SLO far below any achievable TTFT forces a scale-up every window.
    fc = FleetConfig(n_replicas=1, autoscale=True, max_replicas=4,
                     autoscale_window_ms=0.02, autoscale_ttft_slo_ms=1e-6)
    _, fr = fleet_serving(_system(), _gpt2(),
                          _cfg(n_requests=24, arrival_rate_rps=2000.0),
                          _ecfg(max_batch=4), fc)
    assert fr.autoscaled
    assert fr.report.completed == fr.report.n_requests
    assert fr.n_replicas_peak > 1
    assert fr.n_replicas_peak <= 4
    assert fr.autoscale_events  # at least one recorded action
    assert fr.mean_alive_replicas > 1.0


def test_autoscaler_drains_idle_replicas():
    # An SLO far above any TTFT (with a high low-water fraction) drains the
    # fleet toward min_replicas; drained replicas finish their work first,
    # so everything still completes.
    fc = FleetConfig(n_replicas=3, autoscale=True, max_replicas=3,
                     min_replicas=1, autoscale_window_ms=0.02,
                     autoscale_ttft_slo_ms=1e6, autoscale_low_frac=0.99)
    _, fr = fleet_serving(_system(), _gpt2(),
                          _cfg(n_requests=24, arrival_rate_rps=2000.0),
                          _ecfg(max_batch=4), fc)
    assert fr.report.completed == fr.report.n_requests
    assert fr.autoscale_events
    # Some action shrank the alive count below the starting size.
    assert min(alive for _, alive in fr.autoscale_events) < 3
    assert fr.mean_alive_replicas < 3.0


# ---------------------------------------------------------------------------
# Fleet sweep: shared schedule vs exact fleet loops
# ---------------------------------------------------------------------------


def test_fleet_sweep_shared_matches_exact():
    fc = FleetConfig(n_replicas=3, router="least_loaded")
    grid = ServingGridSpec(qps=(300.0,), capacities_mb=(16.0,),
                           technologies=("sram", "sot_opt"), model="gpt2",
                           serving=_cfg(), engine=_ecfg(), fleet=fc)
    shared = sweep_serving_grid(grid, mode="shared", backend="numpy")
    exact = sweep_serving_grid(grid, mode="exact", backend="numpy")
    assert len(shared) == len(exact) == 2
    for rs, re_ in zip(shared, exact):
        assert rs.technology == re_.technology
        assert rs.fleet is not None and re_.fleet is not None
        # Latency metrics ride the replay's per-resource FIFO order, which
        # the shared path preserves exactly: bitwise equality required.
        for m in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                  "tpot_p99_ms", "completed"):
            assert getattr(rs.report, m) == getattr(re_.report, m), m
        # Whole-trace float reductions (energy -> cost) may differ in the
        # last ulp when two replicas step at the same timestamp (step-major
        # vs class-major append order); see sweep._grid_point.
        assert rs.fleet.cost_per_token == pytest.approx(
            re_.fleet.cost_per_token, rel=1e-12)
        assert rs.fleet.n_replicas == re_.fleet.n_replicas == 3
        assert rs.fleet.cost_per_token > 0


def test_fleet_sweep_trivial_fleet_matches_single_accelerator_rows():
    grid_kw = dict(qps=(300.0,), capacities_mb=(16.0,),
                   technologies=("sot_opt",), model="gpt2",
                   serving=_cfg(), engine=_ecfg())
    plain = sweep_serving_grid(ServingGridSpec(**grid_kw), backend="numpy")
    triv = sweep_serving_grid(
        ServingGridSpec(fleet=FleetConfig(), **grid_kw), backend="numpy")
    assert plain[0].fleet is None and triv[0].fleet is None
    assert plain[0].report.ttft_p99_ms == triv[0].report.ttft_p99_ms
    assert plain[0].report.sim.energy_j == triv[0].report.sim.energy_j


# ---------------------------------------------------------------------------
# Scenario layer: fleet block
# ---------------------------------------------------------------------------


def test_scenario_fleet_block_roundtrip_and_validation():
    d = {
        "name": "f", "domain": "nlp", "workloads": ["gpt2"],
        "mode": "serving", "capacities_mb": [16],
        "technologies": ["sot_opt"], "qps": [300.0],
        "fleet": {"n_replicas": 4, "router": "least_loaded",
                  "disaggregation": True, "n_prefill_replicas": 1},
    }
    sc = Scenario.from_dict(d)
    fc = sc.fleet_config()
    assert fc.n_replicas == 4 and fc.disaggregation and not fc.trivial
    # Unknown fleet knob -> rejected at scenario load time.
    bad = dict(d, fleet={"n_replica": 4})
    with pytest.raises(ValueError, match="unknown fleet field"):
        Scenario.from_dict(bad)
    # Router typo -> the suggestion error surfaces through validate().
    with pytest.raises(ValueError, match="least_loaded"):
        Scenario.from_dict(dict(d, fleet={"router": "least_loded"}))
    # Fleet block outside serving mode is meaningless.
    with pytest.raises(ValueError, match="serving"):
        Scenario.from_dict({
            "name": "b", "domain": "cv", "workloads": ["resnet50"],
            "mode": "inference", "technologies": ["sram", "sot_opt"],
            "fleet": {"n_replicas": 2},
        })


def test_fleet_chatbot_example_scenario_loads():
    sc = load_scenario(str(SCENARIOS / "fleet_chatbot.json"))
    fc = sc.fleet_config()
    assert fc.n_replicas == 4 and fc.disaggregation
    assert fc.router == "least_loaded"
    assert sc.resolve_technologies() == ("sram", "sot_opt", "hybrid")
    assert len(sc.qps) > 1  # bursty QPS grid


# ---------------------------------------------------------------------------
# DSE: cost-per-token rows and knee
# ---------------------------------------------------------------------------


def test_dse_fleet_rows_carry_cost_per_token():
    spec = ServingSweepSpec(
        capacities_mb=(16.0,), technologies=("sot_opt",), model="gpt2",
        qps=300.0, slo=ServingSLO(ttft_p99_ms=50.0, tpot_p99_ms=5.0),
        serving=_cfg(), engine=_ecfg(),
        fleet=FleetConfig(n_replicas=2),
    )
    rows = evaluate_serving_grid(spec, backend="numpy")
    assert len(rows) == 1
    row = rows[0]
    assert row["n_replicas"] == 2
    assert row["cost_per_token"] > 0
    assert row["energy_per_token_j"] > 0
    assert row["slo_ok"]


def test_slo_knee_prefers_cost_per_token_on_fleet_rows():
    rows = [
        {"technology": "a", "capacity_mb": 32.0, "slo_ok": True,
         "energy_j": 1.0, "cost_per_token": 9.0},
        {"technology": "b", "capacity_mb": 64.0, "slo_ok": True,
         "energy_j": 5.0, "cost_per_token": 2.0},
    ]
    out = slo_knee(rows)
    # Lower chip energy would pick "a"; the fleet cost index picks "b".
    assert out["best"]["technology"] == "b"
    assert out["knee_capacity_mb"] == {"a": 32.0, "b": 64.0}


def test_slo_knee_falls_back_to_energy_without_fleet():
    rows = [
        {"technology": "a", "capacity_mb": 32.0, "slo_ok": True,
         "energy_j": 1.0},
        {"technology": "b", "capacity_mb": 64.0, "slo_ok": True,
         "energy_j": 5.0},
    ]
    assert slo_knee(rows)["best"]["technology"] == "a"


# ---------------------------------------------------------------------------
# Observability: per-replica timeline tracks, human summary
# ---------------------------------------------------------------------------


def test_fleet_timeline_records_replica_tracks_and_transfers():
    from repro.obs import TimelineRecorder, validate_chrome_trace

    rec = TimelineRecorder()
    fc = FleetConfig(n_replicas=3, disaggregation=True, n_prefill_replicas=1)
    _, fr = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), fc,
                          recorder=rec)
    # The recorder is observational: metrics match the recorder-free run.
    _, fr_bare = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), fc)
    assert fr.report.ttft_p99_ms == fr_bare.report.ttft_p99_ms
    assert fr.kv_xfer_transfers == fr_bare.kv_xfer_transfers
    doc = rec.export()
    validate_chrome_trace(doc)
    events = doc["traceEvents"]
    # Per-replica step spans live in the fleet process group.
    fleet_pids = {e["pid"] for e in events
                  if e.get("ph") == "X" and e.get("name") == "step"}
    assert len(fleet_pids) == 1
    tids = {e["tid"] for e in events
            if e.get("ph") == "X" and e.get("name") == "step"}
    assert len(tids) == 3  # one thread per replica
    # Every KV handoff shows up as a delivery instant on the destination.
    xfers = [e for e in events if e.get("name") == "kv_xfer_in"]
    assert len(xfers) == fr.kv_xfer_transfers == 12
    assert any(e.get("name") == "alive_replicas" for e in events)


def test_summarize_fleet_mentions_every_axis():
    from repro.serve import summarize_fleet

    fc = FleetConfig(n_replicas=2, disaggregation=True, autoscale=True,
                     max_replicas=4)
    _, fr = fleet_serving(_system(), _gpt2(), _cfg(), _ecfg(), fc)
    text = summarize_fleet(fr)
    for needle in ("fleet", "replicas", "KV disaggregation", "autoscaler",
                   "cost per token"):
        assert needle in text, needle
