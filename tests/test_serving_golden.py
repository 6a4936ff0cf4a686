"""Literal golden pins of the single-accelerator serving outputs.

The closed loop and the single-accelerator sweep run as the 1-replica
fleet, so comparing them against each other would compare the fleet loop
with itself.  These pins hold every output a single-accelerator caller sees
to hex digests written down here instead: the sweep rows (``shared`` flag,
``fleet is None`` and every report field) in both modes, with and without
faults, and the Perfetto export of a recorded closed loop and of recorded
sweeps.  The closed loop's own trace and report pins live in
``tests/test_fleet.py``.
"""

import pytest

from _golden_digest import json_digest, report_digest, report_lines
from repro.core.memory_system import HybridMemorySystem, glb_array
from repro.core.workload import NLP_TABLE_V
from repro.faults import FaultConfig
from repro.obs.timeline import TimelineRecorder, validate_chrome_trace
from repro.serve import (
    ServeEngineConfig,
    ServingGridSpec,
    closed_loop_serving,
    sweep_serving_grid,
)
from repro.sim import ServingConfig

# Injects write retries and bank-offline remap windows.
_FAULTS = FaultConfig(seed=3, write_error_scale=50.0, bank_fault_scale=1e6)


def _grid(faults=None) -> ServingGridSpec:
    # Low QPS on a big GLB certifies; 1600 QPS of 512-token prompts on 8 MB
    # congests the SRAM banks, so that row takes the per-technology fallback.
    return ServingGridSpec(
        qps=(100.0, 1600.0), capacities_mb=(8.0, 64.0),
        technologies=("sram", "sot_opt"), model="gpt2",
        serving=ServingConfig(n_requests=10, prompt_len=512, decode_len=16,
                              seed=4),
        engine=ServeEngineConfig(max_batch=8), faults=faults,
    )


def _row_pin(row) -> tuple:
    return (row.technology, row.capacity_mb, row.qps, row.shared,
            row.fleet is None, report_digest(row.report))


SWEEP_PINS = {
    ("shared", False): [
        ("sram", 8.0, 100.0, True, True,
         "a05179be7ea137874f03520cadd2e1a624fe2b8d5da5c65d1f2c7f1361fe41ea"),
        ("sot_opt", 8.0, 100.0, True, True,
         "305038a3b8a2c3f4fc22fb4ca3fe60c6ee8e483d18825a4ca58029a192ca301f"),
        ("sram", 8.0, 1600.0, False, True,
         "0a30e979c132242cf453604aa1e117326c0a4d93d57b858518be97fab9306ee7"),
        ("sot_opt", 8.0, 1600.0, True, True,
         "ce5391afbed56cc3ba1f07320f710b96615868b72994ad68e9f22c0ded6cf638"),
        ("sram", 64.0, 100.0, True, True,
         "b052fc3ff2048394d5784ead56fcc6bd2a29172a87996b584ab7b1546b5fce4f"),
        ("sot_opt", 64.0, 100.0, True, True,
         "3f4bc646d188461116deb718ecd6070a1cf0f04f47803d21f4c84827c73b739e"),
        ("sram", 64.0, 1600.0, True, True,
         "7e739eda35dd9074f11160f63914c54de30d3e9bc563d6f9c49e44c1bc42d387"),
        ("sot_opt", 64.0, 1600.0, True, True,
         "4dcb35eb531f5de47a2c786ba1bb8df15cbb5f498064efc528617a8b7b11e90e"),
    ],
    ("exact", False): [
        ("sram", 8.0, 100.0, False, True,
         "a05179be7ea137874f03520cadd2e1a624fe2b8d5da5c65d1f2c7f1361fe41ea"),
        ("sot_opt", 8.0, 100.0, False, True,
         "015380642e616c9b427c92f6dbd4355be8fd8e04902cbb22a8af3792805f5590"),
        ("sram", 8.0, 1600.0, False, True,
         "0a30e979c132242cf453604aa1e117326c0a4d93d57b858518be97fab9306ee7"),
        ("sot_opt", 8.0, 1600.0, False, True,
         "6c6b7f688abcf3b529bb740060a79aa75580f19c879d1135788419935a0934bc"),
        ("sram", 64.0, 100.0, False, True,
         "b052fc3ff2048394d5784ead56fcc6bd2a29172a87996b584ab7b1546b5fce4f"),
        ("sot_opt", 64.0, 100.0, False, True,
         "3f4bc646d188461116deb718ecd6070a1cf0f04f47803d21f4c84827c73b739e"),
        ("sram", 64.0, 1600.0, False, True,
         "9f48aa4555b9dc57b73f0700d9ff7bc7d77009ac11bbfa9598264c46ba56c2a2"),
        ("sot_opt", 64.0, 1600.0, False, True,
         "4dcb35eb531f5de47a2c786ba1bb8df15cbb5f498064efc528617a8b7b11e90e"),
    ],
    ("shared", True): [
        ("sram", 8.0, 100.0, True, True,
         "a05179be7ea137874f03520cadd2e1a624fe2b8d5da5c65d1f2c7f1361fe41ea"),
        ("sot_opt", 8.0, 100.0, True, True,
         "08204351f5a2fcc4a9568d38eb394d316aec3646e793de0f54d83dbd26f12c27"),
        ("sram", 8.0, 1600.0, False, True,
         "0a30e979c132242cf453604aa1e117326c0a4d93d57b858518be97fab9306ee7"),
        ("sot_opt", 8.0, 1600.0, True, True,
         "08869d640ea232743bb65afcd0427841f7edc87d2bf2a44f9f7a55a81f408100"),
        ("sram", 64.0, 100.0, True, True,
         "b052fc3ff2048394d5784ead56fcc6bd2a29172a87996b584ab7b1546b5fce4f"),
        ("sot_opt", 64.0, 100.0, True, True,
         "f632203949489c18df0a10fd3394ebfadf2cf03e5a5e7668d0a1ff0cb176ec45"),
        ("sram", 64.0, 1600.0, True, True,
         "7e739eda35dd9074f11160f63914c54de30d3e9bc563d6f9c49e44c1bc42d387"),
        ("sot_opt", 64.0, 1600.0, True, True,
         "961926ec04cbc9d0bd19aa10003d653535dca38ef1cee04cf8e9620b6d8df38f"),
    ],
    ("exact", True): [
        ("sram", 8.0, 100.0, False, True,
         "a05179be7ea137874f03520cadd2e1a624fe2b8d5da5c65d1f2c7f1361fe41ea"),
        ("sot_opt", 8.0, 100.0, False, True,
         "04ab48a16d468840ac446886c6ead5950d104b0d8c27d41633af59f625bbdac0"),
        ("sram", 8.0, 1600.0, False, True,
         "0a30e979c132242cf453604aa1e117326c0a4d93d57b858518be97fab9306ee7"),
        ("sot_opt", 8.0, 1600.0, False, True,
         "08869d640ea232743bb65afcd0427841f7edc87d2bf2a44f9f7a55a81f408100"),
        ("sram", 64.0, 100.0, False, True,
         "b052fc3ff2048394d5784ead56fcc6bd2a29172a87996b584ab7b1546b5fce4f"),
        ("sot_opt", 64.0, 100.0, False, True,
         "d3abb40ab19d6223f63993989997f00721c23fc61e3f97a2a570142bac5c47f9"),
        ("sram", 64.0, 1600.0, False, True,
         "9f48aa4555b9dc57b73f0700d9ff7bc7d77009ac11bbfa9598264c46ba56c2a2"),
        ("sot_opt", 64.0, 1600.0, False, True,
         "72f9b1a60fe3f5d92fe0eb99219635ccc239be1ccad794d36569619b7dc68911"),
    ],
}


@pytest.mark.parametrize("mode,faulted", list(SWEEP_PINS))
def test_single_accelerator_sweep_rows_golden(mode, faulted):
    rows = sweep_serving_grid(_grid(_FAULTS if faulted else None), mode=mode,
                              backend="numpy")
    got = [_row_pin(r) for r in rows]
    assert got == SWEEP_PINS[(mode, faulted)], "\n\n".join(
        report_lines(r.report) for r in rows)
    if mode == "shared":
        # Both the certified path and the fallback ran.
        assert {r.shared for r in rows} == {True, False}


def _closed_loop_timeline() -> dict:
    rec = TimelineRecorder()
    closed_loop_serving(
        HybridMemorySystem(glb=glb_array("sot_opt", 32.0)),
        next(s for s in NLP_TABLE_V if s.name == "gpt2"),
        ServingConfig(n_requests=16, prompt_len=64, decode_len=8,
                      arrival_rate_rps=400.0, seed=3),
        ServeEngineConfig(max_batch=4), recorder=rec)
    return rec.export()


def _sweep_timeline(mode: str) -> dict:
    rec = TimelineRecorder()
    sweep_serving_grid(_grid(), mode=mode, backend="numpy", recorder=rec)
    return rec.export()


TIMELINE_PINS = {
    "closed_loop":
        "cfbc3c624ebda848e25a7fddf6af8f441e489aaa3a8f8a0909a4bb4a6eaac67a",
    "sweep_shared":
        "aaaac3ec9ab4be4628772fefcb1cb27df4fec46a94a18c16265ee1990ce7aa82",
    "sweep_exact":
        "3226131e3069becb6e2c108ec276b02393ad9a2e5b55122b887ce361f572f5cd",
}


@pytest.mark.parametrize("case", list(TIMELINE_PINS))
def test_single_accelerator_perfetto_export_golden(case):
    doc = (_closed_loop_timeline() if case == "closed_loop"
           else _sweep_timeline(case.removeprefix("sweep_")))
    assert validate_chrome_trace(doc) == []
    assert json_digest(doc) == TIMELINE_PINS[case]
