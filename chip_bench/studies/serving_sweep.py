"""Study driver: the serving sweep, as ``explore --serving`` and ``serve_sim`` run it.

A configuration names the model, technologies, engine and fleet; a traffic
file names the grid's offered loads, GLB capacities and request count; the
seed is the request-draw seed (``ServingConfig.seed``).  The program gets only
the :class:`repro.serve.ServingGridSpec` and is called through the entry users
reach, ``sweep_serving_grid(spec, backend="auto")``, over the whole grid.

:func:`check` holds the rows of the last measured study to three references
(see :mod:`chip_bench.reference`): the plain FIFO replay on the sampled replay
rows, the program's exact closed loop (``mode="exact"``, numpy replay, scalar
lowering) on sampled grid rows, and the numpy-replay sweep on every row.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.serve import FleetConfig, ServeEngineConfig, ServingGridSpec
from repro.serve.sweep import sweep_serving_grid
from repro.sim import ServingConfig
from repro.sim.engine import resolve_backend

from chip_bench.reference import max_rel_gap, replay_numbers

RATE_METRIC = "events_per_s"
EXACT_ROWS = 2  # grid rows held to the exact closed loop per run
FIFO_ROWS = 2  # replay rows held to the plain FIFO reference per run


def make_spec(config: dict, traffic: dict, seed: int) -> ServingGridSpec:
    fleet = FleetConfig(**config["fleet"]) if config.get("fleet") else FleetConfig()
    return ServingGridSpec(
        qps=tuple(float(q) for q in traffic["qps"]),
        capacities_mb=tuple(float(c) for c in traffic["capacities_mb"]),
        technologies=tuple(config["technologies"]),
        model=config["model"],
        serving=ServingConfig(n_requests=int(traffic["n_requests"]),
                              prompt_len=int(config["prompt_len"]),
                              decode_len=int(config["decode_len"]),
                              seed=int(seed)),
        engine=ServeEngineConfig(max_batch=int(config["max_batch"])),
        fleet=fleet,
    )


def run(spec: ServingGridSpec, timing: dict) -> list:
    """One whole study through the users' entry point."""
    return sweep_serving_grid(spec, backend="auto", timing=timing)


def work(rows: list) -> int:
    """Simulated events of a study: what ``events_per_s`` counts."""
    return sum(int(r.report.sim.n_events) for r in rows)


def describe(rows: list) -> dict:
    return {
        "backend": resolve_backend("auto"),
        "rows": len(rows),
        "fallback_rows": [f"{r.technology}@{r.qps:g}qps/{r.capacity_mb:g}MB"
                          for r in rows if not r.shared],
        "events_per_row": [int(r.report.sim.n_events) for r in rows],
    }


def pick_replay_rows(calls: list, rng: np.random.Generator) -> set:
    """``(call, row)`` pairs for the FIFO check: the longest, then random."""
    pairs = [(c, r) for c, call in enumerate(calls) for r in range(call["rows"])
             if call["n"]]
    if not pairs:
        return set()
    longest = max(pairs, key=lambda p: calls[p[0]]["n"])
    rest = [p for p in pairs if p != longest]
    pick = [rest[i] for i in rng.permutation(len(rest))[:FIFO_ROWS - 1]]
    return {longest, *pick}


def _one_point(spec: ServingGridSpec, row) -> ServingGridSpec:
    return dataclasses.replace(spec, qps=(row.qps,),
                               capacities_mb=(row.capacity_mb,),
                               technologies=(row.technology,))


def _row_view(row) -> dict:
    return {"report": row.report, "fleet": row.fleet}


def check(spec: ServingGridSpec, rows: list, captured: dict,
          rng: np.random.Generator) -> dict:
    """The numbers ``correct`` is decided by, each to be held to its limit."""
    numbers = replay_numbers(captured)

    by_events = sorted(range(len(rows)),
                       key=lambda i: -rows[i].report.sim.n_events)
    sample = [by_events[0]] + [by_events[1:][i] for i in
                               rng.permutation(len(rows) - 1)[:EXACT_ROWS - 1]]
    exact_gap = 0.0
    for i in sample:
        ref = sweep_serving_grid(_one_point(spec, rows[i]), mode="exact",
                                 backend="numpy", lowering="scalar")[0]
        gap, _ = max_rel_gap(_row_view(rows[i]), _row_view(ref))
        exact_gap = max(exact_gap, gap)
    numbers["report_gap_exact"] = exact_gap

    ref_rows = sweep_serving_grid(spec, backend="numpy")
    differ = abs(len(ref_rows) - len(rows))
    for got, ref in zip(rows, ref_rows):
        key = (got.technology, got.qps, got.capacity_mb, got.shared)
        if key != (ref.technology, ref.qps, ref.capacity_mb, ref.shared) \
                or max_rel_gap(_row_view(got), _row_view(ref))[0] > 0:
            differ += 1
    numbers["rows_differing_numpy"] = differ
    return numbers
