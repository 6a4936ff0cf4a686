"""Bring-up check: the simulator's device path on one TPU chip.

Drives the studies users run through the functions the CLIs call, in this
one process (a chip belongs to one process), and holds every device result
to the numpy reference:

1. device — JAX must see a TPU; there is no CPU fallback.
2. serving sweep, ``backend="auto"`` — gpt2 over every registered
   technology, two QPS points, one GLB capacity, sized so each technology
   row of a grid point replays over a million events.  ``auto`` must resolve
   to ``jax`` here, and every report (``dataclasses.asdict``) and shared-
   schedule flag must equal ``backend="numpy"``'s bit for bit.
3. the same grid through the compiled Pallas kernel (``backend="pallas"``).
4. the batch DSE grids (``explore --domain both --models all`` and the
   geometry grid) with ``backend="jax"``, every count and metric within the
   DSE's 1e-9 rtol contract of ``backend="numpy"``.

Earlier lines report events, set-up (first call, compilation included) and
warm seconds per phase: a bring-up record, not a benchmark.  The last line
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
phase raises and exits non-zero.

Usage: ``python chip_smoke.py`` from the repository root.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.core.workload import cv_model_zoo, nlp_model_zoo  # noqa: E402
from repro.device import device_info  # noqa: E402
from repro.dse import (  # noqa: E402
    GeomAxes,
    GridSpec,
    evaluate_geometry_grid,
    evaluate_workload_grid,
)
from repro.serve import (  # noqa: E402
    ServeEngineConfig,
    ServingGridSpec,
    sweep_serving_grid,
)
from repro.sim import ServingConfig  # noqa: E402
from repro.sim.engine import resolve_backend  # noqa: E402
from repro.spec import list_techs  # noqa: E402

# 256 gpt2 requests of 512 prompt + 128 decode tokens: ~2.45M replayed
# events per technology row of each grid point.
SERVING_GRID = ServingGridSpec(
    qps=(400.0, 800.0),
    capacities_mb=(64.0,),
    technologies=tuple(list_techs()),
    model="gpt2",
    serving=ServingConfig(n_requests=256, prompt_len=512, decode_len=128, seed=3),
    engine=ServeEngineConfig(max_batch=16),
)


def log(msg: str) -> None:
    print(f"[bring-up] {msg}", flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def serving_phase(name: str, backend: str, reference: list) -> None:
    """Run the sweep twice on ``backend`` and hold it to ``reference``."""
    sweep = lambda: sweep_serving_grid(SERVING_GRID, backend=backend)  # noqa: E731
    _, setup_s = timed(sweep)
    rows, warm_s = timed(sweep)
    if len(rows) != len(reference):
        raise AssertionError(f"{name}: {len(rows)} rows, numpy {len(reference)}")
    for got, ref in zip(rows, reference):
        point = (got.technology, got.qps, got.capacity_mb)
        if point != (ref.technology, ref.qps, ref.capacity_mb):
            raise AssertionError(f"{name}: row order differs at {point}")
        if got.shared != ref.shared:
            raise AssertionError(f"{name}: shared flag differs at {point}")
        if dataclasses.asdict(got.report) != dataclasses.asdict(ref.report):
            raise AssertionError(f"{name}: report differs from numpy at {point}")
    log(f"{name}: backend={backend} bit-identical to numpy on {len(rows)} "
        f"rows; setup_s={setup_s} warm_s={warm_s}")


def assert_grids_close(name: str, got, ref) -> None:
    """Every count and metric field within the DSE's 1e-9 rtol contract."""
    for part in ("counts", "metrics"):
        a, b = getattr(got, part), getattr(ref, part)
        for f in dataclasses.fields(a):
            np.testing.assert_allclose(
                np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name)),
                rtol=1e-9, atol=0, err_msg=f"{name}: {part}.{f.name}")


def dse_phase(name: str, evaluate) -> None:
    """``evaluate(workload, backend)`` over the CV+NLP zoo: jax vs numpy."""
    zoo = {**cv_model_zoo(), **nlp_model_zoo()}
    reference, ref_s = timed(lambda: {n: evaluate(wl, "numpy")
                                      for n, wl in zoo.items()})
    got, setup_s = timed(lambda: {n: evaluate(wl, "jax")
                                  for n, wl in zoo.items()})
    _, warm_s = timed(lambda: [evaluate(wl, "jax") for wl in zoo.values()])
    for n, grid in got.items():
        if grid.backend != "jax":
            raise AssertionError(f"{name}/{n}: ran on {grid.backend!r}")
        assert_grids_close(f"{name}/{n}", grid, reference[n])
    log(f"{name}: backend=jax within rtol 1e-9 of numpy on {len(zoo)} "
        f"workloads; setup_s={setup_s} warm_s={warm_s} "
        f"numpy_s={ref_s}")


def main() -> int:
    info = device_info()
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {info['platform']!r})",
              file=sys.stderr)
        return 1
    log(f"device: {info['kind']} x{info['count']}")

    backend = resolve_backend("auto")
    if backend != "jax":
        raise AssertionError(f"auto resolved to {backend!r} on a TPU, not 'jax'")
    reference, ref_s = timed(
        lambda: sweep_serving_grid(SERVING_GRID, backend="numpy"))
    events = sorted({r.report.sim.n_events for r in reference})
    log(f"serving grid: {len(SERVING_GRID.qps)} qps x "
        f"{len(SERVING_GRID.capacities_mb)} capacity x "
        f"{len(SERVING_GRID.technologies)} technologies; n_events per "
        f"technology row {events}; numpy reference wall_s={ref_s}")
    serving_phase("serving/auto", "auto", reference)
    serving_phase("serving/pallas", "pallas", reference)
    spec = GridSpec()  # what `explore --domain both --models all` sweeps
    dse_phase("dse/grid",
              lambda wl, b: evaluate_workload_grid(wl, spec, backend=b))
    dse_phase("dse/geometry", lambda wl, b: evaluate_geometry_grid(
        wl, spec, axes=GeomAxes(), backend=b))
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
