"""CI gate for BENCH_serving.json / BENCH_replay.json: fail on drift.

    PYTHONPATH=src python -m benchmarks.check_bench BENCH_serving.json \
        benchmarks/BENCH_serving.baseline.json [--max-regression 2.0] \
        [--replay-current BENCH_replay.json \
         --replay-baseline benchmarks/BENCH_replay.baseline.json]

Compares a fresh benchmark record against the committed baseline:

* **wall-clock**: each benchmark present in both files must not be slower
  than ``max_regression`` x its baseline ``us_per_call`` (default 2x — wide
  enough for runner-to-runner variance, tight enough to catch the serving
  loop quietly falling back to scalar-era behaviour);
* **correctness invariants** on the serving sweep: the scalar and
  vectorized paths must still produce identical metrics
  (``all_scalar_identical``), and the vectorized path must remain faster
  than the scalar reference (``grid_speedup_x > 1``);
* **replay gate** (``--replay-current``/``--replay-baseline``): the replay
  benchmark's backends must still be bit-identical (``numpy``/``jax``/
  ``pallas`` sweep reports, and shared-vs-exact row agreement), its batched
  events/sec must stay above half the baseline's, and its end-to-end
  speedup over the scalar serving baseline must not fall below the floor
  recorded in the baseline (``speedup_floor_x``);
* **fleet gate** (``--fleet-current``/``--fleet-baseline``): the 1-replica
  fleet must stay bit-identical to the single-accelerator closed loop
  (``fleet_identity``), every technology in the baseline's fleet grid must
  still be covered with a positive ``cost_per_token``, all requests must
  complete, and the fleet wall must stay within ``max_regression``;
* **geometry gate** (``--geom-current``/``--geom-baseline``): the
  geometry-derived ``MemTechSpec`` coefficients must keep matching the
  pinned anchors within the documented calibration tolerance
  (``calibration_max_rel_err``), pinned no-geometry designs must stay
  bit-identical to the fixed grid, numpy/jax geometry grids must agree to
  1e-9 rtol, and the sweep wall must stay within ``max_regression``;

* **technology coverage**: every technology registered in ``repro.spec``
  must appear in the baseline's ``tech_coverage`` block — either in
  ``covered`` (part of the benchmark grid) or in ``notes`` (with a reason
  it is excluded).  Registering a new technology without deciding its
  serving-benchmark status fails CI until the baseline is updated.

Additionally the two files' ``manifest`` blocks (``repro.obs``) are
compared on versions/seed/config-hash: disagreement **warns** (it means a
wall-clock delta is not necessarily a code regression — different numpy,
different request population) but does not fail, since the whole point of
the gate is to keep working across environment upgrades.

Exit status 0 on pass, 1 on any violation (each violation is printed).
"""

from __future__ import annotations

import argparse
import json
import sys

# Manifest keys whose drift makes baseline-vs-current wall clocks and
# metrics incomparable.  git_sha/platform/jax_backend/device_kind are
# intentionally absent: the baseline is by definition from an older commit
# and another runner.
MANIFEST_WARN_KEYS = ("schema", "seed", "config_hash", "python", "numpy",
                      "jax")


def check(current: dict, baseline: dict, max_regression: float) -> list[str]:
    problems = []
    cur_b = current.get("benchmarks", {})
    base_b = baseline.get("benchmarks", {})
    for name, base in base_b.items():
        cur = cur_b.get(name)
        if cur is None:
            problems.append(f"{name}: missing from current record")
            continue
        b_us, c_us = base.get("us_per_call"), cur.get("us_per_call")
        if b_us and c_us and c_us > max_regression * b_us:
            problems.append(
                f"{name}: wall-clock {c_us / 1e6:.2f}s vs baseline "
                f"{b_us / 1e6:.2f}s (> {max_regression:.1f}x regression)"
            )
    serving = cur_b.get("serving_qps")
    if serving is not None:
        if not serving.get("all_scalar_identical", False):
            problems.append(
                "serving_qps: vectorized and scalar paths no longer produce "
                "identical metrics"
            )
        speedup = serving.get("grid_speedup_x") or 0.0
        if speedup <= 1.0:
            problems.append(
                f"serving_qps: vectorized grid no faster than the scalar "
                f"path (grid_speedup_x={speedup})"
            )
    problems.extend(check_tech_coverage(baseline))
    return problems


def check_replay(current: dict, baseline: dict,
                 max_regression: float) -> list[str]:
    """Gate BENCH_replay.json against its committed baseline."""
    problems = []
    cur = current.get("benchmarks", {}).get("replay")
    base = baseline.get("benchmarks", {}).get("replay")
    if cur is None:
        return ["replay: missing from current record"]
    if base is None:
        return ["replay: missing from baseline record"]
    b_us, c_us = base.get("us_per_call"), cur.get("us_per_call")
    if b_us and c_us and c_us > max_regression * b_us:
        problems.append(
            f"replay: wall-clock {c_us / 1e6:.2f}s vs baseline "
            f"{b_us / 1e6:.2f}s (> {max_regression:.1f}x regression)"
        )
    if not cur.get("bit_identical_backends", False):
        problems.append(
            "replay: numpy/jax/pallas sweep reports are no longer "
            "bit-identical"
        )
    if not cur.get("per_point_identical", False):
        problems.append(
            "replay: batched shared sweep diverged from the per-point "
            "closed-loop reference on the pinned metrics"
        )
    eps_base = base.get("events_per_sec") or 0.0
    eps_cur = cur.get("events_per_sec") or 0.0
    if eps_base and eps_cur < eps_base / 2:
        problems.append(
            f"replay: batched replay throughput {eps_cur / 1e6:.2f}M "
            f"events/s fell below half the baseline "
            f"({eps_base / 1e6:.2f}M events/s)"
        )
    floor = baseline.get("speedup_floor_x")
    speedup = cur.get("end_to_end_speedup_x") or 0.0
    if floor and speedup < floor:
        problems.append(
            f"replay: end-to-end speedup over the scalar serving baseline "
            f"is {speedup}x, below the recorded floor ({floor}x)"
        )
    return problems


def check_fleet(current: dict, baseline: dict,
                max_regression: float) -> list[str]:
    """Gate BENCH_fleet.json against its committed baseline."""
    problems = []
    cur = current.get("benchmarks", {}).get("fleet")
    base = baseline.get("benchmarks", {}).get("fleet")
    if cur is None:
        return ["fleet: missing from current record"]
    if base is None:
        return ["fleet: missing from baseline record"]
    b_us, c_us = base.get("us_per_call"), cur.get("us_per_call")
    if b_us and c_us and c_us > max_regression * b_us:
        problems.append(
            f"fleet: wall-clock {c_us / 1e6:.2f}s vs baseline "
            f"{b_us / 1e6:.2f}s (> {max_regression:.1f}x regression)"
        )
    if not cur.get("fleet_identity", False):
        problems.append(
            "fleet: 1-replica fleet is no longer bit-identical to the "
            "single-accelerator closed loop"
        )
    if not cur.get("all_completed", False):
        problems.append(
            "fleet: a disaggregated fleet run left requests uncompleted"
        )
    missing = set(base.get("techs", ())) - set(cur.get("techs", ()))
    if missing:
        problems.append(
            f"fleet: technologies {sorted(missing)} covered by the baseline "
            "are missing from the current record"
        )
    for tech, cost in (cur.get("cost_per_token") or {}).items():
        if not cost or cost <= 0:
            problems.append(
                f"fleet: cost_per_token for {tech!r} is {cost!r} "
                "(expected a positive index)"
            )
    return problems


def check_geom(current: dict, baseline: dict,
               max_regression: float) -> list[str]:
    """Gate BENCH_geom.json against its committed baseline."""
    problems = []
    cur = current.get("benchmarks", {}).get("geom_sweep")
    base = baseline.get("benchmarks", {}).get("geom_sweep")
    if cur is None:
        return ["geom_sweep: missing from current record"]
    if base is None:
        return ["geom_sweep: missing from baseline record"]
    b_us, c_us = base.get("us_per_call"), cur.get("us_per_call")
    if b_us and c_us and c_us > max_regression * b_us:
        problems.append(
            f"geom_sweep: wall-clock {c_us / 1e6:.2f}s vs baseline "
            f"{b_us / 1e6:.2f}s (> {max_regression:.1f}x regression)"
        )
    err, tol = cur.get("calibration_max_rel_err"), cur.get("calibration_tol")
    if err is None or tol is None or err > tol:
        problems.append(
            f"geom_sweep: geometry-derived coefficients drifted from the "
            f"pinned anchors (max rel err {err!r} > tol {tol!r})"
        )
    if not cur.get("pinned_identical", False):
        problems.append(
            "geom_sweep: a pinned (no-geometry) design is no longer "
            "bit-identical to the fixed-coefficient grid"
        )
    if not cur.get("backends_equivalent", False):
        problems.append(
            "geom_sweep: numpy and jax geometry grids diverged beyond "
            "the 1e-9 rtol contract"
        )
    missing = set(base.get("techs", ())) - set(cur.get("techs", ()))
    if missing:
        problems.append(
            f"geom_sweep: technologies {sorted(missing)} covered by the "
            "baseline are missing from the current record"
        )
    return problems


def manifest_warnings(current: dict, baseline: dict) -> list[str]:
    """Human-readable warnings for manifest drift (never failures)."""
    try:
        from repro.obs import manifest_diff
    except ImportError:  # bare-JSON invocation without the package
        return []
    diff = manifest_diff(current.get("manifest"), baseline.get("manifest"),
                         keys=MANIFEST_WARN_KEYS)
    return [
        f"manifest: {key} differs (current {cur!r} vs baseline {base!r}) — "
        "wall-clock/metric deltas may not be code regressions"
        for key, (cur, base) in diff.items()
    ]


def check_tech_coverage(baseline: dict) -> list[str]:
    """Every registered technology must be accounted for in the baseline.

    Skips silently when ``repro.spec`` is not importable (the checker can
    also be run on bare JSON without the package on the path).
    """
    try:
        from repro.spec import list_techs
    except ImportError:
        return []
    cov = baseline.get("tech_coverage", {})
    accounted = set(cov.get("covered", ())) | set(cov.get("notes", {}))
    return [
        f"tech_coverage: registered technology {t!r} is neither in the "
        "baseline's covered list nor excused in its notes — add it to "
        "benchmarks/BENCH_serving.baseline.json tech_coverage"
        for t in list_techs()
        if t not in accounted
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("current", help="freshly produced BENCH_serving.json")
    ap.add_argument("baseline", help="committed baseline json")
    ap.add_argument("--max-regression", type=float, default=2.0)
    ap.add_argument("--replay-current", default=None,
                    help="freshly produced BENCH_replay.json")
    ap.add_argument("--replay-baseline", default=None,
                    help="committed replay baseline json")
    ap.add_argument("--fleet-current", default=None,
                    help="freshly produced BENCH_fleet.json")
    ap.add_argument("--fleet-baseline", default=None,
                    help="committed fleet baseline json")
    ap.add_argument("--geom-current", default=None,
                    help="freshly produced BENCH_geom.json")
    ap.add_argument("--geom-baseline", default=None,
                    help="committed geometry-sweep baseline json")
    args = ap.parse_args(argv)

    with open(args.current) as fh:
        current = json.load(fh)
    with open(args.baseline) as fh:
        baseline = json.load(fh)
    for w in manifest_warnings(current, baseline):
        print(f"BENCH WARNING: {w}", file=sys.stderr)
    problems = check(current, baseline, args.max_regression)
    if bool(args.replay_current) != bool(args.replay_baseline):
        problems.append(
            "replay: --replay-current and --replay-baseline must be "
            "passed together"
        )
    elif args.replay_current:
        with open(args.replay_current) as fh:
            replay_cur = json.load(fh)
        with open(args.replay_baseline) as fh:
            replay_base = json.load(fh)
        for w in manifest_warnings(replay_cur, replay_base):
            print(f"BENCH WARNING: {w}", file=sys.stderr)
        problems.extend(
            check_replay(replay_cur, replay_base, args.max_regression)
        )
    if bool(args.fleet_current) != bool(args.fleet_baseline):
        problems.append(
            "fleet: --fleet-current and --fleet-baseline must be "
            "passed together"
        )
    elif args.fleet_current:
        with open(args.fleet_current) as fh:
            fleet_cur = json.load(fh)
        with open(args.fleet_baseline) as fh:
            fleet_base = json.load(fh)
        for w in manifest_warnings(fleet_cur, fleet_base):
            print(f"BENCH WARNING: {w}", file=sys.stderr)
        problems.extend(
            check_fleet(fleet_cur, fleet_base, args.max_regression)
        )
    if bool(args.geom_current) != bool(args.geom_baseline):
        problems.append(
            "geom_sweep: --geom-current and --geom-baseline must be "
            "passed together"
        )
    elif args.geom_current:
        with open(args.geom_current) as fh:
            geom_cur = json.load(fh)
        with open(args.geom_baseline) as fh:
            geom_base = json.load(fh)
        for w in manifest_warnings(geom_cur, geom_base):
            print(f"BENCH WARNING: {w}", file=sys.stderr)
        problems.extend(
            check_geom(geom_cur, geom_base, args.max_regression)
        )
    for p in problems:
        print(f"BENCH REGRESSION: {p}", file=sys.stderr)
    if not problems:
        names = sorted(baseline.get("benchmarks", {}))
        print(f"bench check OK ({', '.join(names)})")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
