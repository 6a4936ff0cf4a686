"""repro.obs: disabled-path no-op guarantees, manifest stability, Chrome-trace
schema validation, recorder-on/off bit-identity, and the console contract."""

import dataclasses
import io
import json
import math
import time

import numpy as np
import pytest

from repro import obs
from repro.core.memory_system import HybridMemorySystem, glb_array
from repro.core.workload import NLP_TABLE_V
from repro.obs import core as obs_core
from repro.obs.manifest import (
    COMPARABLE_KEYS,
    config_hash,
    manifest_diff,
    run_manifest,
    stamp,
)
from repro.obs.timeline import (
    PID_COUNTERS,
    PID_MEMORY,
    PID_REQUESTS,
    TimelineRecorder,
    validate_chrome_trace,
)
from repro.serve import ServeEngineConfig, closed_loop_serving
from repro.sim import ServingConfig, serving_trace, simulate_trace


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts and ends with observability disabled (the library
    default); tests that want it on call ``obs.enable()`` themselves."""
    obs.disable()
    yield
    obs.disable()


def _system(tech="sot_opt", cap_mb=32.0):
    return HybridMemorySystem(glb=glb_array(tech, cap_mb))


def _gpt2():
    return next(s for s in NLP_TABLE_V if s.name == "gpt2")


_SERVE_CFG = ServingConfig(n_requests=16, prompt_len=64, decode_len=8,
                           arrival_rate_rps=400.0, seed=3)
_ENGINE_CFG = ServeEngineConfig(max_batch=4)


# ---------------------------------------------------------------------------
# core: spans and counters
# ---------------------------------------------------------------------------


def test_disabled_span_is_the_shared_noop_singleton():
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b")
    assert s1 is s2 is obs_core._NOOP  # no per-call allocation
    with s1:
        pass
    assert obs.phase_times() == {}
    assert obs.snapshot() == {"enabled": False, "spans": {}, "counters": {}}


def test_disabled_count_is_a_noop():
    obs.count("events", 41)
    obs.count("events")
    assert obs.counters() == {}


def test_disabled_span_overhead_bound():
    """The disabled path must stay cheap enough to leave in hot loops.

    A generous absolute bound (5 us/call amortized over 100k calls, best of
    three) — orders of magnitude above the real cost of returning a module
    singleton, but low enough to catch the path regressing to allocation +
    clock reads per call."""
    n = 100_000

    def once():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("hot"):
                pass
            obs.count("hot")
        return time.perf_counter() - t0

    best = min(once() for _ in range(3))
    assert best / n < 5e-6, f"disabled span+count cost {best / n * 1e9:.0f}ns/call"


def test_enabled_spans_nest_into_slash_paths():
    obs.enable()
    with obs.span("sweep"):
        with obs.span("price"):
            pass
        with obs.span("price"):
            pass
    times = obs.phase_times()
    assert set(times) == {"sweep", "sweep/price"}
    assert all(t >= 0 for t in times.values())
    snap = obs.snapshot()
    assert snap["enabled"] is True
    assert snap["spans"]["sweep/price"]["calls"] == 2
    assert snap["spans"]["sweep"]["calls"] == 1


def test_enabled_counters_accumulate():
    obs.enable()
    obs.count("events", 3)
    obs.count("events", 2.5)
    obs.count("spills")
    assert obs.counters() == {"events": 5.5, "spills": 1}


def test_enable_reset_disable_lifecycle():
    obs.enable()
    obs.enable()  # idempotent
    obs.count("x")
    obs.reset()
    assert obs.enabled() and obs.counters() == {}
    obs.disable()
    obs.reset()  # reset while disabled stays disabled
    assert not obs.enabled()


class _Marks:
    """An ``annotate`` factory that logs each path it is entered with."""

    def __init__(self):
        self.entered, self.exited = [], 0

    def __call__(self, path):
        marks = self

        class _Mark:
            def __enter__(self):
                marks.entered.append(path)
                return self

            def __exit__(self, *exc):
                marks.exited += 1
                return False

        return _Mark()


def test_annotate_is_entered_with_the_full_path_only_when_enabled():
    marks = _Marks()
    with obs.span("sweep"):  # disabled: the no-op, annotate never called
        pass
    obs.enable(annotate=marks)
    with obs.span("sweep"):
        with obs.span("score"):
            with obs.span("replay"):
                pass
    assert marks.entered == ["sweep", "sweep/score", "sweep/score/replay"]
    assert marks.exited == 3
    assert set(obs.phase_times()) == set(marks.entered)
    obs.reset()  # keeps annotating
    with obs.span("a"):
        pass
    obs.enable()  # without annotate: stops annotating
    with obs.span("b"):
        pass
    obs.disable()
    with obs.span("c"):
        pass
    assert marks.entered[3:] == ["a"] and marks.exited == 4


_SWEEP_SPANS = {
    "sweep", "sweep/loop", "sweep/loop/schedule", "sweep/loop/price",
    "sweep/score", "sweep/score/trace", "sweep/score/coalesce",
    "sweep/score/replay", "sweep/score/replay/sort", "sweep/score/distill",
}
_DEVICE_SPANS = {
    "sweep/score/replay/pad", "sweep/score/replay/encode",
    "sweep/score/replay/device/cummax", "sweep/score/replay/decode",
    "sweep/score/replay/device/search",
}


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_sweep_spans_and_counters_leave_rows_bit_identical(backend,
                                                          monkeypatch):
    """The sweep's span tree and lane counters, recorded with the profiler's
    annotation on; rows are bitwise those of a run with obs off."""
    jax = pytest.importorskip("jax")
    from repro.serve import ServingGridSpec, sweep_serving_grid

    grid = ServingGridSpec(qps=(200.0, 400.0), capacities_mb=(32.0,),
                           technologies=("sot_opt", "sram"), model="gpt2",
                           serving=_SERVE_CFG, engine=_ENGINE_CFG)
    rows_off = sweep_serving_grid(grid, backend=backend)
    obs.enable()
    rows_on = sweep_serving_grid(grid, backend=backend)
    obs.disable()

    counted = []
    real_count = obs_core.count

    def count(name, n=1):
        counted.append((name, n))
        real_count(name, n)

    monkeypatch.setattr(obs_core, "count", count)
    obs.enable(annotate=jax.profiler.TraceAnnotation)
    rows_ann = sweep_serving_grid(grid, backend=backend)
    snap = obs.snapshot()

    for rows in (rows_on, rows_ann):
        assert len(rows) == len(rows_off)
        for a, b in zip(rows_off, rows):
            assert (a.technology, a.qps, a.shared) == (
                b.technology, b.qps, b.shared)
            assert _deep_equal(dataclasses.asdict(a.report),
                               dataclasses.asdict(b.report))
    assert all(r.shared for r in rows_off)

    spans = snap["spans"]
    want = _SWEEP_SPANS | (_DEVICE_SPANS if backend == "jax" else set())
    assert set(spans) == want
    assert spans["sweep"]["calls"] == 1
    assert spans["sweep/score/replay"]["calls"] == len(grid.qps)
    if backend == "numpy":
        assert snap["counters"] == {}
        return
    from repro.kernels.segmented_replay.ops import _next_pow2

    assert spans["sweep/score/replay/device/search"]["calls"] == len(grid.qps)
    lanes = [n for name, n in counted if name == "replay/lanes"]
    padded = [n for name, n in counted if name == "replay/lanes_padded"]
    assert len(lanes) == len(padded) == len(grid.qps)
    assert spans["sweep/score/replay/pad"]["calls"] == sum(
        p != r for r, p in zip(lanes, padded))
    R = len(grid.technologies)
    for real, pad in zip(lanes, padded):
        assert pad >= real > 0 and pad % R == 0 and real % R == 0
        width = pad // R
        assert width >= 4096 and width & (width - 1) == 0
        assert width == _next_pow2(real // R)
    assert snap["counters"] == {
        "replay/lanes": sum(lanes), "replay/lanes_padded": sum(padded),
        "replay/bytes_to_device": _BYTES_TO_DEVICE * sum(padded),
        "replay/bytes_from_device": _BYTES_FROM_DEVICE * sum(padded)}


# Per padded lane of the batched device replay: the running max ships one
# key's float64 bits in and out; the depth search ships two keys in and
# returns one int32 insertion point.
_BYTES_TO_DEVICE = 8 + 2 * 8
_BYTES_FROM_DEVICE = 8 + 4


def test_replay_byte_counters_follow_padded_lanes():
    """``replay/bytes_*`` are the per-lane bytes times ``replay/lanes_padded``
    for one sweep, and are absent while ``repro.obs`` is off."""
    pytest.importorskip("jax")
    from repro.serve import ServingGridSpec, sweep_serving_grid

    grid = ServingGridSpec(qps=(400.0,), capacities_mb=(32.0,),
                           technologies=("sot_opt", "sram"), model="gpt2",
                           serving=_SERVE_CFG, engine=_ENGINE_CFG)
    sweep_serving_grid(grid, backend="jax")
    obs.enable()
    assert obs.counters() == {}
    sweep_serving_grid(grid, backend="jax")
    counters = obs.counters()
    padded = counters["replay/lanes_padded"]
    assert padded > 0
    assert counters["replay/bytes_to_device"] == _BYTES_TO_DEVICE * padded
    assert counters["replay/bytes_from_device"] == _BYTES_FROM_DEVICE * padded


def test_replay_pad_span_opens_once_per_call_that_pads():
    """``pad`` opens once per ``replay_scan`` call whose rows are padded to a
    power of two, and not at all where they already are one."""
    pytest.importorskip("jax")
    from repro.kernels.segmented_replay.ops import replay_scan

    rng = np.random.default_rng(5)
    obs.enable()
    for n in (300, 4096, 5000):  # pads to 4096, none, pads to 8192
        t = np.sort(rng.uniform(0, 1e4, (2, n)), axis=1)
        seg = np.zeros((2, n))
        svc = np.ones((2, n))
        replay_scan(t, seg, svc, svc, t, np.ones(2))
    spans = obs.snapshot()["spans"]
    assert spans["pad"]["calls"] == 2
    assert spans["encode"]["calls"] == spans["decode"]["calls"] == 6
    assert spans["device/cummax"]["calls"] == 3


def test_sweep_spans_land_on_the_profilers_host_line(tmp_path):
    """Under a running profiler trace, every span the sweep records is an
    event of the trace's host plane, named by its full path."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    from repro.serve import ServingGridSpec, sweep_serving_grid

    grid = ServingGridSpec(qps=(400.0,), capacities_mb=(32.0,),
                           technologies=("sot_opt", "sram"), model="gpt2",
                           serving=_SERVE_CFG, engine=_ENGINE_CFG)
    with jax.profiler.trace(str(tmp_path)):
        obs.enable(annotate=jax.profiler.TraceAnnotation)
        sweep_serving_grid(grid, backend="jax")
        spans = set(obs.snapshot()["spans"])
        obs.disable()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    host = {e.name for plane in ProfileData.from_file(str(pb)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert spans == _SWEEP_SPANS | _DEVICE_SPANS
    assert spans <= host


# ---------------------------------------------------------------------------
# manifest: provenance stamping
# ---------------------------------------------------------------------------


def test_config_hash_is_order_insensitive_and_stable():
    h1 = config_hash({"b": 2, "a": [1, 2], "c": {"y": 1.0, "x": "s"}})
    h2 = config_hash({"c": {"x": "s", "y": 1.0}, "a": [1, 2], "b": 2})
    assert h1 == h2
    assert len(h1) == 16 and int(h1, 16) >= 0  # 16 hex digits
    assert config_hash({"a": [1, 2]}) != config_hash({"a": [2, 1]})


def test_config_hash_handles_dataclasses_tuples_numpy():
    @dataclasses.dataclass
    class Cfg:
        seed: int
        qps: tuple
        cap: float

    as_dc = config_hash(Cfg(seed=3, qps=(100.0, 200.0), cap=32.0))
    as_dict = config_hash({"seed": 3, "qps": [100.0, 200.0], "cap": 32.0})
    assert as_dc == as_dict  # dataclass canonicalizes to its field dict
    assert config_hash({"n": np.int64(7)}) == config_hash({"n": 7})
    assert config_hash(_SERVE_CFG) == config_hash(_SERVE_CFG)


def test_run_manifest_schema_and_stamp_round_trip():
    obs.enable()
    with obs.span("phase_a"):
        pass
    m = run_manifest(seed=3, config={"cap": 32.0})
    for key in COMPARABLE_KEYS:
        assert key in m
    assert m["seed"] == 3 and m["schema"] == 1
    assert "phase_a" in m["phases_s"]
    # JSON round-trip preserves every field bit-for-bit.
    assert json.loads(json.dumps(m)) == m

    payload = stamp({"metric": 1.0}, seed=3, config={"cap": 32.0})
    assert payload["manifest"]["config_hash"] == m["config_hash"]


def test_manifest_diff_comparable_keys_only():
    a = run_manifest(seed=3, config={"cap": 32.0})
    b = dict(a, created_unix=a["created_unix"] + 100,
             phases_s={"other": 1.0})
    assert manifest_diff(a, b) == {}  # timestamps/phases are not comparable
    b["seed"], b["numpy"] = 4, "9.9.9"
    diff = manifest_diff(a, b)
    assert diff["seed"] == (3, 4) and diff["numpy"][1] == "9.9.9"
    # Either side may predate manifests entirely.
    assert manifest_diff(None, None) == {}
    assert manifest_diff(a, None)["seed"] == (3, None)


def test_check_bench_manifest_warnings():
    check_bench = pytest.importorskip("benchmarks.check_bench")
    m = run_manifest(seed=3, config={"smoke": True})
    assert check_bench.manifest_warnings({"manifest": m}, {"manifest": dict(m)}) == []
    drifted = dict(m, seed=4, git_sha="feedface")  # git_sha drift is expected
    warns = check_bench.manifest_warnings({"manifest": m}, {"manifest": drifted})
    assert len(warns) == 1 and "seed" in warns[0]


# ---------------------------------------------------------------------------
# timeline: Chrome-trace schema
# ---------------------------------------------------------------------------


def test_validator_accepts_minimal_document():
    doc = {"traceEvents": [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "m"}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "read", "ts": 0.0, "dur": 1.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "read", "ts": 2.0, "dur": 0.0},
        {"ph": "C", "pid": 3, "name": "depth", "ts": 0.0, "args": {"v": 1}},
        {"ph": "i", "pid": 2, "tid": 4, "name": "first_token", "ts": 5.0},
    ]}
    assert validate_chrome_trace(doc) == []


@pytest.mark.parametrize("bad,needle", [
    ({"traceEvents": None}, "not a list"),
    ({"traceEvents": [{"pid": 1}]}, "missing ph/pid"),
    ({"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "name": "r"}]},
     "missing/non-finite ts"),
    ({"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "name": "r",
                       "ts": 0.0, "dur": -1.0}]}, "negative dur"),
    ({"traceEvents": [{"ph": "X", "pid": 1, "tid": 0, "name": "r",
                       "ts": math.inf, "dur": 1.0}]}, "non-finite ts"),
    ({"traceEvents": [{"ph": "C", "pid": 3, "name": "d", "ts": 0.0,
                       "args": {"v": "high"}}]}, "non-numeric args"),
    ({"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "r", "ts": 5.0, "dur": 1.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "r", "ts": 4.0, "dur": 1.0},
    ]}, "non-monotone"),
])
def test_validator_rejects_malformed_events(bad, needle):
    problems = validate_chrome_trace(bad)
    assert problems and any(needle in p for p in problems)


def test_validator_monotonicity_is_per_track():
    # Interleaved tracks may go backwards relative to each other; only
    # within one (pid, tid) X-track must ts be non-decreasing.
    doc = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 0, "name": "r", "ts": 10.0, "dur": 1.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "r", "ts": 0.0, "dur": 1.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "r", "ts": 11.0, "dur": 1.0},
    ]}
    assert validate_chrome_trace(doc) == []


def test_recorder_export_from_replay_passes_validation():
    system = _system()
    trace = serving_trace(system, _gpt2(), _SERVE_CFG)
    rec = TimelineRecorder()
    simulate_trace(trace, recorder=rec)
    doc = rec.export(manifest=run_manifest(seed=3, config=_SERVE_CFG))
    assert validate_chrome_trace(doc) == []
    assert doc["otherData"]["n_replays"] == 1
    assert doc["otherData"]["dropped_events"] == 0
    assert doc["otherData"]["manifest"]["seed"] == 3
    assert rec.n_events > 0
    pids = {ev["pid"] for ev in doc["traceEvents"]}
    assert PID_MEMORY in pids
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert any(n.startswith("glb_bank_") for n in names)


def test_recorder_export_from_serving_loop_has_all_tracks():
    rec = TimelineRecorder()
    closed_loop_serving(_system(), _gpt2(), _SERVE_CFG, _ENGINE_CFG,
                        recorder=rec)
    doc = rec.export()
    assert validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    pids = {ev["pid"] for ev in events}
    assert {PID_MEMORY, PID_REQUESTS, PID_COUNTERS} <= pids
    req_spans = {ev["name"] for ev in events
                 if ev["pid"] == PID_REQUESTS and ev["ph"] == "X"}
    assert {"queued", "decode"} <= req_spans
    counter_names = {ev["name"] for ev in events
                     if ev["pid"] == PID_COUNTERS and ev["ph"] == "C"}
    assert {"glb_residency_pct", "kv_pages_spilled", "kv_dram_read_bytes",
            "active_requests"} <= counter_names
    assert doc["otherData"]["n_requests"] == _SERVE_CFG.n_requests


def test_recorder_event_cap_reports_dropped_events():
    system = _system()
    trace = serving_trace(system, _gpt2(), _SERVE_CFG)
    rec = TimelineRecorder(max_events=10)
    simulate_trace(trace, recorder=rec)
    assert rec.n_events == 2 * 10  # X + C event per kept schedule row
    assert rec.dropped_events > 0
    doc = rec.export()
    assert doc["otherData"]["dropped_events"] == rec.dropped_events
    assert validate_chrome_trace(doc) == []


def test_recorder_save_and_cli_validate(tmp_path):
    from repro.obs.timeline import main as validate_main

    rec = TimelineRecorder()
    closed_loop_serving(_system(), _gpt2(), _SERVE_CFG, _ENGINE_CFG,
                        recorder=rec)
    path = tmp_path / "trace.json"
    rec.save(str(path), manifest=run_manifest(seed=3))
    assert validate_main([str(path)]) == 0
    # A corrupted file must fail the CLI gate.
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"pid": 1}]}))
    assert validate_main([str(bad)]) == 1


# ---------------------------------------------------------------------------
# bit-identity: recorder on vs off
# ---------------------------------------------------------------------------


def _deep_equal(a, b) -> bool:
    if isinstance(a, dict) and isinstance(b, dict):
        return a.keys() == b.keys() and all(
            _deep_equal(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)) and isinstance(b, (list, tuple)):
        return len(a) == len(b) and all(
            _deep_equal(x, y) for x, y in zip(a, b))
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        return np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)
    if isinstance(a, float) and isinstance(b, float):
        return a == b or (math.isnan(a) and math.isnan(b))
    return a == b


def test_recorder_leaves_serving_metrics_bit_identical():
    """The golden guarantee: attaching a TimelineRecorder must not perturb a
    single bit of any metric (no RNG draws, no mutation, no reordering)."""
    trace_off, rep_off = closed_loop_serving(
        _system(), _gpt2(), _SERVE_CFG, _ENGINE_CFG)
    rec = TimelineRecorder()
    trace_on, rep_on = closed_loop_serving(
        _system(), _gpt2(), _SERVE_CFG, _ENGINE_CFG, recorder=rec)
    assert rec.n_events > 0  # the recorder really was in the loop
    assert _deep_equal(dataclasses.asdict(rep_off), dataclasses.asdict(rep_on))
    for field in ("t_issue_ns", "resource", "service_ns", "energy_pj",
                  "kind", "line"):
        assert np.array_equal(getattr(trace_off, field),
                              getattr(trace_on, field))


def test_recorder_leaves_replay_metrics_bit_identical():
    system = _system()
    trace = serving_trace(system, _gpt2(), _SERVE_CFG)
    res_off = simulate_trace(trace)
    res_on = simulate_trace(trace, recorder=TimelineRecorder())
    assert _deep_equal(dataclasses.asdict(res_off), dataclasses.asdict(res_on))


def test_recorder_leaves_sweep_metrics_bit_identical():
    from repro.serve import ServingGridSpec, sweep_serving_grid

    grid = ServingGridSpec(qps=(200.0, 400.0), capacities_mb=(32.0,),
                           technologies=("sot_opt", "sram"), model="gpt2",
                           serving=_SERVE_CFG, engine=_ENGINE_CFG)
    rows_off = sweep_serving_grid(grid)
    rec = TimelineRecorder()
    rows_on = sweep_serving_grid(grid, recorder=rec)
    assert rec.n_events > 0
    assert len(rows_off) == len(rows_on)
    for a, b in zip(rows_off, rows_on):
        assert (a.technology, a.capacity_mb, a.qps) == (
            b.technology, b.capacity_mb, b.qps)
        assert _deep_equal(dataclasses.asdict(a.report),
                           dataclasses.asdict(b.report))


def test_sweep_reports_bit_identical_across_backends():
    """Golden backend trio: the whole sweep report — every float in every
    row — must be bitwise identical for numpy, jax, and pallas replays,
    with and without a recorder attached."""
    import pytest

    pytest.importorskip("jax", reason="backend trio needs jax")
    from repro.serve import ServingGridSpec, sweep_serving_grid

    grid = ServingGridSpec(qps=(200.0, 400.0), capacities_mb=(32.0,),
                           technologies=("sot_opt", "sram"), model="gpt2",
                           serving=_SERVE_CFG, engine=_ENGINE_CFG)
    ref = sweep_serving_grid(grid, backend="numpy")
    for backend in ("jax", "pallas"):
        rec = TimelineRecorder()
        rows = sweep_serving_grid(grid, backend=backend, recorder=rec)
        assert rec.n_events > 0
        assert len(rows) == len(ref)
        for a, b in zip(ref, rows):
            assert (a.technology, a.capacity_mb, a.qps, a.shared) == (
                b.technology, b.capacity_mb, b.qps, b.shared), backend
            assert _deep_equal(dataclasses.asdict(a.report),
                               dataclasses.asdict(b.report)), (
                backend, a.technology, a.qps)


# ---------------------------------------------------------------------------
# console: output-mode contract
# ---------------------------------------------------------------------------


def _console(**kw):
    out, err = io.StringIO(), io.StringIO()
    return obs.Console(stream=out, err=err, **kw), out, err


def test_console_text_mode():
    con, out, err = _console()
    con.info("hello")
    con.warn("drift")
    con.result({"x": 1})  # text mode: result is silent (info already printed)
    assert out.getvalue() == "hello\n"
    assert err.getvalue() == "warning: drift\n"


def test_console_json_mode_stdout_is_machine_only():
    con, out, err = _console(json_mode=True)
    con.info("prose goes to stderr")
    con.result({"x": 1, "arr": np.array([1, 2]), "f": np.float64(0.5)})
    doc = json.loads(out.getvalue())  # stdout parses as exactly one document
    assert doc == {"x": 1, "arr": [1, 2], "f": 0.5}
    assert "prose" in err.getvalue()


def test_console_quiet_mode_drops_prose_keeps_errors():
    con, out, err = _console(quiet=True)
    con.info("dropped")
    con.error("kept")
    assert out.getvalue() == ""
    assert err.getvalue() == "kept\n"


# ---------------------------------------------------------------------------
# report CLI: markdown rendering
# ---------------------------------------------------------------------------


def test_report_renders_stamped_record(tmp_path):
    from repro.launch import report

    doc = stamp({"cli": "serve_sim", "wall_s": 0.5,
                 "rows": [{"qps": 100.0, "p99": 1.5}, {"qps": 200.0, "p99": 3.0}]},
                seed=3, config={"cap": 32.0})
    lines = report.render(json.loads(json.dumps(doc)), "metrics.json")
    text = "\n".join(lines)
    assert "| key | value |" in text and "serve_sim" in text
    assert "## rows (2 rows)" in text and "| qps | p99 |" in text
    assert "## manifest" in text


def test_report_diff_flags_manifest_disagreement():
    from repro.launch import report

    a = stamp({"m": 1.0}, seed=3)
    b = stamp({"m": 2.0}, seed=4)
    text = "\n".join(report.render_diff(a, b, "a.json", "b.json"))
    assert "Manifests disagree" in text and "seed" in text
    same = "\n".join(report.render_diff(a, json.loads(json.dumps(a)),
                                        "a.json", "a2.json"))
    assert "manifests agree" in same
