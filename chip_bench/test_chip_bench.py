"""CPU tests of the chip benchmark: discovery, yardsticks, shim, references.

They never need a chip: a run is driven with ``require_tpu=False`` on a tiny
cell written into a copy of the benchmark, with the numpy replay that
``backend="auto"`` picks on the CPU.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chip_bench import roofline, run  # noqa: E402
from chip_bench.reference import (  # noqa: E402
    control_replay,
    depth_bounds,
    fifo_replay,
    max_rel_gap,
)
from chip_bench.shim import ReplayShim  # noqa: E402
from chip_bench.trace_reduce import extract, reduce_file  # noqa: E402

TINY_CELLS = ("tiny.serve", "tiny.fleet")
# The study driver also takes a fleet: 4 replicas, colocated.
TINY_FLEET = {"n_replicas": 4, "router": "least_loaded",
              "disaggregation": False}
FIXTURE_TRACE = Path(__file__).parent / "fixtures" / "small.xplane.pb"
FIXTURE_TEXT = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 8500000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 19000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_replay" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7100000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "study" } }
  event_metadata { key: 2 value { id: 2 name: "replay" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_cummax_lax)" } }
}
"""


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    """A copy of the benchmark with two tiny cells added as files only."""
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "chip_bench", root / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    d = root / "chip_bench"
    for cell, fleet in zip(TINY_CELLS, (None, TINY_FLEET)):
        cfg = json.loads((d / "configs" / "gpt2-serve.json").read_text())
        cfg.update(prompt_len=32, decode_len=8, max_batch=4, fleet=fleet,
                   technologies=["sram", "sot_opt", "hybrid"])
        name = cell.replace(".", "-")
        (d / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"chip_bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
        shutil.copy(d / "limits" / "gpt2-serve.shared.json",
                    d / "limits" / f"{cell}.json")
        for m in bench["per_layer"] + bench["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    (d / "traffic" / "tiny.json").write_text(json.dumps(
        {"n_requests": 8, "qps": [400.0, 800.0], "capacities_mb": [64.0]}))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU is no benchmark device; give it a peak for the traced tests."""
    monkeypatch.setitem(roofline.PEAKS, "cpu", {"hbm_bytes_per_s": 100e9})


def _run(root, cell, **kw):
    result, _ = run.run_cell(cell, 2**31 + 9, 0.0, kw.pop("trace", False),
                             root=root, require_tpu=False, **kw)
    return result


# -- discovery -------------------------------------------------------------

def test_benchmark_cells_resolve_by_name():
    bench = run.Bench(ROOT)
    for w in bench.spec["workloads"]:
        config = bench.config(w["config"])
        study = bench.study(config["study"])
        spec = study.make_spec(config, bench.traffic(w["traffic"]), 1)
        assert spec.serving.seed == 1 and spec.technologies
        assert {"replay_finish_gap_ns", "report_gap_exact",
                "rows_differing_numpy"} <= set(bench.limits(w["name"]))
        assert set(bench.readers(w["name"])) == {
            "loop_ns_per_event", "score_ns_per_event",
            "replay_roofline", "device_idle_pct"}


def test_new_cell_and_metric_are_files_only(tiny_root, cpu_peaks):
    """A cell (configuration + traffic + limits) and a per-layer metric are
    added as new files plus entries in BENCHMARK.json, editing no file."""
    (tiny_root / "chip_bench" / "metrics" / "replay_calls_n.py").write_text(
        "def read(record):\n    return len(record['replay_calls'])\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "replay_calls_n", "unit": "calls", "better": "lower",
        "source": "host_clock", "layer": "replay kernels",
        "moves": "events_per_s", "workloads": ["tiny.serve"]})
    extra = tiny_root / "extra"
    shutil.copytree(tiny_root, extra, ignore=shutil.ignore_patterns("extra"))
    (extra / "BENCHMARK.json").write_text(json.dumps(bench))
    assert "replay_calls_n" in run.Bench(extra).readers("tiny.serve")
    assert "replay_calls_n" not in run.Bench(extra).readers("tiny.fleet")
    result = _run(extra, "tiny.serve", trace=True)
    assert result["metrics"]["replay_calls_n"]["value"] == 2


# -- yardsticks ------------------------------------------------------------

@pytest.mark.parametrize("rows,n,expected", [
    (1, 10, 10 * 8 + 10 * (4 + 8) + 10 * (3 * 8 + 8)),
    (5, 4, 4 * 8 + 5 * 4 * (4 + 8) + 5 * 4 * (3 * 8 + 8)),
    (3, 0, 0),
])
def test_replay_bytes_hand_count(rows, n, expected):
    assert roofline.replay_bytes(rows, n) == expected


def test_peaks_refuse_unknown_device_kind():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("TPU v99")


def test_trace_reduction_on_committed_trace():
    from jax.profiler import ProfileData

    # The committed file is FIXTURE_TEXT, serialized.
    assert extract(ProfileData.from_file(str(FIXTURE_TRACE))) == \
        extract(ProfileData.from_text_proto(FIXTURE_TEXT))
    got = reduce_file(FIXTURE_TRACE)
    # Study span 500..20500 ns; ops 1000-6000, 8000-9000, 8500-9500.
    assert got["window_s"] == pytest.approx(20_000e-9)
    assert got["busy_s"] == pytest.approx(6_500e-9)
    assert got["idle_pct"] == pytest.approx(67.5)
    assert [n for n, _ in got["device_ops"]] == ["fusion.1", "copy.2"]
    assert got["device_ops"][0][1] == pytest.approx(6_000e-9)
    assert [[label, pytest.approx(s)] for label, s in got["idle_gaps"]] == [
        ["sweep host work", 11_000e-9], ["replay", 2_000e-9],
        ["sweep host work", 500e-9]]


# -- shim and references ---------------------------------------------------

def test_shim_leaves_rows_bit_identical(tiny_root):
    bench = run.Bench(tiny_root)
    study = bench.study("serving_sweep")
    spec = study.make_spec(bench.config("tiny-serve"), bench.traffic("tiny"), 3)
    off = study.run(spec, {})
    with ReplayShim() as shim:
        on = study.run(spec, {})
    assert [c["rows"] for c in shim.calls] == [3, 3]
    assert [dataclasses.asdict(r) for r in on] == \
        [dataclasses.asdict(r) for r in off]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fifo_reference_agrees_with_program_replay(seed):
    from repro.sim.engine import replay_schedule

    rng = np.random.default_rng(seed)
    n = 2000
    t = np.sort(rng.uniform(0, 1e6, n)).round(-1)  # ties within resources
    res = rng.integers(0, 7, n).astype(np.int32)
    svc = rng.uniform(0, 300, n)
    got = replay_schedule(t, res, svc, np.zeros(n, np.int8))
    order, t_s, _, start, finish, wait, depth = fifo_replay(t, res, svc)
    assert np.array_equal(got.order, order)
    assert np.array_equal(got.queue_depth, depth)
    lo, hi = depth_bounds(res[order], t_s, finish)
    assert np.all((lo <= depth) & (depth <= hi))
    assert np.all(depth_bounds(res[order], t_s, finish, tie_ns=0.0)[0] == depth)
    np.testing.assert_allclose(got.finish_ns, finish, rtol=0, atol=1e-6)
    np.testing.assert_allclose(got.start_ns, start, rtol=0, atol=1e-6)
    np.testing.assert_allclose(got.wait_ns, wait, rtol=0, atol=1e-6)
    # float32 is far off at these magnitudes: what the control relies on.
    _, _, _, _, f32, _, _ = fifo_replay(t, res, svc, np.float32)
    assert np.max(np.abs(f32 - finish)) > 1e-3


def test_max_rel_gap_reads_every_leaf():
    @dataclasses.dataclass
    class R:
        a: float
        b: dict
        c: tuple

    x = R(1.0, {"k": 2.0, "s": "x"}, (1, 2))
    assert max_rel_gap(x, R(1.0, {"k": 2.0, "s": "x"}, (1, 2))) == (0.0, "")
    assert max_rel_gap(x, R(1.0, {"k": 2.5, "s": "x"}, (1, 2)))[1] == "b.k"
    assert max_rel_gap(x, R(1.0, {"k": 2.0, "s": "y"}, (1, 2)))[0] == np.inf
    assert max_rel_gap(x, R(1.0, {"k": 2.0, "s": "x"}, (1,)))[0] == np.inf
    assert max_rel_gap(R(float("nan"), {}, ()), R(float("nan"), {}, ()))[0] == 0


# -- a whole run, sound and broken -----------------------------------------

@pytest.mark.parametrize("cell", TINY_CELLS)
def test_run_is_correct_and_reports_end_to_end(tiny_root, cell):
    result = _run(tiny_root, cell)
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"events_per_s", "host_peak_rss_mb",
                                      "setup_s"}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_traced_run_reports_per_layer(tiny_root, cell, cpu_peaks):
    result = _run(tiny_root, cell, trace=True)
    assert result["correct"] is True, result["checks"]
    m = result["metrics"]
    assert {"loop_ns_per_event", "score_ns_per_event",
            "replay_roofline"} <= set(m)
    assert 0 < m["replay_roofline"]["value"] < 100
    assert "device_idle_pct" not in m  # no device plane in a CPU trace
    assert {"device_ops", "idle_gaps"} <= set(result["breakdown"])


def _rows_of(out):
    """Callable over the per-row arrays of a (batched) replay schedule."""
    batched = out.finish_ns.ndim == 2

    def two(a):
        return np.array(a if batched else a[None], copy=True)

    return batched, {f.name: two(getattr(out, f.name))
                     for f in dataclasses.fields(out)}


def _rebuild(out, batched, cols):
    return type(out)(**{k: (v if batched else v[0]) for k, v in cols.items()})


def _unqueued(cols, rows, lanes=slice(None)):
    """Leave ``rows`` x ``lanes`` unreplayed: served at issue, no queue."""
    t, svc = cols["t_issue_ns"], cols["service_ns"]
    cols["start_ns"][rows, lanes] = t[rows, lanes]
    cols["finish_ns"][rows, lanes] = t[rows, lanes] + svc[rows, lanes]
    cols["wait_ns"][rows, lanes] = 0.0
    cols["queue_depth"][rows, lanes] = 0


def fault_altered(out):
    """One answer altered where it is produced: the last finish, +1 us."""
    batched, cols = _rows_of(out)
    i = int(np.argmax(cols["finish_ns"][0]))
    cols["finish_ns"][0, i] += 1000.0
    return _rebuild(out, batched, cols)


def fault_half_batch(out):
    """Half of the batch left out: the second half of the rows (of the
    events, in a one-row call) is never replayed."""
    batched, cols = _rows_of(out)
    R, n = cols["finish_ns"].shape
    if R > 1:
        _unqueued(cols, slice(R - R // 2, R))
    else:
        _unqueued(cols, slice(None), slice(n // 2, n))
    return _rebuild(out, batched, cols)


def fault_unchanged(out):
    """The replay returns its input unchanged: nothing is served."""
    batched, cols = _rows_of(out)
    cols["start_ns"][:] = cols["t_issue_ns"]
    cols["finish_ns"][:] = cols["t_issue_ns"]
    cols["wait_ns"][:] = 0.0
    cols["queue_depth"][:] = 0
    return _rebuild(out, batched, cols)


@pytest.mark.parametrize("cell", TINY_CELLS)
def test_control_comes_out_not_correct(tiny_root, cell):
    """The float32 reference in the replay's place must fail the check."""
    result = _run(tiny_root, cell, substitute=control_replay)
    assert result["correct"] is False
    assert result["failed"] > 0


@pytest.mark.parametrize("fault", [fault_altered, fault_half_batch,
                                   fault_unchanged])
@pytest.mark.parametrize("cell", TINY_CELLS)
def test_planted_fault_comes_out_not_correct(tiny_root, cell, fault):
    result = _run(tiny_root, cell, alter=fault)
    assert result["correct"] is False, result["checks"]


# -- the command itself ------------------------------------------------------

def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chip_bench/run.py", "--workload", "gpt2-serve.shared",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_tpu_exits_nonzero_and_prints_no_result():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "TPU" in proc.stderr


def test_command_in_bare_benchmark_dir_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chip_bench", tmp_path / "chip_bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
