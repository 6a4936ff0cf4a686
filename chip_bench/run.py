"""Run one benchmark cell on the chip and print its result line.

Usage, from the root of a checkout::

    python chip_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run:

1. exits non-zero, printing no result, unless JAX sees a TPU with at least
   the chips the cell asks for;
2. finds the cell in ``BENCHMARK.json``, and by name its configuration,
   traffic, limits, study driver and per-layer metric readers;
3. set-up (``setup_s``, from process start): runs the cell's whole study
   once, which compiles every padded shape the study uses (JAX's persistent
   compile cache is ``<checkout>/.jax_cache``);
4. ``--trace 0``: runs whole studies back to back until the first study
   boundary at or after ``--seconds`` (at least one) and reports the
   end-to-end metrics; ``--trace 1``: traces one whole study with the
   profiler (no Python tracer) and reports the per-layer metrics;
5. holds the last study's rows to the references (after device memory has
   been read) and prints each compared number beside its limit;
6. prints one JSON line last: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` when traced), then ``checks``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

# libtpu logs to a fixed /tmp/tpu_logs unless told otherwise: keep its logs
# in this run's own temporary directory.
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

SETUP_METRIC = "setup_s"
RSS_METRIC = "host_peak_rss_mb"
SEED_MODULUS = 2**63  # np.random.default_rng takes non-negative seeds


def log(msg: str) -> None:
    print(f"[chip_bench] {msg}", flush=True)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Bench:
    """``BENCHMARK.json`` and the files it names, found by name under
    ``<root>/chip_bench``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / "chip_bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{[w['name'] for w in self.spec['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.dir / "limits" / f"{workload}.json").read_text())

    def study(self, kind: str):
        return _load_module(self.dir / "studies" / f"{kind}.py",
                            f"chip_bench_study_{kind}")

    def readers(self, workload: str) -> dict:
        """``{metric: read}`` of the per-layer metrics this cell reports,
        each from ``metrics/<metric>.py``."""
        return {
            m["name"]: _load_module(
                self.dir / "metrics" / f"{m['name']}.py",
                f"chip_bench_metric_{m['name'].replace('.', '_')}").read
            for m in self.spec["per_layer"]
            if workload in m.get("workloads", (workload,))
        }

    def units(self) -> dict:
        return {m["name"]: m["unit"]
                for m in self.spec["end_to_end"] + self.spec["per_layer"]}


def _device_or_exit(chips: int) -> dict:
    from repro.device import device_info

    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        print(f"chip_bench: needs {chips} TPU chip(s); JAX sees "
              f"{info['count']} {info['platform']!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return info


def _compile_cache(root: Path) -> None:
    """The persistent compile cache at a fixed path inside the checkout, and
    every program cached, so that only a checkout's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _CompileCounter:
    """Counts backend compilations while ``on``: none belong in the window."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and "backend_compile" in event:
            self.count += 1


def _device_peak_bytes() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             substitute=None, alter=None) -> tuple[dict, dict]:
    """One run of ``workload``; returns the result line as a dict, and every
    number the study's check read (those with a limit are in the line).

    ``require_tpu=False`` skips the look for a chip (the CPU tests and
    ``calibrate.py --cpu`` drive the rest of a run that way);
    ``substitute``/``alter`` reach the replay shim (see
    :mod:`chip_bench.shim`) for the control and the planted faults.
    """
    import numpy as np
    from jax.profiler import TraceAnnotation

    from chip_bench.shim import ReplayShim

    bench = Bench(root)
    cell = bench.workload(workload)
    info = _device_or_exit(int(cell["chips"])) if require_tpu else None
    if require_tpu:
        _compile_cache(bench.root)
    if info is None:
        from repro.device import device_info

        info = device_info()
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    study = bench.study(config["study"])
    seed = int(seed) % SEED_MODULUS
    spec = study.make_spec(config, traffic, seed)
    rng = np.random.default_rng([seed, 0x5EED])
    compiles = _CompileCounter()

    shim = ReplayShim(substitute=substitute, alter=alter)
    with shim:
        rows = study.run(spec, {})  # warm-up: compiles every shape
        shim.capture = study.pick_replay_rows(shim.calls, rng)
        setup_s = time.perf_counter() - T_START
        log(f"{workload}: seed={seed} setup_s={setup_s} "
            f"warm-up {json.dumps(study.describe(rows))}")
        compiles.on = True
        studies, record = [], {"device_kind": info["kind"]}
        if trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with tempfile.TemporaryDirectory() as tdir:
                jax.profiler.start_trace(tdir, profiler_options=opts)
                shim.reset()
                timing = {}
                t0 = time.perf_counter()
                with TraceAnnotation("study"):
                    rows = study.run(spec, timing)
                studies.append(time.perf_counter() - t0)
                jax.profiler.stop_trace()
                from chip_bench.trace_reduce import reduce_file

                pb = sorted(Path(tdir).rglob("*.xplane.pb"))
                record["trace"] = reduce_file(pb[0]) if pb else None
            log(f"{workload}: trace files={[str(f.name) for f in pb]} layout="
                f"{json.dumps((record['trace'] or {}).get('layout'))}")
            if require_tpu and not (record["trace"] or {}).get("busy_s"):
                raise RuntimeError("the profiler trace holds no device "
                                   "operation inside the study span")
            record.update(loop_s=timing["loop_s"], score_s=timing["score_s"],
                          work=study.work(rows), replay_calls=shim.calls)
            total_work = record["work"]
        else:
            total_work, t0 = 0, time.perf_counter()
            while True:
                shim.reset()
                t1 = time.perf_counter()
                with TraceAnnotation("study"):
                    rows = study.run(spec, {})
                studies.append(time.perf_counter() - t1)
                total_work += study.work(rows)
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        compiles.on = False
        captured = shim.captured
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"], "memory_peak_bytes": _device_peak_bytes()}
    log(f"{workload}: window studies={len(studies)} study_s={studies} "
        f"work={total_work} compiles_in_window={compiles.count} "
        f"replay_calls={[(c['rows'], c['n'], c['seconds']) for c in shim.calls]}")

    metrics, breakdown = {}, None
    units = bench.units()
    if trace:
        for name, read in bench.readers(workload).items():
            value = read(record)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
        tr = record.get("trace") or {}
        device.update(busy_s=tr.get("busy_s", 0.0),
                      window_s=tr.get("window_s", studies[0]))
        breakdown = {"device_ops": tr.get("device_ops", []),
                     "idle_gaps": tr.get("idle_gaps", [])}
    else:
        metrics[study.RATE_METRIC] = {"value": total_work / window_s,
                                      "unit": units[study.RATE_METRIC]}
        metrics[RSS_METRIC] = {"value": rss_mb, "unit": units[RSS_METRIC]}
        metrics[SETUP_METRIC] = {"value": setup_s, "unit": units[SETUP_METRIC]}

    # Correctness, once the window has closed and device memory was read.
    gc.collect()
    t_ref = time.perf_counter()
    numbers = study.check(spec, rows, captured, rng)
    checks = {k: {"value": numbers[k], "limit": v} for k, v in limits.items()
              if k in numbers}
    missing = sorted(set(limits) - set(numbers))
    correct = not missing and all(
        not math.isnan(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"{workload}: reference_s={time.perf_counter() - t_ref}")
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if missing:
        print(f"check missing {missing}", file=sys.stderr)
    result = {"correct": bool(correct),
              "attempted": len(studies) * len(rows),
              "failed": 0 if correct else len(rows),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                         bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
