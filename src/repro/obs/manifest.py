"""Run manifests: provenance stamped into every JSON artifact.

A manifest answers "what produced this file?": git sha, RNG seed, a stable
hash of the driving config/Scenario, library versions, platform, the JAX
backend and device kind, and the wall-time-per-phase split recorded by
:mod:`repro.obs.core`.  Two runs
whose manifests agree on ``(git_sha, seed, config_hash, versions)`` should
produce bit-identical metrics; ``manifest_diff`` makes disagreement
legible, and ``benchmarks/check_bench.py`` warns when a fresh benchmark
record and the committed baseline were produced by different
versions/seeds (a wall-clock delta between them is then not a regression
signal).

``config_hash`` canonicalizes dataclasses / dicts / tuples / numpy scalars
to sorted-key JSON before hashing, so hashes are stable across process
restarts and insertion orders — pinned by ``tests/test_obs.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import platform
import subprocess
import time

MANIFEST_SCHEMA = 1

_GIT_SHA_CACHE: dict[str, str | None] = {}


def git_sha(cwd: str | None = None) -> str | None:
    """HEAD commit sha of the repo containing ``cwd`` (None outside git)."""
    key = cwd or os.getcwd()
    if key not in _GIT_SHA_CACHE:
        try:
            out = subprocess.run(
                ["git", "rev-parse", "HEAD"],
                cwd=key, capture_output=True, text=True, timeout=5,
            )
            sha = out.stdout.strip() if out.returncode == 0 else None
        except (OSError, subprocess.SubprocessError):
            sha = None
        _GIT_SHA_CACHE[key] = sha or None
    return _GIT_SHA_CACHE[key]


def _canonical(obj):
    """Reduce ``obj`` to JSON-serializable primitives, deterministically."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            f.name: _canonical(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        }
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items(), key=lambda kv: str(kv[0]))}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (str, bool)) or obj is None:
        return obj
    if isinstance(obj, float):
        return float(obj)
    if isinstance(obj, int):
        return int(obj)
    # numpy scalars and anything else with .item(); fall back to repr.
    item = getattr(obj, "item", None)
    if callable(item):
        try:
            return _canonical(item())
        except (TypeError, ValueError):
            pass
    return repr(obj)


def config_hash(config) -> str:
    """Stable 16-hex-digit digest of a config/Scenario/dataclass/dict."""
    blob = json.dumps(_canonical(config), sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def environment() -> dict:
    """Library versions, platform, and the JAX device the run sees."""
    import numpy as np

    try:
        import jax

        from repro.device import device_info

        jax_version, device = jax.__version__, device_info()
    except (ImportError, RuntimeError):
        jax_version, device = None, {}
    return {
        "python": platform.python_version(),
        "numpy": np.__version__,
        "jax": jax_version,
        "platform": platform.platform(),
        "jax_backend": device.get("platform"),
        "device_kind": device.get("kind"),
    }


def run_manifest(
    seed: int | None = None,
    config=None,
    extra: dict | None = None,
    phases: dict | None = None,
) -> dict:
    """Build one run's manifest dict.

    ``phases`` defaults to the live :func:`repro.obs.phase_times` snapshot
    (empty when observability is disabled).  ``created_unix`` is the only
    non-deterministic field; comparisons (``manifest_diff``, the bench
    gate) ignore it.
    """
    from repro.obs.core import phase_times

    m = {
        "schema": MANIFEST_SCHEMA,
        "git_sha": git_sha(),
        "seed": seed,
        "config_hash": config_hash(config) if config is not None else None,
        **environment(),
        "created_unix": int(time.time()),
        "phases_s": {
            k: round(v, 6)
            for k, v in (phases if phases is not None else phase_times()).items()
        },
    }
    if extra:
        m.update(extra)
    return m


def stamp(payload: dict, seed: int | None = None, config=None,
          extra: dict | None = None, phases: dict | None = None) -> dict:
    """Inject a ``"manifest"`` key into a JSON-bound payload (in place)."""
    payload["manifest"] = run_manifest(seed=seed, config=config, extra=extra,
                                       phases=phases)
    return payload


# Fields whose disagreement makes two runs incomparable; everything else
# (timestamps, phase timings) is expected to vary run to run.
COMPARABLE_KEYS = ("schema", "git_sha", "seed", "config_hash", "python",
                   "numpy", "jax", "platform", "jax_backend", "device_kind")


def manifest_diff(a: dict | None, b: dict | None,
                  keys: tuple[str, ...] = COMPARABLE_KEYS) -> dict:
    """``{key: (a_value, b_value)}`` for every comparable key that differs.

    Either side may be ``None`` (artifact predates manifests): every key
    present on the other side then reports against ``None``.
    """
    a, b = a or {}, b or {}
    diff = {}
    for k in keys:
        va, vb = a.get(k), b.get(k)
        if va != vb:
            diff[k] = (va, vb)
    return diff
