"""Hierarchical spans and counters with a near-zero-overhead disabled path.

The host-side face of ``repro.obs``: ``span("lower")`` times a phase,
``count("events", n)`` bumps a counter.  Observability is **off by
default** — the CLIs switch it on at startup (``enable()``), library code
never does — and the disabled path is designed to vanish: ``span()``
returns a module-level singleton no-op context manager (no allocation, no
clock read) and ``count()`` is a dict lookup away from a bare ``return``.
``tests/test_obs.py`` pins both properties, and the ``benchmarks/
serving_qps`` wall-clock gate (< 2x vs baseline in ``check_bench``) keeps
the hot paths honest.

Enabled spans nest: entering ``span("sweep")`` then ``span("price")``
records the inner time under the path ``"sweep/price"``.  Aggregation is
by path — ``phase_times()`` returns ``{path: total_seconds}``, which the
run manifest embeds as ``phases_s`` so every JSON artifact says where its
wall time went.  State is process-global and single-threaded by design
(the engines are single-threaded array programs); ``reset()`` clears it
between runs.

``enable(annotate=...)`` shares the spans with another clock: given a
context-manager factory such as ``jax.profiler.TraceAnnotation``, every
enabled span also enters ``annotate(path)`` under its full slash path, so
the span lands on that tool's timeline too (a profiler trace, aligned with
the device's operations).  This module itself imports nothing beyond the
standard library.
"""

from __future__ import annotations

import time


class _NoopSpan:
    """Singleton returned by ``span()`` while observability is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()
_STATE: "_ObsState | None" = None  # None <=> disabled


class _ObsState:
    __slots__ = ("spans", "counters", "stack", "annotate")

    def __init__(self, annotate=None):
        self.spans: dict[str, list] = {}  # path -> [n_calls, total_s]
        self.counters: dict[str, float] = {}
        self.stack: list[str] = []
        self.annotate = annotate  # path -> context manager, or None


class _Span:
    __slots__ = ("name", "t0", "mark")

    def __init__(self, name: str):
        self.name = name
        self.mark = None

    def __enter__(self):
        state = _STATE
        if state is not None:  # disabled mid-flight: degrade to no-op
            state.stack.append(self.name)
            if state.annotate is not None:
                self.mark = state.annotate("/".join(state.stack))
                self.mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.mark is not None:
            self.mark.__exit__(*exc)
        state = _STATE
        if state is not None and state.stack:
            path = "/".join(state.stack)
            state.stack.pop()
            rec = state.spans.get(path)
            if rec is None:
                state.spans[path] = [1, dt]
            else:
                rec[0] += 1
                rec[1] += dt
        return False


def enable(annotate=None) -> None:
    """Turn recording on (fresh state).  Idempotent.

    ``annotate`` (a factory ``path -> context manager``, e.g.
    ``jax.profiler.TraceAnnotation``) is entered around every span under
    its full path from now on; ``None`` stops annotating.
    """
    global _STATE
    if _STATE is None:
        _STATE = _ObsState()
    _STATE.annotate = annotate


def disable() -> None:
    """Turn recording off and drop all recorded state."""
    global _STATE
    _STATE = None


def enabled() -> bool:
    return _STATE is not None


def reset() -> None:
    """Clear spans/counters without changing the enabled/disabled state."""
    global _STATE
    if _STATE is not None:
        _STATE = _ObsState(_STATE.annotate)


def span(name: str):
    """Context manager timing one phase; nested spans record ``a/b`` paths.

    Disabled: returns the shared no-op singleton — no allocation, no clock.
    """
    if _STATE is None:
        return _NOOP
    return _Span(name)


def count(name: str, n: float = 1) -> None:
    """Bump a named counter by ``n``.  Disabled: a single ``is None`` test."""
    state = _STATE
    if state is None:
        return
    state.counters[name] = state.counters.get(name, 0) + n


def counters() -> dict[str, float]:
    """Current counter values (empty when disabled)."""
    return dict(_STATE.counters) if _STATE is not None else {}


def phase_times() -> dict[str, float]:
    """``{span_path: total_seconds}`` for every completed span."""
    if _STATE is None:
        return {}
    return {path: rec[1] for path, rec in _STATE.spans.items()}


def snapshot() -> dict:
    """Everything recorded so far, JSON-ready."""
    if _STATE is None:
        return {"enabled": False, "spans": {}, "counters": {}}
    return {
        "enabled": True,
        "spans": {
            path: {"calls": rec[0], "total_s": rec[1]}
            for path, rec in _STATE.spans.items()
        },
        "counters": dict(_STATE.counters),
    }
