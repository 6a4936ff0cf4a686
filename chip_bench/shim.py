"""Timing shim around the replay entry points the serving path calls.

:class:`ReplayShim` wraps ``repro.sim.engine.replay_schedule`` (the 1-D
replay of a closed loop's trace) and ``replay_schedule_batch`` (the sweep's
batched replay, also bound by name in ``repro.serve.replay``).  Each call is
timed on the host clock inside a ``jax.profiler.TraceAnnotation("replay")``
span and recorded with its real ``(rows, n)``; outputs pass through
untouched, so rows are bit-identical with the shim on or off.

Two hooks serve the correctness check and its tests, and are off in a
measured run: ``substitute(t, resource, service, kind)`` computes a batched
call's outputs in place of the program (the control), and ``alter(out)``
rewrites what the program returned (planted faults).  ``capture`` names the
``(call, row)`` pairs whose inputs and outputs are kept for the reference.
"""

from __future__ import annotations

import time

import numpy as np

import repro.serve.replay as serve_replay
import repro.sim.engine as engine


def _as_batch(t_issue, resource, service, kind, out):
    """View a 1-D call as a one-row batch: ``(resource, service, out rows)``."""
    if resource.ndim == 2:
        return resource, service, out
    return resource[None], service[None], engine.BatchedReplaySchedule(
        resource=out.resource[None], t_issue_ns=out.t_issue_ns[None],
        service_ns=out.service_ns[None], kind=out.kind[None],
        start_ns=out.start_ns[None], finish_ns=out.finish_ns[None],
        wait_ns=out.wait_ns[None], queue_depth=out.queue_depth[None],
        order=out.order[None])


class ReplayShim:
    """Context manager that times (and optionally captures) replay calls."""

    def __init__(self, substitute=None, alter=None):
        self.substitute = substitute
        self.alter = alter
        self.capture: set[tuple[int, int]] = set()
        self.calls: list[dict] = []
        self.captured: dict[tuple[int, int], dict] = {}
        self._saved = None

    def reset(self) -> None:
        """Start a new study: call indices count from 0 again."""
        self.calls = []
        self.captured = {}

    def _wrap(self, orig, batched: bool):
        from jax.profiler import TraceAnnotation

        def replay(t_issue, resource, service, kind, backend="numpy"):
            call = len(self.calls)
            with TraceAnnotation("replay"):
                t0 = time.perf_counter()
                if self.substitute is not None:
                    out = self.substitute(t_issue, resource, service, kind,
                                          batched=batched)
                else:
                    out = orig(t_issue, resource, service, kind, backend)
                t1 = time.perf_counter()
            if self.alter is not None:
                out = self.alter(out)
            rows = resource.shape[0] if batched else 1
            n = t_issue.shape[0]
            self.calls.append({"rows": rows, "n": n, "seconds": t1 - t0,
                               "t0": t0, "t1": t1})
            wanted = [r for (c, r) in self.capture if c == call]
            if wanted:
                res2, svc2, out2 = _as_batch(t_issue, resource, service, kind,
                                             out)
                for r in wanted:
                    if r < rows:
                        self.captured[(call, r)] = {
                            "t_issue": np.array(t_issue),
                            "resource": np.array(res2[r]),
                            "service": np.array(svc2[r]),
                            "order": np.array(out2.order[r]),
                            "finish": np.array(out2.finish_ns[r]),
                            "depth": np.array(out2.queue_depth[r]),
                        }
            return out

        return replay

    def __enter__(self):
        self._saved = (engine.replay_schedule, engine.replay_schedule_batch,
                       serve_replay.replay_schedule_batch)
        single, batch, _ = self._saved
        engine.replay_schedule = self._wrap(single, batched=False)
        engine.replay_schedule_batch = self._wrap(batch, batched=True)
        serve_replay.replay_schedule_batch = engine.replay_schedule_batch
        return self

    def __exit__(self, *exc):
        (engine.replay_schedule, engine.replay_schedule_batch,
         serve_replay.replay_schedule_batch) = self._saved
        self._saved = None
        return False
