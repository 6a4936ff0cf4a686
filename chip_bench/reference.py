"""Plain references the benchmark holds the timed path to.

* :func:`fifo_replay` — per-resource FIFO service written as the recurrence
  itself (``start = max(issue, previous finish)``), one event per resource
  per step, in the precision asked for.  It shares no code with the
  program's closed-form segmented scan.  :func:`control_replay` is the same
  reference computed in float32 and put in the program's place: the control
  that the comparison must refuse.
* :func:`flatten` / :func:`max_rel_gap` — field-by-field comparison of two
  reports (every numeric leaf of the dataclass, strings by equality).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# Finish times this close to an issue time may count as in flight or not.
DEPTH_TIE_NS = 1.0


def fifo_replay(t_issue, resource, service, dtype=np.float64):
    """Solve FIFO service per resource; outputs in ``(resource, issue)`` order.

    Events on one resource are served one at a time in issue order, ties in
    input order.  Returns ``(order, t_sorted, svc_sorted, start, finish,
    wait, depth)`` where ``order`` maps sorted position to input index and
    ``depth[i]`` counts the earlier events of the same resource that have not
    finished before event ``i`` is issued (finish >= issue).
    """
    order = np.lexsort((t_issue, resource))
    res = np.asarray(resource)[order]
    t = np.asarray(t_issue)[order].astype(dtype)
    svc = np.asarray(service)[order].astype(dtype)
    n = t.shape[0]
    finish = np.empty(n, dtype)
    depth = np.empty(n, np.int64)
    if n == 0:
        return order, t, svc, finish.copy(), finish, finish.copy(), depth
    first = np.flatnonzero(np.r_[True, res[1:] != res[:-1]])
    length = np.diff(np.r_[first, n])
    # Longest segments first, so the segments still live at step k are a
    # prefix of this list.
    by_len = np.argsort(-length, kind="stable")
    first, length = first[by_len], length[by_len]
    prev = np.full(first.shape[0], -np.inf, dtype)
    live = first.shape[0]
    for k in range(int(length[0])):
        while length[live - 1] <= k:
            live -= 1
        idx = first[:live] + k
        done = np.maximum(t[idx], prev[:live]) + svc[idx]
        finish[idx] = done
        prev[:live] = done
    start = finish - svc
    wait = start - t
    for f, m in zip(first, length):
        seg = slice(f, f + m)
        depth[seg] = np.arange(m) - np.searchsorted(finish[seg], t[seg],
                                                    side="left")
    return order, t, svc, start, finish, wait, depth


def control_replay(t_issue, resource, service, kind, batched):
    """The float32 FIFO reference in the replay's place (the control)."""
    from repro.sim.engine import BatchedReplaySchedule, ReplaySchedule

    rows = resource if batched else resource[None]
    svcs = service if batched else service[None]
    parts = {k: [] for k in ("order", "resource", "t", "svc", "start",
                             "finish", "wait", "depth")}
    for res, svc in zip(rows, svcs):
        order, t, s, start, finish, wait, depth = fifo_replay(
            t_issue, res, svc, np.float32)
        for k, v in (("order", order), ("resource", res[order]),
                     ("t", t), ("svc", s), ("start", start),
                     ("finish", finish), ("wait", wait), ("depth", depth)):
            parts[k].append(v.astype(np.float64) if v.dtype == np.float32
                            else v)
    out = {k: np.stack(v) for k, v in parts.items()}
    cls = BatchedReplaySchedule if batched else ReplaySchedule
    pick = (lambda a: a) if batched else (lambda a: a[0])
    return cls(resource=pick(out["resource"]), t_issue_ns=pick(out["t"]),
               service_ns=pick(out["svc"]), kind=pick(kind[out["order"]]),
               start_ns=pick(out["start"]), finish_ns=pick(out["finish"]),
               wait_ns=pick(out["wait"]), queue_depth=pick(out["depth"]),
               order=pick(out["order"]))


def replay_numbers(captured: dict) -> dict:
    """Hold captured program replay rows to :func:`fifo_replay`.

    Both sides are put back in input order by their own sort permutation,
    so a wrong order shows as a finish-time gap.  A queue depth counts as
    wrong only outside what the reference allows when finish times within
    :data:`DEPTH_TIE_NS` of the issue time may fall either side of it: the
    program's closed-form scan and the recurrence round differently.
    """
    gap, depth_bad = 0.0, 0
    for cap in captured.values():
        t, res, svc = cap["t_issue"], cap["resource"], cap["service"]
        order, t_s, _, _, finish, _, _ = fifo_replay(t, res, svc)
        n = order.shape[0]
        if not n:
            continue
        ref_finish = np.empty(n)
        ref_finish[order] = finish
        got_finish = np.empty(n)
        got_finish[cap["order"]] = cap["finish"]
        gap = max(gap, float(np.max(np.abs(got_finish - ref_finish))))
        lo, hi = depth_bounds(np.asarray(res)[order], t_s, finish)
        got_depth = np.empty(n, np.int64)
        got_depth[cap["order"]] = cap["depth"]
        d = got_depth[order]
        depth_bad += int(np.count_nonzero((d < lo) | (d > hi)))
    return {"replay_finish_gap_ns": gap, "replay_depth_outside": depth_bad}


def depth_bounds(res_s, t_s, finish, tie_ns: float = None):
    """Least and most in-flight counts per event (sorted order) when finish
    times within ``tie_ns`` of the issue time may count either way."""
    tie_ns = DEPTH_TIE_NS if tie_ns is None else tie_ns
    n = t_s.shape[0]
    lo = np.empty(n, np.int64)
    hi = np.empty(n, np.int64)
    first = np.flatnonzero(np.r_[True, res_s[1:] != res_s[:-1]])
    for f, e in zip(first, np.r_[first[1:], n]):
        k = np.arange(e - f)
        fin, t = finish[f:e], t_s[f:e]
        lo[f:e] = k - np.minimum(np.searchsorted(fin, t + tie_ns, side="left"), k)
        hi[f:e] = k - np.minimum(np.searchsorted(fin, t - tie_ns, side="left"), k)
    return lo, hi


def flatten(obj, prefix: str = "") -> dict:
    """Every leaf of a (nested) dataclass / dict / tuple, keyed by path."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        obj = {f.name: getattr(obj, f.name) for f in dataclasses.fields(obj)}
    if isinstance(obj, dict):
        out = {}
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}{k}."))
        return out
    if isinstance(obj, (list, tuple)):
        out = {f"{prefix}#": len(obj)}
        for i, v in enumerate(obj):
            out.update(flatten(v, f"{prefix}{i}."))
        return out
    return {prefix.rstrip("."): obj}


def rel_gap(a, b) -> float:
    """0 for equal leaves (NaN equals NaN), else the gap relative to the
    larger magnitude; inf for leaves that cannot be compared."""
    if isinstance(a, (bool, np.bool_, int, float, np.integer, np.floating)) \
            and isinstance(b, (bool, np.bool_, int, float, np.integer,
                               np.floating)):
        a, b = float(a), float(b)
        if a == b or (math.isnan(a) and math.isnan(b)):
            return 0.0
        if math.isnan(a) or math.isnan(b) or math.isinf(a) or math.isinf(b):
            return math.inf
        return abs(a - b) / max(abs(a), abs(b))
    return 0.0 if a == b else math.inf


def max_rel_gap(a, b) -> tuple[float, str]:
    """Largest :func:`rel_gap` over the leaves of two objects, and where."""
    fa, fb = flatten(a), flatten(b)
    worst, where = 0.0, ""
    for key in sorted(set(fa) | set(fb)):
        g = rel_gap(fa[key], fb[key]) if key in fa and key in fb else math.inf
        if g > worst:
            worst, where = g, key
    return worst, where
