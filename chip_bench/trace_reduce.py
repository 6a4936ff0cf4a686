"""Reduce a profiler trace (``.xplane.pb``) to device busy time and idle gaps.

The device's operations are the events of each device plane's ``XLA Ops``
line (``XLA Modules`` where a plane has no op line).  Busy time is the union
of their intervals inside the traced window, averaged over the devices; the
window is the harness's ``study`` span on the host.  Each idle gap is
labelled by the innermost harness span open on the host at its midpoint:
``replay`` inside a replay call, ``sweep host work`` elsewhere in the study.
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
HARNESS_SPANS = ("study", "replay")
TOP = 10


def extract(profile) -> dict:
    """Device op intervals per device, the harness spans on the host, and
    the names of every plane's lines, from a
    :class:`jax.profiler.ProfileData`; times in ns."""
    devices, spans, layout = [], [], {}
    for plane in profile.planes:
        lines = {line.name: line for line in plane.lines}
        layout[plane.name] = sorted(lines)
        if DEVICE_PLANE.match(plane.name):
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            devices.append([] if line is None else [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events if e.name in HARNESS_SPANS)
    return {"devices": devices, "spans": spans, "layout": layout}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(t: float, spans) -> str:
    inner = None
    for s, e, name in spans:
        if s <= t <= e and (inner is None or e - s < inner[1] - inner[0]):
            inner = (s, e, name)
    if inner is None:
        return "outside study"
    return "replay" if inner[2] == "replay" else "sweep host work"


def reduce(extracted: dict) -> dict:
    """``busy_s``, ``window_s``, ``idle_pct``, top ops and longest idle gaps.

    Returns ``busy_s = 0`` and no gaps when the trace holds no device plane;
    the caller decides whether that is an error.
    """
    spans = extracted["spans"]
    studies = [(s, e) for s, e, name in spans if name == "study"]
    devices = extracted["devices"]
    if studies:
        w0, w1 = studies[0]
    else:
        ends = [(s, e) for dev in devices for s, e, _ in dev]
        w0 = min((s for s, _ in ends), default=0.0)
        w1 = max((e for _, e in ends), default=0.0)
    window_ns = w1 - w0
    op_ns: dict = defaultdict(float)
    busy, gaps = [], []
    for dev in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in dev
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            op_ns[n] += e - s
        merged = _union((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                gaps.append((s - edge, _label((s + edge) / 2, spans)))
            edge = max(edge, e)
    busy_ns = sum(busy) / len(busy) if busy else 0.0
    gaps.sort(key=lambda g: -g[0])
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window_ns * 1e-9,
        "idle_pct": (100.0 * (1.0 - busy_ns / window_ns)
                     if window_ns > 0 and busy else None),
        "device_ops": [[name, ns * 1e-9] for name, ns in top],
        "idle_gaps": [[label, ns * 1e-9] for ns, label in gaps[:TOP]],
        "layout": extracted.get("layout", {}),
    }


def reduce_file(path) -> dict:
    from jax.profiler import ProfileData

    return reduce(extract(ProfileData.from_file(str(path))))
