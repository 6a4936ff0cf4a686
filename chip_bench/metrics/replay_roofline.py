"""Replay scan's share of its HBM roofline, in percent.

The least time one chip could take for the study's replay calls (the bytes
their interface must move, over peak HBM bandwidth) divided by the wall time
of those calls, timed by the harness's shim around the replay entry points.
The share stays comparable whatever implements the scan, on the device or on
the host.
"""

from chip_bench.roofline import peaks, replay_bytes


def read(record: dict):
    calls = [c for c in record.get("replay_calls", ()) if c["n"]]
    seconds = sum(c["seconds"] for c in calls)
    if not calls or seconds <= 0:
        return None
    total = sum(replay_bytes(c["rows"], c["n"]) for c in calls)
    floor_s = total / peaks(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * floor_s / seconds
