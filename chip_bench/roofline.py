"""Peak rates of each device kind, and the bytes a replay call must move.

The peaks are published figures, keyed by ``device_kind`` as JAX reports
it.  A kind that is not in the table is an error: a roofline share against a
guessed peak would mean nothing.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM at 819 GB/s.  JAX
    # reports the v5e as "TPU v5 lite".
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

# Interface widths of the replay entry points (repro.sim.engine): issue and
# service times are float64, resource ids int32, queue depths int64.
TIME_BYTES = 8
RESOURCE_BYTES = 4
DEPTH_BYTES = 8


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; KeyError if it is unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(have {sorted(PEAKS)})") from None


def replay_bytes(rows: int, n: int) -> int:
    """Least bytes one replay call over ``rows`` x ``n`` events must move.

    Each per-event input is read once (issue time, resource, service) and
    each output written once (start, finish, wait, queue depth).  Issue
    times are one ``(n,)`` column shared by every row (the 1-D call has one
    row).  Implementation passes and padding do not count: this is the work
    the scan's interface asks for.
    """
    issue = n * TIME_BYTES
    inputs = rows * n * (RESOURCE_BYTES + TIME_BYTES)
    outputs = rows * n * (3 * TIME_BYTES + DEPTH_BYTES)
    return issue + inputs + outputs
