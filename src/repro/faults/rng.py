"""Counter-based RNG for deterministic fault injection.

Every fault draw is a *pure function* of ``(seed, stream, index)`` — there
is no sequential generator state to thread through the pricing paths, so
the serving loop's per-step ``TechPricer.price_step`` and the sweep's
batched :meth:`~repro.serve.replay.NeutralRun.price` reproduce the same
draws as long as they agree on the per-event index (they do: the
within-class global event index is identical across both paths, see
``docs/faults.md``).  The hash is a splitmix64-style finalizer over the
mixed counter words; the top 53 bits become a float64 uniform in [0, 1).
"""

from __future__ import annotations

import numpy as np

# Draw streams: disjoint key spaces so a write-retry draw can never collide
# with a bank-window or replica-lifetime draw at the same index.
STREAM_WRITE_RETRY = 0x1
STREAM_BANK_WINDOW = 0x2
STREAM_REPLICA_LIFE = 0x3

_GAMMA = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_INV_2_53 = float(2.0**-53)


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (vectorized, wrapping uint64 arithmetic)."""
    x = (x ^ (x >> np.uint64(30))) * _MIX1
    x = (x ^ (x >> np.uint64(27))) * _MIX2
    return x ^ (x >> np.uint64(31))


def counter_uniform(seed: int, stream: int, idx, idx2=0) -> np.ndarray:
    """Uniform [0, 1) float64 draws keyed on ``(seed, stream, idx, idx2)``.

    ``idx``/``idx2`` may be scalars or integer arrays (broadcast together);
    the result has the broadcast shape.  Bit-reproducible across platforms:
    only wrapping uint64 arithmetic and a constant scale are involved.
    """
    a = np.asarray(idx, np.int64).astype(np.uint64)
    b = np.asarray(idx2, np.int64).astype(np.uint64)
    a, b = np.broadcast_arrays(a, b)
    with np.errstate(over="ignore"):
        key = np.uint64(seed) * _GAMMA + np.uint64(stream) * _MIX2
        x = _mix(a * _GAMMA + key)
        x = _mix(x ^ (b * _MIX1 + _GAMMA))
    return (x >> np.uint64(11)).astype(np.float64) * _INV_2_53
