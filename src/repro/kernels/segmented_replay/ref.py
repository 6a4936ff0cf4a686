"""NumPy reference for the batched segmented replay scan.

Pure numpy (no jax import): this is both the bit-exactness anchor the
jax/pallas backends are tested against and the fallback the no-jax CI leg
runs.  Row ``r`` of every array is one independent pricing of the same
event stream (one technology), already sorted into ``(resource, t_issue)``
order; the math is operand-for-operand the 1-D path in
``repro.sim.engine.replay_schedule``, so per-row outputs are bit-identical
to replaying each row alone (pinned by ``tests/test_replay_kernel.py``).
:func:`_to_pair` and :func:`_from_pair` are the numpy reference of the
order-preserving key-word map that the device programs apply (``ops``).
"""

from __future__ import annotations

import numpy as np

_MAGNITUDE = np.int64(0x7FFF_FFFF_FFFF_FFFF)


def replay_scan_np(v, seg_id, s_local, svc, t_s, big):
    """Solve the segmented max-plus recurrence for a batch of rows.

    Inputs are ``(R, n)`` float64/int64 arrays (``big`` is ``(R,)``); see
    ``repro.sim.engine.replay_schedule`` for their derivation.  Returns
    ``(finish, start, wait, depth)``, each ``(R, n)``.
    """
    # In-place updates below are bitwise-neutral: they only reuse buffers
    # (same elementwise ops) and swap addend order (IEEE + is commutative;
    # only *re-association* changes results).
    off = seg_id * big[:, None]
    aug = v + off
    np.maximum.accumulate(aug, axis=1, out=aug)
    aug -= off  # running max, decoded in place
    finish = aug
    finish += s_local  # s_local + running_max
    start = finish - svc
    wait = start - t_s

    # Queue depth via the same offset trick: one searchsorted per row over
    # the segment-augmented finish times (identical arithmetic to the 1-D
    # path's ``big2`` construction).
    fmax = np.maximum(finish.max(axis=1), t_s.max(axis=1))
    fmin = np.minimum(finish.min(axis=1), t_s.min(axis=1))
    big2 = (fmax - fmin) + 1.0
    off2 = seg_id * big2[:, None]
    finish_aug = finish + off2
    query = off2
    query += t_s  # t_s + off2, reusing the offset buffer
    R, n = v.shape
    ar = np.arange(n)
    depth = np.empty((R, n), np.int64)
    for r in range(R):
        depth[r] = ar - np.searchsorted(finish_aug[r], query[r], side="left")
    return finish, start, wait, depth


def _to_pair(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Order-preserving int32 ``(hi, lo)`` image of float64 ``x``.

    The numpy reference of the device's key-word map
    (``ops._key_words``).  The int64 key is the IEEE bits with the
    magnitude bits flipped for negative values (no NaN, no ``-0.0``); its
    high word and its low word (offset so that signed order is unsigned
    order) compare lexicographically as the floats do.
    """
    bits = np.ascontiguousarray(x, np.float64).view(np.int64)
    return _split(bits ^ ((bits >> 63) & _MAGNITUDE))


def _split(k: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The int32 ``(hi, lo)`` words of int64 keys ``k``, in ``k``'s order."""
    return (k >> 32).astype(np.int32), ((k & 0xFFFF_FFFF) - 2**31).astype(np.int32)


def _from_pair(hi, lo) -> np.ndarray:
    """The float64 values of ``(hi, lo)`` pairs; :func:`_to_pair` inverted."""
    k = (np.asarray(hi).astype(np.int64) << 32) | (
        np.asarray(lo).astype(np.int64) + 2**31
    )
    return (k ^ ((k >> 63) & _MAGNITUDE)).view(np.float64)
