"""Public segmented-replay ops: device cummax + the batched replay scan.

Two entry points, both returning numpy arrays bit-identical to the numpy
reference path in ``repro.sim.engine`` / :mod:`.ref`:

* :func:`cummax` — row-wise running max on the device, via the Pallas
  kernel (``scan="pallas"``) or an XLA doubling scan (``scan="lax"``); what
  ``SimConfig(backend="jax"|"pallas")`` routes the 1-D replay's scan
  through.
* :func:`replay_scan` — the batched sweep replay: the reference's float
  arithmetic on the host, its two order operations (the running max and the
  queue-depth ``searchsorted``) on the device.

Only order operations run on the device, and only on 32-bit integers.
Each float64 is mapped on the host to an order-preserving int64 key (the
IEEE bits with the magnitude bits flipped for negative values), split into
an int32 ``(hi, lo)`` pair (:func:`_to_pair`), and the device compares
pairs lexicographically.  The result then depends neither on the device's
float64 nor on its int64, both of which a TPU emulates: on a v5e a float64
round-trip already changes low bits, and XLA's running max over int64
replay keys returned wrong words.  Everything that rounds — the offset
encode/decode, finish/start/wait, the ``big2`` span — stays in numpy,
operand for operand as in :mod:`.ref`, which also keeps multiplies (which
XLA may contract into FMAs) and reassociating reductions off the device.
The keys are never ``-0.0``: every keyed value is ``x + off`` with
``off >= +0.0``, so key order and float order agree on every tie.

To bound recompiles across a sweep (event counts differ per grid point),
``replay_scan`` pads rows to the next power of two with a *neutral tail
segment*: pad values chosen so the padded entries form their own trailing
segment whose finish/issue values stay inside the real data's range — the
big2 span, every real output, and every real queue depth are bit-identical
to the unpadded computation (see ``_pad_neutral``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segmented_replay.segmented_replay import (
    PAIR_MIN,
    cummax_2d,
    lexmax,
)

DEFAULT_CHUNK = 1024
_MAGNITUDE = np.int64(0x7FFF_FFFF_FFFF_FFFF)


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _to_pair(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Order-preserving int32 ``(hi, lo)`` image of float64 ``x``.

    The int64 key is the IEEE bits with the magnitude bits flipped for
    negative values (no NaN, no ``-0.0``); its high word and its low word
    (offset so that signed order is unsigned order) compare
    lexicographically as the floats do.
    """
    bits = np.ascontiguousarray(x, np.float64).view(np.int64)
    k = bits ^ ((bits >> 63) & _MAGNITUDE)
    return (k >> 32).astype(np.int32), ((k & 0xFFFF_FFFF) - 2**31).astype(np.int32)


def _from_pair(hi, lo) -> np.ndarray:
    k = (np.asarray(hi).astype(np.int64) << 32) | (
        np.asarray(lo).astype(np.int64) + 2**31
    )
    return (k ^ ((k >> 63) & _MAGNITUDE)).view(np.float64)


@jax.jit
def _cummax_lax(hi, lo):
    """Doubling-shift running max of ``(hi, lo)`` pairs along axis 1.

    The XLA twin of the Pallas kernel.  Used instead of ``jax.lax.cummax``,
    whose TPU lowering takes minutes to compile at a million lanes; this one
    compiles in about two seconds.
    """
    n, s = hi.shape[1], 1
    while s < n:
        fill = jnp.full((hi.shape[0], s), PAIR_MIN, jnp.int32)
        hi, lo = lexmax(
            hi, lo,
            jnp.concatenate([fill, hi[:, :-s]], axis=1),
            jnp.concatenate([fill, lo[:, :-s]], axis=1),
        )
        s *= 2
    return hi, lo


@jax.jit
def _searchsorted_rows(a_hi, a_lo, q_hi, q_lo):
    """Row-wise ``searchsorted(a, q, side="left")`` over ``(hi, lo)`` pairs.

    A binary search with lexicographic compares: the first index whose
    ``a`` is not below ``q`` (``a`` sorted along each row).
    """
    n = a_hi.shape[1]

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        at = jnp.minimum(mid, n - 1)
        m_hi = jnp.take_along_axis(a_hi, at, axis=1)
        m_lo = jnp.take_along_axis(a_lo, at, axis=1)
        below = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo < q_lo))
        open_ = lo < hi
        return (jnp.where(open_ & below, mid + 1, lo),
                jnp.where(open_ & ~below, mid, hi))

    bounds = (jnp.zeros(q_hi.shape, jnp.int32), jnp.full(q_hi.shape, n, jnp.int32))
    return jax.lax.fori_loop(0, n.bit_length(), halve, bounds)[0]


def _running_max(x, scan, chunk, interpret) -> np.ndarray:
    """Row-wise running max of float64 ``x``, ordered on the device as pairs.

    The device never orders a 64-bit value: the v5e emulates 64-bit
    integers, and XLA's running max over int64 replay keys disagreed with
    numpy there, while the same max over int32 pairs agreed.
    """
    hi, lo = (jnp.asarray(w) for w in _to_pair(x))
    if scan == "pallas":
        hi, lo = cummax_2d(hi, lo, chunk=chunk, interpret=interpret)
    else:
        hi, lo = _cummax_lax(hi, lo)
    return _from_pair(hi, lo)


def cummax(
    x: np.ndarray,
    *,
    scan: str = "pallas",
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> np.ndarray:
    """Row-wise running max of a 2D array, bitwise ``np.maximum.accumulate``."""
    if interpret is None:
        interpret = _auto_interpret()
    return _running_max(x, scan, chunk, interpret)


def _next_pow2(n: int, floor: int = 4096) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def _pad_neutral(v, seg_id, s_local, svc, t_s, npad):
    """Pad ``(R, n)`` inputs to ``(R, npad)`` without perturbing real outputs.

    The pad entries form one extra trailing segment per row (``seg_id`` one
    past the row's last) with ``t = t_max`` (the row's latest issue time),
    ``svc = s_local = 0`` and hence ``v = finish = t_max``.  Consequences,
    all exact: the cummax never feeds pads back into real lanes (pads come
    last); ``t_max`` lies inside ``[min(t), max(finish)]`` so the big2 span
    is unchanged; and the pads' augmented finish times sort strictly above
    every real entry, so real searchsorted insertion points are unchanged.
    """
    R, n = v.shape
    pad = npad - n
    t_max = t_s.max(axis=1, keepdims=True)
    zeros = np.zeros((R, pad))

    def cat(a, p):
        return np.concatenate([a, p], axis=1)

    return (
        cat(v, np.broadcast_to(t_max, (R, pad))),
        cat(seg_id, np.broadcast_to(seg_id[:, -1:] + 1, (R, pad))),
        cat(s_local, zeros),
        cat(svc, zeros),
        cat(t_s, np.broadcast_to(t_max, (R, pad))),
    )


def replay_scan(
    v: np.ndarray,
    seg_id: np.ndarray,
    s_local: np.ndarray,
    svc: np.ndarray,
    t_s: np.ndarray,
    big: np.ndarray,
    *,
    scan: str = "lax",
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched replay scan; bitwise-equal to ``ref.replay_scan_np``.

    ``scan="lax"`` runs the running max as an XLA program, ``scan="pallas"``
    through the chunked Pallas kernel; the depth ``searchsorted`` is XLA
    either way.  Returns numpy ``(finish, start, wait, depth)``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    R, n = v.shape
    if n == 0:
        e = np.empty((R, 0))
        return e, e.copy(), e.copy(), np.empty((R, 0), np.int64)
    npad = _next_pow2(n)
    if npad != n:
        v, seg_id, s_local, svc, t_s = _pad_neutral(
            v, seg_id, s_local, svc, t_s, npad
        )
    off = seg_id * big[:, None]
    running_max = _running_max(v + off, scan, chunk, interpret) - off
    finish = s_local + running_max
    start = finish - svc
    wait = start - t_s
    fmax = np.maximum(finish.max(axis=1), t_s.max(axis=1))
    fmin = np.minimum(finish.min(axis=1), t_s.min(axis=1))
    big2 = (fmax - fmin) + 1.0
    off2 = seg_id * big2[:, None]
    idx = _searchsorted_rows(
        *(jnp.asarray(w) for w in (*_to_pair(finish + off2), *_to_pair(t_s + off2)))
    )
    depth = np.arange(npad) - np.asarray(idx).astype(np.int64)
    return finish[:, :n], start[:, :n], wait[:, :n], depth[:, :n]
