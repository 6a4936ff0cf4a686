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

The queue-depth ``searchsorted`` is a bitonic merge rank
(:func:`_merge_rank`), made only of elementwise passes and shifts: no
gather, scatter or sort.  It merges each row's finish keys with its
reversed issue keys by the bitonic half-cleaner stages, counts with a
running sum of tags how many finish keys precede each issue key, and
routes the counts back by replaying the stages' swaps in reverse.  It
needs both rows sorted, as ``numpy.searchsorted`` in :mod:`.ref` needs its
first: within a bank segment finish times are nondecreasing (a running max
plus a nondecreasing local sum) and so are issue times (events are sorted
by ``(resource, t_issue)``); the ``seg_id * big2`` offsets keep segments
apart; and the neutral pad tail sorts above every real lane.

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

With ``repro.obs`` enabled, each host phase opens a span (``pad``,
``encode``, ``decode``) and each device program one ``device/cummax`` or
``device/search`` span, from the first transfer of its int32 pairs to the
numpy result in hand (transfer in, kernel, transfer out); ``replay_scan``
counts the lanes it gives the device (``replay/lanes``, R x n) and the
padded lanes they fill (``replay/lanes_padded``, R x npad).  The device
programs carry stable names (``replay_cummax``, ``replay_search``) in the
profiler's trace: as module names (``jit_replay_cummax``,
``jit_replay_search``) and as a ``jax.named_scope`` over their ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segmented_replay.segmented_replay import (
    LANES,
    PAIR_MIN,
    ROWS,
    cummax_2d,
    lexmax,
)
from repro.obs import core as obs

DEFAULT_CHUNK = 1024
_MAGNITUDE = np.int64(0x7FFF_FFFF_FFFF_FFFF)
TILE = ROWS * LANES  # lanes of one (8, 128) int32 tile


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _named_jit(name: str):
    """``jax.jit`` under a stable program name.

    The compiled module is ``jit_<name>``, the name a profiler trace gives
    the module's event around the program's ops, whatever the Python
    function is called; a ``jax.named_scope`` of the same name inside marks
    each op's metadata as well.
    """
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)

    return wrap


def _to_pair(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Order-preserving int32 ``(hi, lo)`` image of float64 ``x``.

    The int64 key is the IEEE bits with the magnitude bits flipped for
    negative values (no NaN, no ``-0.0``); its high word and its low word
    (offset so that signed order is unsigned order) compare
    lexicographically as the floats do.
    """
    bits = np.ascontiguousarray(x, np.float64).view(np.int64)
    return _split(bits ^ ((bits >> 63) & _MAGNITUDE))


def _split(k: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The int32 ``(hi, lo)`` words of int64 keys ``k``, in ``k``'s order."""
    return (k >> 32).astype(np.int32), ((k & 0xFFFF_FFFF) - 2**31).astype(np.int32)


def _from_pair(hi, lo) -> np.ndarray:
    k = (np.asarray(hi).astype(np.int64) << 32) | (
        np.asarray(lo).astype(np.int64) + 2**31
    )
    return (k ^ ((k >> 63) & _MAGNITUDE)).view(np.float64)


@_named_jit("replay_cummax")
def _cummax_lax(hi, lo):
    """Doubling-shift running max of ``(hi, lo)`` pairs along axis 1.

    The XLA twin of the Pallas kernel.  Used instead of ``jax.lax.cummax``,
    whose TPU lowering takes minutes to compile at a million lanes; this one
    compiles in about two seconds.
    """
    n, s = hi.shape[1], 1
    with jax.named_scope("replay_cummax"):
        while s < n:
            fill = jnp.full((hi.shape[0], s), PAIR_MIN, jnp.int32)
            hi, lo = lexmax(
                hi, lo,
                jnp.concatenate([fill, hi[:, :-s]], axis=1),
                jnp.concatenate([fill, lo[:, :-s]], axis=1),
            )
            s *= 2
    return hi, lo


def _lt(a, b):
    """Lexicographic ``a < b`` of ``(hi, lo, tag)`` int32 word triples."""
    (ah, al, at), (bh, bl, bt) = a, b
    return (ah < bh) | ((ah == bh) & ((al < bl) | ((al == bl) & (at < bt))))


def _halves(x, h):
    """The lower and upper ``h`` lanes of every ``2h`` block of ``x``.

    ``x`` is ``(R, M, LANES)``, one row of ``M * LANES`` lanes per ``r``;
    for ``h`` a multiple of ``TILE`` both halves are whole ``(8, 128)``
    tiles, so the split is a reshape of major dimensions.
    """
    R, M, _ = x.shape
    g = h // LANES
    v = x.reshape(R, M // (2 * g), 2, g, LANES)
    return v[:, :, 0], v[:, :, 1]


def _partner(x, h):
    """Each lane's partner ``h`` lanes away, and which lanes are lower.

    For ``h`` below a tile the partner lies in the same ``(8, 128)`` tile:
    ``h // 128`` sublanes away, or ``h`` lanes away within a 128-lane row,
    so both are rotations of ``x`` and no lane pairs across a row.
    """
    axis, s = (1, h // LANES) if h >= LANES else (2, h)
    lower = (jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) & s) == 0
    return lower, jnp.where(lower, jnp.roll(x, -s, axis), jnp.roll(x, s, axis))


def _swaps(words, h):
    """Which pairs of the half-cleaner stage ``h`` are out of order.

    One flag per pair (the lower half's shape) where ``h >= TILE``, else
    one per lane, equal on both lanes of a pair.  Ties stay in place.
    """
    if h >= TILE:
        lower, upper = zip(*(_halves(w, h) for w in words))
        return _lt(upper, lower)
    lower, partner = zip(*(_partner(w, h) for w in words))
    return jnp.where(lower[0], _lt(partner, words), _lt(words, partner))


def _exchange(x, h, swap):
    """Swap the pairs of stage ``h`` that ``swap`` flags; its own inverse."""
    if h >= TILE:
        lower, upper = _halves(x, h)
        return jnp.stack(
            [jnp.where(swap, upper, lower), jnp.where(swap, lower, upper)], axis=2
        ).reshape(x.shape)
    return jnp.where(swap, _partner(x, h)[1], x)


def _prefix_sum(x, axis):
    """Inclusive running sum along ``axis`` by doubling shift-adds.

    Exact in int32; used instead of ``jnp.cumsum`` for the reason
    :func:`_cummax_lax` avoids ``lax.cummax``.
    """
    n, s = x.shape[axis], 1
    while s < n:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (s, 0)
        x = x + jnp.pad(jax.lax.slice_in_dim(x, 0, n - s, axis=axis), pad)
        s *= 2
    return x


@_named_jit("replay_search")
def _merge_rank(a_hi, a_lo, q_hi, q_lo):
    """Row-wise ``searchsorted(a, q, side="left")`` over ``(hi, lo)`` pairs.

    Both ``a`` and ``q`` must be sorted along each row, and their common
    length ``n`` a power of two of at least 64.  A bitonic merge
    rank, with no gather, scatter or sort:

    1. per row, ``a`` (tag 1) followed by ``q`` reversed (tag 0) is a
       bitonic sequence of ``m = 2n`` lanes;
    2. the half-cleaner stages ``h = n, n/2, ..., 1`` sort it by
       ``(hi, lo, tag)``, each keeping its swap flags; on a tie ``q``
       (tag 0) precedes ``a``, so only ``a`` strictly below ``q`` precedes
       it, which is ``side="left"``;
    3. the running sum of the tags then holds, at each ``q`` lane, the
       count of ``a`` below it;
    4. replaying the stages' swaps in reverse order (``h = 1, ..., n``)
       routes each count back to its own lane, and ``q``'s counts are
       read from the second half, reversed.

    Equal keys of one tag are interchangeable, so it does not matter that
    the merge is not stable.  Rows are laid out ``(R, m // 128, 128)``.
    """
    R, n = a_hi.shape
    m = 2 * n

    def row(a, q):
        return jnp.concatenate([a, q[:, ::-1]], axis=1).reshape(R, m // LANES, LANES)

    with jax.named_scope("replay_search"):
        words = [row(a_hi, q_hi), row(a_lo, q_lo),
                 row(jnp.ones_like(a_hi), jnp.zeros_like(q_hi))]
        stages, h = [], n
        while h:
            swap = _swaps(words, h)
            words = [_exchange(w, h, swap) for w in words]
            stages.append((h, swap))
            h //= 2
        within = _prefix_sum(words[2], 2)  # along each 128-lane row
        before = _prefix_sum(within[:, :, -1], 1) - within[:, :, -1]
        count = within + before[:, :, None]
        for h, swap in reversed(stages):
            count = _exchange(count, h, swap)
        return count.reshape(R, m)[:, n:][:, ::-1]


def _device_cummax(hi, lo, scan, chunk, interpret):
    """Row-wise running max of int32 ``(hi, lo)`` pairs on the device.

    The device never orders a 64-bit value: the v5e emulates 64-bit
    integers, and XLA's running max over int64 replay keys disagreed with
    numpy there, while the same max over int32 pairs agreed.  Blocks until
    the numpy result is on the host.
    """
    with obs.span("device/cummax"):
        hi, lo = jnp.asarray(hi), jnp.asarray(lo)
        if scan == "pallas":
            hi, lo = cummax_2d(hi, lo, chunk=chunk, interpret=interpret)
        else:
            hi, lo = _cummax_lax(hi, lo)
        return np.asarray(hi), np.asarray(lo)


def _device_search(a_hi, a_lo, q_hi, q_lo) -> np.ndarray:
    """:func:`_merge_rank` on the device; blocks for the numpy result."""
    with obs.span("device/search"):
        pairs = [jnp.asarray(w) for w in (a_hi, a_lo, q_hi, q_lo)]
        return np.asarray(_merge_rank(*pairs))


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
    with obs.span("encode"):
        hi, lo = _to_pair(x)
    hi, lo = _device_cummax(hi, lo, scan, chunk, interpret)
    with obs.span("decode"):
        return _from_pair(hi, lo)


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
    through the chunked Pallas kernel; the depth ``searchsorted`` is the XLA
    merge rank either way.  Returns numpy ``(finish, start, wait, depth)``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    R, n = v.shape
    if n == 0:
        e = np.empty((R, 0))
        return e, e.copy(), e.copy(), np.empty((R, 0), np.int64)
    npad = _next_pow2(n)
    obs.count("replay/lanes", R * n)
    obs.count("replay/lanes_padded", R * npad)
    if npad != n:
        with obs.span("pad"):
            v, seg_id, s_local, svc, t_s = _pad_neutral(
                v, seg_id, s_local, svc, t_s, npad
            )
    with obs.span("encode"):
        off = seg_id * big[:, None]
        hi, lo = _to_pair(v + off)
    hi, lo = _device_cummax(hi, lo, scan, chunk, interpret)
    with obs.span("decode"):
        running_max = _from_pair(hi, lo) - off
        finish = s_local + running_max
        start = finish - svc
        wait = start - t_s
        fmax = np.maximum(finish.max(axis=1), t_s.max(axis=1))
        fmin = np.minimum(finish.min(axis=1), t_s.min(axis=1))
        big2 = (fmax - fmin) + 1.0
    with obs.span("encode"):
        off2 = seg_id * big2[:, None]
        pairs = (*_to_pair(finish + off2), *_to_pair(t_s + off2))
    idx = _device_search(*pairs)
    with obs.span("decode"):
        depth = np.arange(npad) - idx.astype(np.int64)
        return finish[:, :n], start[:, :n], wait[:, :n], depth[:, :n]
