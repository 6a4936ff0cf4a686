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
apart; and the pad tail sorts above every real lane.

Only order operations run on the device, and only on 32-bit integers.
The host hands each keyed float64 array over as its raw IEEE bits (a
zero-copy ``int64`` view, never a float64 array), and each device program
first maps every value's two 32-bit words to an order-preserving int32
``(hi, lo)`` pair (:func:`_key_words`: the magnitude bits flipped for
negative values, the low word offset so that signed order is unsigned
order); the pairs compare lexicographically as the floats do.  The
running max maps its result back by the same map, which is its own
inverse, and returns the IEEE high and low words, which the host
interleaves into float64 in one copy.  The device touches 64-bit values
only to split them into words, by shift and convert; it never orders or
adds them.  The result then depends
neither on the device's float64 nor on its int64 arithmetic, both of
which a TPU emulates: on a v5e a float64 round-trip already changes low
bits, and XLA's running max over int64 replay keys returned wrong words.
Everything that rounds — the offset encode/decode, finish/start/wait, the
``big2`` span — stays in numpy, operand for operand as in :mod:`.ref`,
which also keeps multiplies (which XLA may contract into FMAs) and
reassociating reductions off the device.  The keys are never ``-0.0``:
every keyed value is ``x + off`` with ``off >= +0.0``, so key order and
float order agree on every tie.  :func:`.ref._to_pair` is the numpy
reference of the word map.

To bound recompiles across a sweep (event counts differ per grid point),
the device sees rows padded to the next power of two; the host does not.
Every float pass of ``replay_scan`` runs on the ``(R, n)`` real lanes,
operand for operand as in :mod:`.ref`, and each key is written straight
into the real part of an ``(R, npad)`` int64 bit buffer whose tail holds
``+inf``'s bits (:data:`PAD_KEY`).  Real keys are finite, so the tail
sorts above every one of them.  That is all the padding needs: the
running max never carries a pad back into real lanes, since the pads come
last; the merge rank still sees sorted rows; and no pad ``a`` counts
below a real ``q``, since ``side="left"`` counts only keys strictly
below.  The pads' own results are cut off on the host before any decode.

With ``repro.obs`` enabled, each host phase opens a span (``pad``: the
bit buffers and their tails; ``encode``, ``decode``: float work on the
real lanes and the running max's word interleave) and each device
program one ``device/cummax`` or ``device/search`` span, from the first
transfer of its key bits to the numpy result in hand (transfer in, word
map and kernel, transfer out); ``replay_scan`` counts the lanes it gives
the device (``replay/lanes``, R x n) and the padded lanes they fill
(``replay/lanes_padded``, R x npad), and every device call the bytes it
ships each way (``replay/bytes_to_device``, ``replay/bytes_from_device``).
The device programs carry stable names (``replay_cummax``,
``replay_search``) in the profiler's trace: as module names
(``jit_replay_cummax``, ``jit_replay_search``) and as a
``jax.named_scope`` over their ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.device import x64
from repro.kernels.segmented_replay.segmented_replay import (
    LANES,
    PAIR_MIN,
    ROWS,
    cummax_2d,
    lexmax,
)
from repro.obs import core as obs

DEFAULT_CHUNK = 1024
TILE = ROWS * LANES  # lanes of one (8, 128) int32 tile
_MAGNITUDE = np.int32(0x7FFF_FFFF)


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _named_jit(name: str, **jit_kwargs):
    """``jax.jit`` under a stable program name.

    The compiled module is ``jit_<name>``, the name a profiler trace gives
    the module's event around the program's ops, whatever the Python
    function is called; a ``jax.named_scope`` of the same name inside marks
    each op's metadata as well.
    """
    def wrap(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, **jit_kwargs)

    return wrap


def _key_words(h, l):
    """Order-preserving int32 ``(hi, lo)`` pair of float64 IEEE words.

    ``h`` and ``l`` are the high and low 32-bit words of the bits; the pair
    is :func:`.ref._to_pair`'s, word for word.  A negative value (sign bit
    set, ``s = -1``) has its magnitude bits flipped, and the low word is
    offset by ``2**31`` so that signed order is unsigned order.  The map
    keeps the sign bit, so it is its own inverse: applied to a pair it
    gives back the IEEE words.
    """
    s = h >> 31
    return h ^ (s & _MAGNITUDE), l ^ s ^ PAIR_MIN


def _words(bits):
    """The high and low int32 words of int64 ``bits``, by shift and convert."""
    return (bits >> 32).astype(jnp.int32), bits.astype(jnp.int32)


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


@_named_jit("replay_cummax", static_argnames=("scan", "chunk", "interpret"))
def _replay_cummax(bits, scan, chunk, interpret):
    """Row-wise running max of float64 values given as int64 IEEE bits.

    Splits the bits into words, maps them to key pairs, takes the running
    max (the Pallas kernel for ``scan="pallas"``, else :func:`_cummax_lax`)
    and maps the result back to the IEEE high and low words.  They return
    as two int32 arrays: an int64 result comes back from a v5e several
    times slower, and interleaving the words on the device would build a
    minor dimension of 2, which XLA pads to 128 lanes.
    """
    with jax.named_scope("replay_cummax"):
        hi, lo = _key_words(*_words(bits))
        if scan == "pallas":
            hi, lo = cummax_2d(hi, lo, chunk=chunk, interpret=interpret)
        else:
            hi, lo = _cummax_lax(hi, lo)
        return _key_words(hi, lo)


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


@jax.jit
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


@_named_jit("replay_search")
def _replay_search(a_bits, q_bits):
    """:func:`_merge_rank` of float64 rows given as int64 IEEE bits."""
    with jax.named_scope("replay_search"):
        return _merge_rank(*_key_words(*_words(a_bits)),
                           *_key_words(*_words(q_bits)))


def _bits(x: np.ndarray) -> np.ndarray:
    """The IEEE bits of float64 ``x``: a zero-copy ``int64`` view."""
    return np.ascontiguousarray(x, np.float64).view(np.int64)


def _floats(h: np.ndarray, l: np.ndarray) -> np.ndarray:
    """float64 values from their IEEE high and low int32 words: one
    interleaving copy (the words of a little-endian float64 lie low first)."""
    w = np.empty(h.shape + (2,), np.int32)
    w[..., 0], w[..., 1] = l, h
    return w.view(np.float64)[..., 0]


def _device_cummax(bits, scan, chunk, interpret):
    """Row-wise running max of float64 values given as int64 IEEE ``bits``.

    Bitwise ``np.maximum.accumulate``, returned as its IEEE high and low
    int32 words; blocks until they are on the host.  The device orders
    int32 pairs only: XLA's running max over int64 replay keys disagreed
    with numpy on a v5e, while the same max over int32 pairs agreed.
    """
    with obs.span("device/cummax"), x64():
        h, l = (np.asarray(w) for w in _replay_cummax(
            jax.device_put(bits), scan=scan, chunk=chunk, interpret=interpret))
    obs.count("replay/bytes_to_device", bits.nbytes)
    obs.count("replay/bytes_from_device", h.nbytes + l.nbytes)
    return h, l


def _device_search(a_bits, q_bits) -> np.ndarray:
    """:func:`_replay_search` of float64 rows given as int64 IEEE bits;
    blocks for the numpy result."""
    with obs.span("device/search"), x64():
        idx = np.asarray(_replay_search(jax.device_put(a_bits),
                                        jax.device_put(q_bits)))
    obs.count("replay/bytes_to_device", a_bits.nbytes + q_bits.nbytes)
    obs.count("replay/bytes_from_device", idx.nbytes)
    return idx


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
    h, l = _device_cummax(_bits(x), scan, chunk, interpret)
    with obs.span("decode"):
        return _floats(h, l)


def _next_pow2(n: int, floor: int = 4096) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


# The key of every pad lane: above every finite key, which every real one is.
PAD_KEY = np.inf


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
    # The bits the device gets: the running max's keys in row 0, then the
    # search's finish keys there and its issue keys in row 1.  The keys are
    # computed straight into the real lanes, through float64 views.
    bits = np.empty((2, R, npad), np.int64)
    keys = bits[:, :, :n].view(np.float64)
    if npad != n:
        with obs.span("pad"):
            bits[:, :, n:].view(np.float64)[...] = PAD_KEY
    with obs.span("encode"):
        off = seg_id * big[:, None]
        np.add(v, off, out=keys[0])
    h, l = _device_cummax(bits[0], scan, chunk, interpret)
    # Operand for operand as ``ref.replay_scan_np``, on the real lanes.
    with obs.span("decode"):
        finish = _floats(h[:, :n], l[:, :n])  # the running max
        finish -= off
        finish += s_local
        start = finish - svc
        wait = start - t_s
        fmax = np.maximum(finish.max(axis=1), t_s.max(axis=1))
        fmin = np.minimum(finish.min(axis=1), t_s.min(axis=1))
        big2 = (fmax - fmin) + 1.0
    with obs.span("encode"):
        off2 = np.multiply(seg_id, big2[:, None], out=off)
        np.add(finish, off2, out=keys[0])
        np.add(t_s, off2, out=keys[1])
    idx = _device_search(bits[0], bits[1])
    with obs.span("decode"):
        return finish, start, wait, np.arange(n) - idx[:, :n]
