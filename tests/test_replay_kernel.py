"""Differential suite for the segmented-replay kernel and batched scan.

Three layers of pinning, strongest first:

1.  **Oracle** — a tiny intentional-python FIFO queue replays each trace
    event by event; on integer-valued inputs every float op is exact, so the
    vectorized ``replay_schedule`` must match it *exactly*.
2.  **Backend trio** — ``numpy`` / ``jax`` / ``pallas`` must be *bitwise*
    identical on every schedule field, including on non-integer float data
    where XLA's FMA contraction of ``v + seg_id * big`` once silently
    diverged (the offsets are now multiplied out host-side; see
    ``repro.kernels.segmented_replay.ops``).
3.  **Batch vs per-row** — ``replay_schedule_batch`` row ``r`` must equal
    ``replay_schedule`` on that row's 1-D inputs, bitwise, per backend.

The adversarial cases cover empty traces, single events, empty banks /
gapped resource ids, single-event segments, timestamp ties, unsorted input
(the lexsort path), zero-service events, and segments longer than the
Pallas chunk (carry across grid steps).  Runs without jax (oracle + numpy
layers; the trio tests skip) and without hypothesis (seeded-sampling shim).
"""

import math

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels.segmented_replay.ref import replay_scan_np
from repro.sim.engine import (
    BACKENDS,
    SimConfig,
    UnknownBackendError,
    replay_schedule,
    replay_schedule_batch,
    resolve_backend,
)

try:
    import jax  # noqa: F401

    HAVE_JAX = True
except ImportError:
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax/pallas backends need jax")

SCHED_FIELDS = (
    "resource", "t_issue_ns", "service_ns", "kind",
    "start_ns", "finish_ns", "wait_ns", "queue_depth", "order",
)


def _assert_sched_equal(a, b, ctx=""):
    for f in SCHED_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype, f"{ctx}{f} dtype {x.dtype} != {y.dtype}"
        np.testing.assert_array_equal(x, y, err_msg=f"{ctx}{f}")


def fifo_oracle(t, res, svc):
    """Per-event FIFO replay: the semantic ground truth.

    Exact (no rounding ambiguity) whenever ``t`` and ``svc`` are
    integer-valued floats.  Returns arrays in ``lexsort((t, res))`` order.
    """
    order = np.lexsort((t, res))
    n = order.size
    start = np.empty(n)
    finish = np.empty(n)
    depth = np.empty(n, np.int64)
    prev_finish = {}
    history = {}  # resource -> finish times of its earlier events
    for i, j in enumerate(order):
        r = res[j]
        st = max(float(t[j]), prev_finish.get(r, -math.inf))
        fin = st + float(svc[j])
        depth[i] = sum(1 for f in history.setdefault(r, []) if f >= t[j])
        history[r].append(fin)
        prev_finish[r] = fin
        start[i], finish[i] = st, fin
    return order, start, finish, depth


def _trace(case, rng=None):
    """Adversarial trace library: (t_issue, resource, service) float64/int32."""
    rng = rng or np.random.default_rng(0)
    if case == "empty":
        return (np.empty(0), np.empty(0, np.int32), np.empty(0))
    if case == "single":
        return (np.array([3.0]), np.array([7], np.int32), np.array([5.0]))
    if case == "gapped_banks":
        # Banks 0..63 exist but only {3, 17, 59} see traffic; ids far apart.
        n = 120
        t = np.sort(rng.integers(0, 500, n)).astype(np.float64)
        res = rng.choice([3, 17, 59], n).astype(np.int32)
        return t, res, rng.integers(1, 20, n).astype(np.float64)
    if case == "single_event_segments":
        # Every event on its own bank: all segments have length one.
        n = 64
        t = np.sort(rng.integers(0, 300, n)).astype(np.float64)
        return t, np.arange(n, dtype=np.int32), np.full(n, 4.0)
    if case == "ties":
        # Many identical timestamps, several per bank: order is decided by
        # the stable sort alone.
        t = np.repeat([10.0, 10.0, 20.0, 20.0], 8)
        res = np.tile(np.arange(4, dtype=np.int32), 8)
        return t, res, np.full(32, 3.0)
    if case == "unsorted":
        # Out-of-order issue times force the lexsort path.
        n = 150
        t = rng.integers(0, 400, n).astype(np.float64)
        res = rng.integers(0, 6, n).astype(np.int32)
        return t, res, rng.integers(0, 15, n).astype(np.float64)
    if case == "zero_service":
        n = 50
        t = np.sort(rng.integers(0, 100, n)).astype(np.float64)
        return t, rng.integers(0, 3, n).astype(np.int32), np.zeros(n)
    if case == "long_segment":
        # One saturated bank, longer than the Pallas chunk: the scan carry
        # must propagate across grid steps.
        n = 1500
        t = np.sort(rng.integers(0, 2000, n)).astype(np.float64)
        return t, np.zeros(n, np.int32), rng.integers(1, 9, n).astype(np.float64)
    raise AssertionError(case)


CASES = ("empty", "single", "gapped_banks", "single_event_segments",
         "ties", "unsorted", "zero_service", "long_segment")


def _batch_inputs(t, res, svc, R=3):
    """R pricings of one stream: scaled services, permuted bank ids."""
    n = t.size
    resource = np.stack([(res + 11 * r) % max(64, res.max(initial=0) + 1)
                         for r in range(R)]).astype(np.int32)
    service = np.stack([svc * (r + 1) for r in range(R)])
    kind = (np.arange(n) % 5).astype(np.int8)
    return t, resource, service, kind


# ---------------------------------------------------------------------------
# Layer 1: oracle


@pytest.mark.parametrize("case", CASES)
def test_numpy_matches_fifo_oracle(case):
    t, res, svc = _trace(case)
    kind = np.zeros(t.size, np.int8)
    s = replay_schedule(t, res, svc, kind, backend="numpy")
    order, start, finish, depth = fifo_oracle(t, res, svc)
    np.testing.assert_array_equal(s.order, order)
    np.testing.assert_array_equal(s.start_ns, start)
    np.testing.assert_array_equal(s.finish_ns, finish)
    np.testing.assert_array_equal(s.wait_ns, start - t[order])
    np.testing.assert_array_equal(s.queue_depth, depth)


@pytest.mark.parametrize("case", CASES)
def test_batch_numpy_matches_oracle_per_row(case):
    t, res, svc = _trace(case)
    t, resource, service, kind = _batch_inputs(t, res, svc)
    b = replay_schedule_batch(t, resource, service, kind, backend="numpy")
    for r in range(resource.shape[0]):
        order, start, finish, depth = fifo_oracle(t, resource[r], service[r])
        np.testing.assert_array_equal(b.order[r], order)
        np.testing.assert_array_equal(b.finish_ns[r], finish)
        np.testing.assert_array_equal(b.queue_depth[r], depth)


# ---------------------------------------------------------------------------
# Layer 2: backend trio, bitwise


@needs_jax
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_trio_bitwise_1d(case, backend):
    t, res, svc = _trace(case)
    kind = np.zeros(t.size, np.int8)
    ref = replay_schedule(t, res, svc, kind, backend="numpy")
    got = replay_schedule(t, res, svc, kind, backend=backend)
    _assert_sched_equal(ref, got, ctx=f"{case}/{backend}/")


@needs_jax
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_backend_trio_bitwise_batch(case, backend):
    t, res, svc = _trace(case)
    t, resource, service, kind = _batch_inputs(t, res, svc)
    ref = replay_schedule_batch(t, resource, service, kind, backend="numpy")
    got = replay_schedule_batch(t, resource, service, kind, backend=backend)
    _assert_sched_equal(ref, got, ctx=f"{case}/{backend}/")


@needs_jax
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_float_times_bitwise(backend):
    """Non-integer-valued data: the FMA-contraction regression pin.

    With random floats the products ``seg_id * big`` are inexact, so an FMA
    inside the jitted program (one rounding) differs from numpy's separate
    multiply+add (two roundings) in low bits.  The device programs must
    contain no multiply for this to hold bitwise.
    """
    rng = np.random.default_rng(42)
    n = 800
    t = np.sort(rng.uniform(0.0, 1e6, n))
    res = rng.integers(0, 12, n).astype(np.int32)
    svc = rng.uniform(0.5, 300.0, n)
    t, resource, service, kind = _batch_inputs(t, res, svc)
    service = service * math.pi / 3  # keep values non-integer after scaling
    ref = replay_schedule_batch(t, resource, service, kind, backend="numpy")
    got = replay_schedule_batch(t, resource, service, kind, backend=backend)
    _assert_sched_equal(ref, got, ctx=f"float/{backend}/")


@needs_jax
@pytest.mark.parametrize("chunk", [128, 256, 1024])
def test_cummax_matches_numpy(chunk):
    """Device cummax == ``np.maximum.accumulate`` bitwise, across chunkings."""
    from repro.kernels.segmented_replay.ops import cummax

    rng = np.random.default_rng(7)
    x = rng.uniform(-1e9, 1e9, (4, 1000))
    x[0, :10] = -np.inf  # the kernel's own padding/carry identity value
    ref = np.maximum.accumulate(x, axis=1)
    for scan in ("pallas", "lax"):
        got = cummax(x, scan=scan, chunk=chunk)
        np.testing.assert_array_equal(got, ref, err_msg=f"{scan}/chunk={chunk}")


@needs_jax
@pytest.mark.parametrize("R, n", [
    (2, 5000),  # pads to 8192 (> the 4096 floor)
    (2, 8192),  # a power of two above the floor: nothing is padded
    (3, 300),   # below the floor: pads to 4096
    (2, 1),     # one event a row
])
@pytest.mark.parametrize("t0", [0.0, -1e9])
def test_replay_scan_padding_is_neutral(t0, R, n):
    """Pow-2 padding must not perturb any real output, bitwise.

    ``t0 = -1e9`` puts every issue time, and so every key the device
    orders, below zero: the word map's negative branch.
    """
    from repro.kernels.segmented_replay.ops import replay_scan

    rng = np.random.default_rng(11)
    t = t0 + np.sort(rng.uniform(0, 1e5, (R, n)), axis=1)
    svc = rng.uniform(1, 50, (R, n))
    seg_id = np.sort(rng.integers(0, 40, (R, n)), axis=1).astype(np.float64)
    cs = np.cumsum(svc, axis=1)
    new_seg = np.ones((R, n), bool)
    new_seg[:, 1:] = seg_id[:, 1:] != seg_id[:, :-1]
    seg_base = np.maximum.accumulate(np.where(new_seg, cs - svc, -np.inf), axis=1)
    s_local = cs - seg_base
    v = t - (s_local - svc)
    big = (v.max(axis=1) - v.min(axis=1)) + 1.0
    ref = replay_scan_np(v, seg_id, s_local, svc, t, big)
    for scan in ("lax", "pallas"):
        got = replay_scan(v, seg_id, s_local, svc, t, big, scan=scan)
        for name, a, b in zip(("finish", "start", "wait", "depth"), ref, got):
            assert b.shape == (R, n) and b.dtype == a.dtype
            np.testing.assert_array_equal(a, b, err_msg=f"{scan}/{name}")


WORD_CASES = ("random_bits", "subnormal", "max_finite", "inf", "equal_hi",
              "pad_tail")


def _word_case(case, R, n, rng):
    """float64 ``(R, n)`` values for the key-word map: no NaN, no ``-0.0``."""
    if case == "random_bits":  # every IEEE pattern of both signs
        bits = rng.integers(-(2**63), 2**63, (R, n), dtype=np.int64)
    elif case == "subnormal":  # magnitudes below the smallest normal
        bits = rng.integers(1, 2**52, (R, n), dtype=np.int64)
        bits[:, ::7] = 2**52  # the smallest normal
    elif case == "equal_hi":  # one high word per sign, every low word
        bits = (np.int64(0x4237_4876) << 32) | rng.integers(0, 2**32, (R, n))
    else:
        bits = None
    if bits is not None:
        x = bits.view(np.float64)
        if case != "random_bits":
            x = np.where(rng.random((R, n)) < 0.5, -x, x)
    elif case == "max_finite":
        top = np.finfo(np.float64).max
        x = rng.choice([top, np.nextafter(top, 0), 1.0, 0.0, -1.0,
                        -np.nextafter(top, 0), -top], (R, n))
    elif case == "inf":
        x = rng.normal(0.0, 1e11, (R, n))
        x[rng.random((R, n)) < 0.2] = np.inf
        x[rng.random((R, n)) < 0.2] = -np.inf
    elif case == "pad_tail":  # replay keys, then replay_scan's pad tail
        from repro.kernels.segmented_replay.ops import PAD_KEY

        real = n - n // 3
        t = np.sort(rng.uniform(0, 1e5, (R, real)), axis=1)
        seg = np.sort(rng.integers(0, 20, (R, real)), axis=1).astype(np.float64)
        v = t - rng.uniform(1, 50, (R, real))
        x = np.full((R, n), PAD_KEY)
        x[:, :real] = v + seg * ((v.max(axis=1) - v.min(axis=1)) + 1.0)[:, None]
    else:
        raise AssertionError(case)
    bad = np.isnan(x) | ((x == 0) & np.signbit(x))
    return np.where(bad, 1.0, x)


@needs_jax
@pytest.mark.parametrize("case", WORD_CASES)
def test_key_word_map_matches_reference(case):
    """The device's key-word map is ``ref._to_pair`` / ``ref._from_pair``.

    The map alone, jitted, word for word; then the two program entries that
    wrap it: a running max of unsorted rows, one of sorted rows (which must
    give the input bits back), and the depth search of sorted rows.
    """
    import jax

    from repro.device import x64
    from repro.kernels.segmented_replay.ops import (
        _floats,
        _key_words,
        _replay_cummax,
        _replay_search,
        _words,
    )
    from repro.kernels.segmented_replay.ref import _from_pair, _to_pair

    R, n = 3, 4096
    x = _word_case(case, R, n, np.random.default_rng(WORD_CASES.index(case)))
    bits = x.view(np.int64)
    hi, lo = _to_pair(x)
    with x64():
        got_hi, got_lo = jax.jit(lambda b: _key_words(*_words(b)))(bits)
        np.testing.assert_array_equal(got_hi, hi)
        np.testing.assert_array_equal(got_lo, lo)
        back = _floats(*jax.jit(_key_words)(hi, lo)).view(np.int64)
        np.testing.assert_array_equal(back, _from_pair(hi, lo).view(np.int64))
        np.testing.assert_array_equal(back, bits)

        srt = np.sort(x, axis=1)
        for rows, want in ((x, np.maximum.accumulate(x, axis=1)), (srt, srt)):
            got = _floats(*_replay_cummax(rows.view(np.int64), scan="lax",
                                          chunk=1024, interpret=True))
            np.testing.assert_array_equal(got.view(np.int64), want.view(np.int64))
        q = np.sort(np.random.default_rng(1).permutation(x.ravel())[:R * n]
                    .reshape(R, n), axis=1)
        got = _replay_search(srt.view(np.int64), q.view(np.int64))
    want = np.stack([np.searchsorted(srt[r], q[r], side="left") for r in range(R)])
    np.testing.assert_array_equal(got, want)


MERGE_CASES = ("ties", "long_runs", "equal_hi", "extreme_words",
               "negative_floats", "pad_tail")


def _merge_rank_case(case, R, n, rng):
    """Sorted ``(a, q)`` rows for the depth search: float64 or int64 keys."""
    def rows(draw):
        return np.sort(draw((R, n)), axis=1)

    if case == "ties":  # every q equal to some a, and runs of both
        a = rows(lambda s: rng.integers(0, n // 4, s)).astype(np.int64)
        return a, np.sort(np.take_along_axis(a, rng.integers(0, n, (R, n)), 1), 1)
    if case == "long_runs":  # three distinct keys in all
        return (rows(lambda s: rng.integers(0, 3, s)),
                rows(lambda s: rng.integers(0, 3, s)))
    if case == "equal_hi":  # one hi word; lo over its whole range
        lo = lambda s: rng.integers(0, 2**32, s)  # noqa: E731
        return (7 << 32) + rows(lo), (7 << 32) + rows(lo)
    if case == "extreme_words":  # hi and lo words at PAIR_MIN and int32 max
        words = np.array([0, 1, 2**32 - 2, 2**32 - 1], np.int64)
        keys = np.sort(np.concatenate([
            (-(2**31) << 32) + words, (5 << 32) + words, ((2**31 - 1) << 32) + words]))
        return (rows(lambda s: rng.choice(keys, s)),
                rows(lambda s: rng.choice(keys, s)))
    if case == "negative_floats":
        return (rows(lambda s: -rng.exponential(1e6, s)),
                rows(lambda s: -rng.exponential(1e6, s)))
    if case == "pad_tail":  # replay_scan's tail: PAD_KEY above every real lane
        from repro.kernels.segmented_replay.ops import PAD_KEY

        a, q = rows(lambda s: rng.uniform(0, 1e5, s)), rows(lambda s: rng.uniform(0, 1e5, s))
        real = n - n // 3
        a[:, real:] = q[:, real:] = PAD_KEY
        return a, q
    raise AssertionError(case)


def _merge_rank_check(a, q):
    from repro.kernels.segmented_replay.ops import _merge_rank
    from repro.kernels.segmented_replay.ref import _split, _to_pair

    words = _to_pair if a.dtype == np.float64 else _split
    got = np.asarray(_merge_rank(*words(a), *words(q)))
    ref = np.stack([np.searchsorted(a[r], q[r], side="left") for r in range(a.shape[0])])
    np.testing.assert_array_equal(got, ref)


@needs_jax
@pytest.mark.parametrize("n", [4096, 8192])
@pytest.mark.parametrize("R", [1, 4, 5])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_merge_rank_matches_searchsorted(case, R, n):
    """The device depth search is ``searchsorted(side="left")`` per row."""
    rng = np.random.default_rng([MERGE_CASES.index(case), R, n])
    _merge_rank_check(*_merge_rank_case(case, R, n, rng))


@needs_jax
@settings(max_examples=10, deadline=None)
@given(
    R=st.integers(min_value=1, max_value=5),
    seed=st.integers(min_value=0, max_value=2**20),
    distinct=st.integers(min_value=1, max_value=5000),
)
def test_property_merge_rank(R, seed, distinct):
    """Sorted float64 rows drawn from ``distinct`` values, shared by a and q."""
    rng = np.random.default_rng(seed)
    values = rng.normal(0.0, 1e4, distinct)
    a, q = (np.sort(rng.choice(values, (R, 4096)), axis=1) for _ in range(2))
    _merge_rank_check(a, q)


# ---------------------------------------------------------------------------
# Layer 3: batch vs per-row, per backend


@pytest.mark.parametrize("case", CASES)
def test_batch_matches_per_row(case):
    backends = ["numpy"] + (["jax", "pallas"] if HAVE_JAX else [])
    t, res, svc = _trace(case)
    t, resource, service, kind = _batch_inputs(t, res, svc)
    for backend in backends:
        b = replay_schedule_batch(t, resource, service, kind, backend=backend)
        for r in range(resource.shape[0]):
            one = replay_schedule(t, resource[r], service[r], kind,
                                  backend=backend)
            _assert_sched_equal(one, b.row(r), ctx=f"{case}/{backend}/row{r}/")


# ---------------------------------------------------------------------------
# Property sweep (hypothesis when installed, seeded shim otherwise)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=300),
    n_res=st.integers(min_value=1, max_value=24),
    seed=st.integers(min_value=0, max_value=2**20),
    sorted_t=st.sampled_from([True, False]),
)
def test_property_oracle_and_jax(n, n_res, seed, sorted_t):
    rng = np.random.default_rng(seed)
    t = rng.integers(0, 4 * n, n).astype(np.float64)
    if sorted_t:
        t.sort()
    res = rng.integers(0, n_res, n).astype(np.int32)
    svc = rng.integers(0, 25, n).astype(np.float64)
    kind = np.zeros(n, np.int8)
    s = replay_schedule(t, res, svc, kind, backend="numpy")
    order, start, finish, depth = fifo_oracle(t, res, svc)
    np.testing.assert_array_equal(s.finish_ns, finish)
    np.testing.assert_array_equal(s.queue_depth, depth)
    if HAVE_JAX:
        tb, resource, service, kb = _batch_inputs(t, res, svc, R=2)
        ref = replay_schedule_batch(tb, resource, service, kb, backend="numpy")
        got = replay_schedule_batch(tb, resource, service, kb, backend="jax")
        _assert_sched_equal(ref, got, ctx="property/")


# ---------------------------------------------------------------------------
# Backend-name validation


def test_unknown_backend_suggests_near_miss():
    with pytest.raises(UnknownBackendError, match=r"did you mean 'numpy'\?"):
        SimConfig(backend="nunpy")
    with pytest.raises(UnknownBackendError, match="available: numpy, jax, pallas"):
        replay_schedule(np.empty(0), np.empty(0, np.int32), np.empty(0),
                        np.empty(0, np.int8), backend="cuda")
    with pytest.raises(UnknownBackendError):
        replay_schedule_batch(np.empty(0), np.empty((1, 0), np.int32),
                              np.empty((1, 0)), np.empty(0, np.int8),
                              backend="pallsa")


def test_auto_backend_resolves():
    resolved = resolve_backend("auto")
    assert resolved in BACKENDS
    if HAVE_JAX:
        import jax

        expect = "jax" if jax.default_backend() != "cpu" else "numpy"
    else:
        expect = "numpy"
    assert resolved == expect
    assert SimConfig(backend="auto").backend == resolved
