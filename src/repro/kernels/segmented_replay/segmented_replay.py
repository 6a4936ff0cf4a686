"""Segmented-replay cummax kernel (Pallas TPU).

The FIFO replay in ``repro.sim.engine`` reduces the per-bank recurrence to a
single running max over the offset-augmented array ``v + seg_id * big``
(``big`` separates bank segments so earlier banks can never win).  This
kernel computes that running max — a plain row-wise cummax — in the same
chunked associative-scan idiom as ``ssd_scan``: grid ``(row blocks,
chunks)`` with the chunk axis innermost and sequential, a VMEM carry holding
the inter-chunk running max, and a log2(Q) doubling-shift max-scan (lane
rolls) inside each chunk.

It scans integers, not floats.  The calling program maps each float64's
IEEE words to an order-preserving int32 ``hi``/``lo`` pair
(``ops._key_words``; Mosaic takes 32-bit operands), and the kernel takes
the lexicographic max of the pairs.
The comparisons are exact, so the output is bit-identical to
``np.maximum.accumulate`` for any chunk size — float64 on the TPU is
emulated and not IEEE-exact, so a float scan there would not be.

Rows are padded to the 8-row sublane tile and lanes to a multiple of the
chunk with the smallest pair (a max identity), so pads never leak into real
outputs.  Off-TPU the kernel runs in interpret mode; on a TPU it is
compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8  # sublane tile: row blocks are 8 rows high
LANES = 128  # lane tile: chunks are a multiple of 128 wide
PAIR_MIN = np.int32(np.iinfo(np.int32).min)  # the smallest pair's words


def lexmax(ah, al, bh, bl):
    """Elementwise max of the pairs ``(ah, al)`` and ``(bh, bl)``."""
    take_b = (bh > ah) | ((bh == ah) & (bl > al))
    return jnp.where(take_b, bh, ah), jnp.where(take_b, bl, al)


def _cummax_kernel(hi_ref, lo_ref, ohi_ref, olo_ref, chi, clo, *, chunk: int):
    @pl.when(pl.program_id(1) == 0)
    def _reset():
        chi[...] = jnp.full(chi.shape, PAIR_MIN, jnp.int32)
        clo[...] = jnp.full(clo.shape, PAIR_MIN, jnp.int32)

    h, l = hi_ref[...], lo_ref[...]  # (ROWS, Q)
    lane = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    # Doubling-shift max-scan: after step s, lane i holds max(x[i-2s+1 .. i]).
    s = 1
    while s < chunk:
        inside = lane >= s
        shift = np.int32(s)  # Mosaic's rotate takes a 32-bit shift
        h, l = lexmax(
            h, l,
            jnp.where(inside, pltpu.roll(h, shift, 1), PAIR_MIN),
            jnp.where(inside, pltpu.roll(l, shift, 1), PAIR_MIN),
        )
        s *= 2
    h, l = lexmax(h, l, chi[:, :1], clo[:, :1])  # earlier chunks of the row
    ohi_ref[...] = h
    olo_ref[...] = l
    # Rolling by one brings the last lane to lane 0: an aligned slice.
    one = np.int32(1)
    chi[...] = jnp.broadcast_to(pltpu.roll(h, one, 1)[:, :1], chi.shape)
    clo[...] = jnp.broadcast_to(pltpu.roll(l, one, 1)[:, :1], clo.shape)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def cummax_2d(
    hi: jax.Array, lo: jax.Array, *, chunk: int = 1024, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Row-wise running lexicographic max of int32 ``(hi, lo)`` pairs.

    ``chunk`` (a multiple of 128) is the in-block scan length.  Rows are
    padded to a multiple of 8 and lanes to a multiple of the chunk with the
    smallest pair; the pad is sliced off the outputs.
    """
    if chunk % LANES:
        raise ValueError(f"chunk must be a multiple of {LANES}; got {chunk}")
    R, n = hi.shape
    if n == 0:
        return hi, lo
    Q = min(chunk, -(-n // LANES) * LANES)
    rpad, npad = -(-R // ROWS) * ROWS, -(-n // Q) * Q
    pad = ((0, rpad - R), (0, npad - n))
    hi = jnp.pad(hi, pad, constant_values=PAIR_MIN)
    lo = jnp.pad(lo, pad, constant_values=PAIR_MIN)
    block = pl.BlockSpec((ROWS, Q), lambda r, c: (r, c))
    out_hi, out_lo = pl.pallas_call(
        functools.partial(_cummax_kernel, chunk=Q),
        grid=(rpad // ROWS, npad // Q),
        in_specs=[block, block],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct((rpad, npad), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((ROWS, LANES), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="replay_cummax",
    )(hi, lo)
    return out_hi[:R, :n], out_lo[:R, :n]
