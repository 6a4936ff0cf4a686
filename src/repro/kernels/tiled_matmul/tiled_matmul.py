"""Weight-stationary tiled GEMM kernel (Pallas TPU).

This is the paper's co-design loop made executable on TPU: the (bm, bk, bn)
BlockSpec tile shapes are produced by ``core.vmem_planner.plan_matmul_tiles``
— enumerate tilings, keep those whose working set fits the VMEM budget (the
GLB capacity constraint of Algorithm 1/2), and pick the one maximising
operational intensity (the bandwidth constraint of Eq. 1/6).

Grid: (M/bm, N/bn, K/bk) with K innermost/sequential; an fp32 accumulator
scratch carries partial sums across K tiles (the "partial ofmap" of the
paper's row-stationary analysis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _mm_kernel(a_ref, b_ref, o_ref, acc, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(ki == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def tiled_matmul_fwd(
    a: jax.Array,  # (M, K)
    b: jax.Array,  # (K, N)
    *,
    bm: int,
    bk: int,
    bn: int,
    interpret: bool = False,
) -> jax.Array:
    M, K = a.shape
    N = b.shape[1]
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    Mp, Kp, Np = (-(-d // t) * t for d, t in ((M, bm), (K, bk), (N, bn)))
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
