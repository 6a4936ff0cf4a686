"""Mamba-2 SSD chunked-scan kernel (Pallas TPU).

Grid: (batch, head, chunks) with the chunk axis innermost and sequential —
the fp32 (P, N) state scratch carries the inter-chunk recurrence, exactly
the structure of the SSD algorithm (arXiv:2405.21060 §6): per chunk a dense
(Q,Q) decay-masked attention-like product handles intra-chunk terms on the
MXU, and the state adds the inter-chunk contribution.

VMEM per step: x(Q,P) + B,C(Q,N) + scores(Q,Q)f32 + state(P,N)f32 — a few
hundred KB at Q=128..256, far under budget; Q is the paper's "GLB tile".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(
    x_ref,  # (1, Q, 1, P)
    dt_ref,  # (1, Q, 1)
    a_ref,  # (1,)
    b_ref,  # (1, Q, 1, N)
    c_ref,  # (1, Q, 1, N)
    y_ref,  # (1, Q, 1, P)
    st_ref,  # (1, 1, P, N) final-state output
    state,  # scratch (P, N) f32
    *,
    chunk: int,
):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    B_ = b_ref[0, :, 0, :].astype(jnp.float32)  # (Q, N)
    C_ = c_ref[0, :, 0, :].astype(jnp.float32)  # (Q, N)
    a = -jnp.exp(a_ref[0].astype(jnp.float32))  # scalar decay rate

    dA = dt * a  # (Q,)
    seg = jnp.cumsum(dA)  # (Q,)
    rel = seg[:, None] - seg[None, :]
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    )
    # mask before exp (above-diagonal rel > 0 would overflow)
    L = jnp.exp(jnp.where(causal, rel, -jnp.inf))
    scores = jax.lax.dot_general(
        C_, B_, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    xdt = x * dt[:, None]
    y_intra = jax.lax.dot(scores * L, xdt, preferred_element_type=jnp.float32)
    # inter-chunk: y += exp(seg) * C @ state^T
    y_inter = (
        jax.lax.dot_general(
            C_, state[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * jnp.exp(seg)[:, None]
    )
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    total = seg[-1]
    decay_to_end = jnp.exp(total - seg)  # (Q,)
    upd = jax.lax.dot_general(
        xdt * decay_to_end[:, None], B_, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (P, N)
    state[...] = jnp.exp(total) * state[...] + upd

    @pl.when(ci == nc - 1)
    def _done():
        st_ref[0, 0, ...] = state[...]


def ssd_scan_fwd(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    a_log: jax.Array,  # (H,)
    B_: jax.Array,  # (B, S, G, N)
    C_: jax.Array,  # (B, S, G, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    Bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    Q = min(chunk, S)
    Sp = -(-S // Q) * Q
    if Sp != S:  # dt=0 pads are exact no-ops for the recurrence
        x = jnp.pad(x, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, Sp - S), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    nc = Sp // Q

    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y, st = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, Q, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, Q, 1, N), lambda b, h, c: (b, c, h // rep, 0)),
            pl.BlockSpec((1, Q, 1, N), lambda b, h, c: (b, c, h // rep, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, Sp, H, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, dt, a_log, B_, C_)
    return y[:, :S], st
