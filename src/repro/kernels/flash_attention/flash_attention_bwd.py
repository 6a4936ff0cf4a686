"""Flash-attention backward kernels (Pallas TPU) — FlashAttention-2 style.

Two kernels, both recomputing score tiles from (q, k, v, lse):
  * dq kernel:   grid (B, H, nq, nk), kv innermost; accumulates dq in a
                 VMEM scratch across kv tiles.
  * dkv kernel:  grid (B, H, nk, nq), q innermost; accumulates (dk, dv)
                 in VMEM scratch across q tiles.

Inputs are head-major: q (B,H,S,hd), k/v (B,KV,T,hd) with GQA handled in
the K/V index maps for the dq kernel; the dkv kernel runs per q-head and
the wrapper segment-sums group gradients back onto the KV heads.

Needs the forward's logsumexp (lse, (B,H,S)) and D = rowsum(dO * O).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38


def _mask(q0, k0, bq, bkv, T, causal, window):
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    m = k_pos < T
    if causal:
        m &= k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m


def _scores(q, k, scale, softcap):
    s_raw = (
        jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        * scale
    )
    if softcap is not None:
        return softcap * jnp.tanh(s_raw / softcap), s_raw
    return s_raw, s_raw


def _dsoftcap(ds, s_raw, softcap):
    if softcap is None:
        return ds
    t = jnp.tanh(s_raw / softcap)
    return ds * (1.0 - t * t)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref, acc,
    *, scale, causal, window, softcap, block_q, block_kv, kv_len,
):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]  # (bq, 1)
    dsum = dsum_ref[0, 0]  # (bq, 1)

    s, s_raw = _scores(q, k, scale, softcap)
    mask = _mask(qi * block_q, ki * block_kv, block_q, block_kv, kv_len, causal, window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - dsum)
    ds = jnp.where(mask, _dsoftcap(ds, s_raw, softcap), 0.0)
    acc[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0, ...] = acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, scale, causal, window, softcap, block_q, block_kv, kv_len,
):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    dsum = dsum_ref[0, 0]

    s, s_raw = _scores(q, k, scale, softcap)
    mask = _mask(qi * block_q, ki * block_kv, block_q, block_kv, kv_len, causal, window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - dsum)
    ds = jnp.where(mask, _dsoftcap(ds, s_raw, softcap), 0.0)
    dk_acc[...] += (
        jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        * scale
    )

    @pl.when(qi == nq - 1)
    def _done():
        dk_ref[0, 0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, ...] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, lse, dout,
    *, causal=True, window=None, softcap=None,
    block_q=512, block_kv=512, interpret=False,
):
    """q/out/dout: (B,H,S,hd); k,v: (B,KV,T,hd); lse: (B,H,S).
    Returns (dq, dk, dv) with dk/dv reduced onto the KV heads."""
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    group = H // KV
    scale = hd ** -0.5
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    Sp = -(-S // block_q) * block_q
    Tp = -(-T // block_kv) * block_kv

    dsum = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)  # (B,H,S)
    if Sp != S:
        pad4 = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
        q = jnp.pad(q, pad4)
        dout = jnp.pad(dout, pad4)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, Sp - S)), constant_values=1.0)
        dsum = jnp.pad(dsum, ((0, 0), (0, 0), (0, Sp - S)))
    if Tp != T:
        padkv = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        k = jnp.pad(k, padkv)
        v = jnp.pad(v, padkv)
    # expand KV heads for the dkv kernel (wrapper reduces groups after)
    ke = jnp.repeat(k, group, axis=1) if group > 1 else k
    ve = jnp.repeat(v, group, axis=1) if group > 1 else v
    lse_col = lse[..., None]  # (B,H,Sp,1)
    dsum_col = dsum[..., None]
    nq, nk = Sp // block_q, Tp // block_kv
    kw = dict(
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, kv_len=T,
    )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, dout, lse_col, dsum_col)

    dke, dve = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, hd), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tp, hd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, hd), jnp.float32),
            pltpu.VMEM((block_kv, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, ke, ve, dout, lse_col, dsum_col)

    dq = dq[:, :, :S]
    dke = dke[:, :, :T]
    dve = dve[:, :, :T]
    if group > 1:  # reduce expanded-head grads back onto KV heads
        dk = dke.reshape(B, KV, group, T, hd).sum(axis=2)
        dv = dve.reshape(B, KV, group, T, hd).sum(axis=2)
    else:
        dk, dv = dke, dve
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
