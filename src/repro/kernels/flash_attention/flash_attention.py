"""Flash-attention forward kernel (Pallas TPU).

Grid: (batch, q_head, q_blocks, kv_blocks) with the kv axis innermost and
sequential ("arbitrary") so the fp32 (m, l, acc) scratch accumulators carry
the online softmax across kv tiles; output is written on the last kv step.

GQA is handled in the K/V BlockSpec index maps (``head // group``), so K/V
tiles are fetched once per kv-head — the kernel never materialises repeated
KV heads.  Supports causal masking, sliding-window ("local") masking and
gemma2/grok-style logit softcap.

VMEM working set per step: q(bq,hd) + k/v(bkv,hd) + scores(bq,bkv)f32 +
acc(bq,hd)f32 — block sizes are chosen by ``core.vmem_planner`` to fit the
budget, mirroring the paper's GLB sizing loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.3819763e38


def _fa_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    softcap: float | None,
    block_q: int,
    block_kv: int,
    kv_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    v = v_ref[0, 0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bkv)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = k_pos < kv_len  # tail padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
        p.astype(v.dtype), v[...], preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, ...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, ...] = m_scr[...] + jnp.log(l)


def flash_attention_fwd(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, KV, T, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    group = H // KV
    scale = hd ** -0.5

    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    Sp = -(-S // block_q) * block_q
    Tp = -(-T // block_kv) * block_kv
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    nq, nk = Sp // block_q, Tp // block_kv

    kernel = functools.partial(
        _fa_fwd_kernel,
        scale=scale,
        causal=causal,
        window=window,
        softcap=softcap,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=T,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    out, lse = out
    return out[:, :, :S], lse[:, :, :S, 0]
