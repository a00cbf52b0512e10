"""Blocked GQA flash attention — Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the online-softmax accumulator lives in VMEM
scratch and persists across the sequential innermost grid dimension (the
k-block loop), so the S x T score matrix never exists in HBM. Block shapes
are MXU-aligned (bq = bk = 128 default; head_dim is the contraction dim).
Grid: (batch, q_head, q_blocks, k_blocks) — the first three are parallel,
the last is an "arbitrary" (sequential) accumulation dimension. Operands are
head-major (B, H, S, hd) so every block's last two dims are (block, hd):
the batch and head axes are squeezed block dims, which is the tiling the
TPU compiler accepts for any head count.

Causal and sliding-window masks are applied from global positions computed
off program_id; blocks that cannot contribute are skipped with pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            softcap: float, kv_len: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q_lo = iq * bq
    k_lo = ik * bk
    # can this k block contribute to this q block at all?
    contrib = k_lo < kv_len
    if causal:
        contrib &= k_lo <= q_lo + bq - 1
    if window > 0:
        contrib &= (k_lo + bk - 1) >= (q_lo - window + 1)

    @pl.when(contrib)
    def _step():
        q = q_ref[...]                              # (bq, hd)
        k = k_ref[...]                              # (bk, hd)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool, window: int,
                           softcap: float, kv_len: int,
                           block_q: int, block_k: int,
                           interpret: bool = False):
    """q: (B, H, Sp, hd); k/v: (B, K, Tp, hd). Sp/Tp pre-padded to blocks."""
    B, H, Sp, hd = q.shape
    K, Tp = k.shape[1], k.shape[2]
    G = H // K
    nq, nk = Sp // block_q, Tp // block_k
    scale = hd ** -0.5

    kernel = functools.partial(
        _kernel, bq=block_q, bk=block_k, nk=nk, causal=causal,
        window=window, softcap=softcap, kv_len=kv_len, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
