"""Single-token KV-cache attention (flash-decode) — Pallas TPU kernel.

One new query token per sequence attends over a ring cache of length W.
Grid: (batch, kv_head, k_blocks); the k-block dimension is sequential and
accumulates the online softmax in VMEM scratch. All G = H/K query heads of
one kv head are processed together so the score matmul is (G x hd)·(hd x bk)
— MXU work instead of a matvec. The cache is read head-major (B, K, W, hd)
so each k/v block's last two dims are (block, hd), with the batch and kv-head
axes squeezed: the tiling the TPU compiler accepts for any kv-head count.

The current position ``pos`` arrives via SMEM (scalar memory), mirroring how
a CSR would parameterize a ZynqParrot hardware timer: the kernel masks ring
slots that are not yet valid (slot > pos while the ring is not full).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s, *,
            bk: int, nk: int, softcap: float, scale: float, W: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[0]
    q = q_ref[...]                                   # (G, hd)
    k = k_ref[...]                                   # (bk, hd)
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    slots = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ring_full = pos + 1 >= W
    # padded slots (>= W) are never valid; real slots follow ring semantics
    valid = (slots < W) & jnp.logical_or(slots <= pos, ring_full)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[...] = (acc[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, pos_arr, *, softcap: float,
                            block_k: int, W: int, interpret: bool = False):
    """q: (B, K, G, hd); k/v: (B, K, Wp, hd); pos_arr: (1,) i32."""
    B, K, G, hd = q.shape
    Wp = k.shape[2]
    nk = Wp // block_k
    kernel = functools.partial(_kernel, bk=block_k, nk=nk, softcap=softcap,
                               scale=hd ** -0.5, W=W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nk),
        in_specs=[
            pl.BlockSpec((None, None, G, hd),
                         lambda b, kv, ik, pos: (b, kv, 0, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, kv, ik, pos: (b, kv, ik, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, kv, ik, pos: (b, kv, ik, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, kv, ik, pos: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos_arr, q, k, v)
