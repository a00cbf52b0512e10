"""Grouped (per-expert) matmul — Pallas TPU kernel for the MoE FFN.

The capacity-based dispatch (repro.models.moe) produces uniform (E, C, D)
expert batches, so the grouped GEMM is a batched matmul with an expert grid
dimension. Blocks are MXU-aligned; the contraction dimension is the
innermost sequential grid axis accumulating into an f32 VMEM scratch tile.
Grid: (experts, M-blocks, N-blocks, K-blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc, *, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def grouped_gemm_kernel(x, w, *, block_m: int, block_n: int, block_k: int,
                        interpret: bool = False):
    """x: (E, M, K) @ w: (E, K, N) -> (E, M, N), per-expert."""
    E, M, K = x.shape
    N = w.shape[2]
    nm, nn, nk = M // block_m, N // block_n, K // block_k
    kernel = functools.partial(_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(E, nm, nn, nk),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda e, im, in_, ik: (e, im, ik)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda e, im, in_, ik: (e, ik, in_)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda e, im, in_, ik: (e, im, in_)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
