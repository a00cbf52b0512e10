"""Mamba-1 selective scan — Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the GPU mamba kernel streams the recurrence
through shared memory per thread-block; on TPU the natural mapping keeps the
(bd, N) state resident in VMEM scratch across the *sequential chunk grid
dimension*, streaming (chunk, bd) input tiles HBM->VMEM and writing (chunk,
bd) output tiles back. The channel dimension is blocked (bd) and parallel;
time is chunked and sequential — the state never round-trips to HBM.

Grid: (B, Din/bd, S/chunk), semantics (parallel, parallel, arbitrary).
All recurrence math in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dt_ref, A_ref, B_ref, C_ref, x_ref, y_ref, h_last_ref, h_s, *,
            chunk: int, nc: int, N: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_s[...] = jnp.zeros_like(h_s)

    A = A_ref[...]                      # (bd, N) f32

    def step(t, h):
        dt_t = dt_ref[0, t, :]          # (bd,)
        B_t = B_ref[0, t, :]            # (N,)
        C_t = C_ref[0, t, :]            # (N,)
        x_t = x_ref[0, t, :]            # (bd,)
        dA = jnp.exp(dt_t[:, None] * A)             # (bd, N)
        h = dA * h + (dt_t * x_t)[:, None] * B_t[None, :]
        # an f32 lane reduction, not an MXU dot: at default precision
        # the MXU rounds f32 operands to bf16
        y_ref[0, t, :] = jnp.sum(h * C_t[None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_s[...])
    h_s[...] = h

    @pl.when(ic == nc - 1)
    def _final():
        h_last_ref[0] = h


def ssm_scan_kernel(dt, A, B_, C_, x, *, block_d: int, chunk: int,
                    interpret: bool = False):
    """dt/x: (B,S,Din) f32; A: (Din,N) f32; B_/C_: (B,S,N) f32.
    Returns y (B,S,Din) f32, h_last (B,Din,N) f32."""
    B, S, Din = dt.shape
    N = A.shape[1]
    nd, nc = Din // block_d, S // chunk
    kernel = functools.partial(_kernel, chunk=chunk, nc=nc, N=N)
    return pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, block_d, N), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, Din), jnp.float32),
            jax.ShapeDtypeStruct((B, Din, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, A, B_, C_, x)
