"""RG-LRU diagonal linear recurrence — Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the LRU width. Same VMEM-resident
state pattern as ssm_scan: channel dim blocked+parallel, time chunked and
sequential, state (bw,) persists in scratch across the chunk grid dimension.
Pure VPU (elementwise) work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, y_ref, h_last_ref, h_s, *,
            chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_s[...] = h0_ref[...]

    def step(t, h):
        h = a_ref[0, t, :] * h + b_ref[0, t, :]   # h: (1, bw)
        y_ref[0, t, :] = h[0]
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_s[...])
    h_s[...] = h

    @pl.when(ic == nc - 1)
    def _final():
        h_last_ref[...] = h


def rglru_scan_kernel(a, b, h0, *, block_w: int, chunk: int,
                      interpret: bool = False):
    """a/b: (B,S,W) f32; h0: (B,1,W) f32 -> (h_all (B,S,W), h_last (B,1,W)).

    The state rides as (B, 1, W) so its blocks are (1, bw): a unit dim
    equal to the array's, which the TPU tiling accepts for any batch."""
    B, S, W = a.shape
    nw, nc = W // block_w, S // chunk
    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, w, c: (b_, c, w)),
            pl.BlockSpec((1, chunk, block_w), lambda b_, w, c: (b_, c, w)),
            pl.BlockSpec((None, 1, block_w), lambda b_, w, c: (b_, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, w, c: (b_, c, w)),
            pl.BlockSpec((None, 1, block_w), lambda b_, w, c: (b_, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
