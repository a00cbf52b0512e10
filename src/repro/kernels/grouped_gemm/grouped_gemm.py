"""Ragged grouped expert FFN: a Pallas TPU kernel for the dropless MoE
layer.

Rows ``xs`` (P, D) are sorted by group and ``sizes`` counts the rows of
each of the G groups of the weight stacks (G, D, F) and (G, F, D): the
contract of ``jax.lax.ragged_dot``. A decode step routes few rows, so most
groups are empty. The kernel walks a schedule of the non-empty groups
only, which the wrapper scalar-prefetches (``ops.schedule``): grid step i
reads group ``gid[i]``'s weight blocks straight out of the stacks by
their BlockSpec index, so no stack is sliced or copied, and the pipeline
fetches step i+1's blocks while step i computes. Steps past the last
non-empty group repeat its block index, so they issue no DMA, and a
``pl.when`` skips their compute. A step computes
``(silu(xs @ wg[g]) * (xs @ wu[g])) @ wd[g]`` on all rows, the gate and up
products in f32, the down product accumulated over F tiles in f32, and
writes its group's rows, under a row mask, into an output block that
stays in VMEM for the whole grid: every row belongs to one group.
Grid: (schedule steps, F tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(gid_ref, start_ref, count_ref, n_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, acc_ref, *, nt: int):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def write_rows(y):
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        start = start_ref[i]
        mine = (rows >= start) & (rows < start + count_ref[i])
        o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)

    @pl.when(i < n_ref[0])
    def _group():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
        y = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        if nt == 1:
            write_rows(y)
            return

        @pl.when(j == 0)
        def _first():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _more():
            acc_ref[...] += y

        @pl.when(j == nt - 1)
        def _last():
            write_rows(acc_ref[...])


def ragged_ffn_kernel(sched, xs, wg, wu, wd, *, tf: int,
                      interpret: bool = False):
    """sched: (gid, start, count, n) of ``ops.schedule``; xs: (P, D) rows
    sorted by group, P a multiple of the sublane tile; wg, wu: (G, D, F);
    wd: (G, F, D), F a multiple of ``tf`` -> (P, D)."""
    P, D = xs.shape
    F = wg.shape[2]
    nt = F // tf
    steps = sched[0].shape[0]

    def tile(i, j, n):
        # a padded step stays on the last real step's tile: same block
        return 0 if nt == 1 else jnp.where(i < n[0], j, nt - 1)

    up = pl.BlockSpec((1, D, tf), lambda i, j, g, s, c, n: (
        g[i], 0, tile(i, j, n)))
    down = pl.BlockSpec((1, tf, D), lambda i, j, g, s, c, n: (
        g[i], tile(i, j, n), 0))
    # three weight blocks, double-buffered: step i+1's in flight while
    # step i computes; then the rows, the output and the f32 accumulator
    vmem = 2 * 3 * D * tf * wg.dtype.itemsize + 4 * P * D * 4 + (8 << 20)
    return pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps, nt),
            in_specs=[pl.BlockSpec((P, D), lambda i, j, *_: (0, 0)),
                      up, up, down],
            out_specs=pl.BlockSpec((P, D), lambda i, j, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((P, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((P, D), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ragged_grouped_ffn",
    )(*sched, xs, wg, wu, wd)
