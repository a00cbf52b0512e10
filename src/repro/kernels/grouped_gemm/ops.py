"""Public wrapper of the ragged grouped expert FFN, with the contract of
``jax.lax.ragged_dot``: ``xs`` (P, D) rows sorted by group, ``sizes``
(G,) rows per group of the weight stacks, groups read in place. The
output is ``xs.dtype``, accumulated in f32. Its VJP is the VJP of the
same FFN as three ``ragged_dot`` products, so a differentiated caller
keeps its gradients."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grouped_gemm.grouped_gemm import ragged_ffn_kernel
from repro.kernels.grouped_gemm.ref import ragged_swiglu_ref

#: VMEM bytes the streamed weight blocks may take, both pipeline buffers
#: together. A v5e core has 128 MiB of VMEM; this leaves room for the
#: rows, the output and the compiler's own scratch.
WEIGHT_VMEM_BYTES = 48 << 20


def schedule(sizes, steps: int):
    """The non-empty groups, in order, padded to ``steps`` entries:
    (group id, first row, rows, [number non-empty]). A padded entry
    repeats the last non-empty group's id with 0 rows."""
    live = sizes > 0
    n = jnp.sum(live, dtype=jnp.int32)
    gid = jnp.nonzero(live, size=steps, fill_value=0)[0].astype(jnp.int32)
    real = jnp.arange(steps) < n
    gid = jnp.where(real, gid, gid[jnp.maximum(n - 1, 0)])
    starts = (jnp.cumsum(sizes) - sizes).astype(jnp.int32)
    return (gid, starts[gid], jnp.where(real, sizes[gid], 0).astype(jnp.int32),
            n[None])


def _tile_width(F: int, per_col: int) -> int:
    """The widest tile of the ``F`` columns, a multiple of 128 dividing
    ``F`` (or ``F`` itself), whose two pipeline copies of ``per_col``
    bytes a column fit in :data:`WEIGHT_VMEM_BYTES`."""
    if 2 * F * per_col <= WEIGHT_VMEM_BYTES or F % 128:
        return F
    fits = [t for t in range(128, F, 128)
            if F % t == 0 and 2 * t * per_col <= WEIGHT_VMEM_BYTES]
    return max(fits, default=128)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ffn(xs, wg, wu, wd, sizes, interpret):
    P, D = xs.shape
    G, _, F = wg.shape
    pad = (-P) % (32 // xs.dtype.itemsize)          # the sublane tile
    # each non-empty group holds a row: at most min(P, G) of them
    sched = schedule(sizes, max(1, min(P, G)))
    out = ragged_ffn_kernel(
        sched, jnp.pad(xs, ((0, pad), (0, 0))), wg, wu, wd,
        tf=_tile_width(F, 3 * D * wg.dtype.itemsize), interpret=interpret)
    return out[:P]


def _ffn_fwd(xs, wg, wu, wd, sizes, interpret):
    return _ffn(xs, wg, wu, wd, sizes, interpret), (xs, wg, wu, wd, sizes)


def _ffn_bwd(interpret, res, g):
    *arrays, sizes = res
    _, vjp = jax.vjp(lambda *a: ragged_swiglu_ref(*a, sizes), *arrays)
    return (*vjp(g), None)


_ffn.defvjp(_ffn_fwd, _ffn_bwd)


def ragged_grouped_ffn(xs, wg, wu, wd, sizes, *, interpret: bool = False):
    """The SwiGLU expert FFN over ragged groups in one kernel:
    ``(silu(xs @ wg[g]) * (xs @ wu[g])) @ wd[g]`` for the rows of each
    group g; wg, wu (G, D, F), wd (G, F, D) -> (P, D); rows past
    ``sum(sizes)`` are zero. Only the non-empty groups' weights are read,
    each group's three blocks once; the gate and up products stay in VMEM
    in f32 until the down product."""
    return _ffn(xs, wg, wu, wd, sizes, interpret)
