"""jit'd public wrapper for the RG-LRU scan kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rglru_scan.rglru_scan import rglru_scan_kernel


@functools.partial(jax.jit, static_argnames=("block_w", "chunk", "interpret"))
def rglru_scan(a, b, h0, *, block_w: int = 512, chunk: int = 128,
               interpret: bool = False):
    """a/b: (B,S,W); h0: (B,W) -> (h_all (B,S,W) f32, h_last (B,W) f32)."""
    B, S, W = a.shape
    bw = min(block_w, W)
    while W % bw:
        bw //= 2
    c = min(chunk, S)
    while S % c:
        c //= 2
    f32 = lambda t: t.astype(jnp.float32)
    h_all, h_last = rglru_scan_kernel(f32(a), f32(b), f32(h0)[:, None],
                                      block_w=bw, chunk=c,
                                      interpret=interpret)
    return h_all, h_last[:, 0]
