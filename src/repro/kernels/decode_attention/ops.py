"""jit'd public wrapper for decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "block_k", "interpret"))
def decode_attention(q, k, v, *, pos, window: int, layer=None,
                     softcap: float = 0.0, block_k: int = 128,
                     interpret: bool = False):
    """q: (B,H,hd); k/v: (B,W,K,hd), or with ``layer`` a stack of layers'
    (n,B,W,K,hd) read at layer ``layer`` in place; pos scalar i32 ->
    (B,H,hd).

    ``window`` is the ring length W (slots wrap at W); padding of W to the
    k-block size is masked via slot validity (padded slots > pos, and the
    ring-full override only applies to real slots < W).
    """
    if layer is None:
        k, v, layer = k[None], v[None], 0
    B, H, hd = q.shape
    W, K = k.shape[2], k.shape[3]
    G = H // K
    bk = min(block_k, max(8, W))
    pad = (-W) % bk
    # head-major cache view (n, B, K, W, hd): (block, hd) are the last two
    # dims of every k/v block
    k, v = jnp.swapaxes(k, 2, 3), jnp.swapaxes(v, 2, 3)
    if pad:
        # padding writes a copy: of the one layer read, not of the stack
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        k, v = (jnp.pad(jax.lax.dynamic_slice_in_dim(c, layer, 1), widths)
                for c in (k, v))
        layer = 0
    qg = q.reshape(B, K, G, hd)
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32),
                         jnp.asarray(layer, jnp.int32)])
    out = decode_attention_kernel(qg, k, v, scalars, softcap=softcap,
                                  block_k=bk, W=window, interpret=interpret)
    return out.reshape(B, H, hd)
