"""jit'd public wrapper for decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "block_k", "interpret"))
def decode_attention(q, k, v, *, pos, window: int, softcap: float = 0.0,
                     block_k: int = 128, interpret: bool = False):
    """q: (B,H,hd); k/v: (B,W,K,hd); pos scalar i32 -> (B,H,hd).

    ``window`` is the ring length W (slots wrap at W); padding of W to the
    k-block size is masked via slot validity (padded slots > pos, and the
    ring-full override only applies to real slots < W).
    """
    B, H, hd = q.shape
    W, K = k.shape[1], k.shape[2]
    G = H // K
    bk = min(block_k, max(8, W))
    pad = (-W) % bk
    # head-major cache view (B, K, W, hd): (block, hd) are the last two
    # dims of every k/v block
    widths = ((0, 0), (0, 0), (0, pad), (0, 0))
    k = jnp.pad(jnp.swapaxes(k, 1, 2), widths)
    v = jnp.pad(jnp.swapaxes(v, 1, 2), widths)
    qg = q.reshape(B, K, G, hd)
    pos_arr = jnp.asarray([pos], jnp.int32)
    out = decode_attention_kernel(qg, k, v, pos_arr, softcap=softcap,
                                  block_k=bk, W=window, interpret=interpret)
    return out.reshape(B, H, hd)
