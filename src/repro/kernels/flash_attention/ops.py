"""jit'd public wrapper: pads to block multiples, dispatches the kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_kernel)


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B,S,H,hd); k/v: (B,T,K,hd) -> (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, T))
    # the kernel is head-major: (B, H, S, hd) keeps (block, hd) as the
    # last two dims of every block
    head_major = lambda x: jnp.swapaxes(x, 1, 2)      # noqa: E731
    qp = _pad_to(head_major(q), 2, bq)
    kp = _pad_to(head_major(k), 2, bk)
    vp = _pad_to(head_major(v), 2, bk)
    out = flash_attention_kernel(
        qp, kp, vp, causal=causal, window=window, softcap=softcap,
        kv_len=T, block_q=bq, block_k=bk, interpret=interpret)
    return head_major(out[:, :, :S])
