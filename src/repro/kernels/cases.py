"""The five Pallas kernels at published model widths: one table for the
ahead-of-time compile test (``tests/test_tpu_compile.py``) and the on-chip
agreement check (``chip_smoke.py``), so both always see the same shapes.

Widths: granite-8b attention (32 query heads, 8 kv heads, head_dim 128,
2048 positions, a 4096-slot decode cache), the qwen3-30b-a3b expert FFN
of a decode step (8 tokens x top-8 = 64 rows over 52 of layer 1's 128
experts of 2048 x 768, read in place from a 2-layer stack of 256), the
falcon-mamba-7b selective scan
(d_inner 8192, state 16) and the recurrentgemma-2b RG-LRU (width 2560).
``small=True`` shrinks every dim for a CPU rehearsal in interpret mode.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.grouped_gemm.ops import ragged_grouped_ffn
from repro.kernels.grouped_gemm.ref import ragged_swiglu_ref
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _route(draw, E: int, rows: int, touched: int):
    """Group sizes over a 2-layer stack of ``E`` experts a layer: ``rows``
    rows dealt round-robin over ``touched`` experts of layer 1, picked by
    the order of ``draw``."""
    experts = E + jnp.argsort(draw[E:2 * E])[:touched]
    return jnp.zeros(draw.shape, I32).at[
        experts[jnp.arange(rows) % touched]].add(1)


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """``call(*operands, interpret=...)`` runs the kernel, ``ref(*operands)``
    its pure-jnp reference; ``prep`` maps standard-normal draws of the
    operand shapes to valid operands; outputs must agree to ``tol``
    (``atol = rtol``)."""
    call: Callable
    ref: Callable
    operands: Tuple[jax.ShapeDtypeStruct, ...]
    tol: float
    prep: Callable = lambda *xs: xs

    def inputs(self, key) -> tuple:
        keys = jax.random.split(key, len(self.operands))
        raw = [jax.random.normal(k, s.shape, F32)
               for k, s in zip(keys, self.operands)]
        return tuple(jnp.asarray(x).astype(s.dtype)
                     for x, s in zip(self.prep(*raw), self.operands))


def kernel_cases(small: bool = False) -> Dict[str, KernelCase]:
    if small:
        S, H, K, hd, W, E, M, D, F, Din, L = 128, 4, 2, 32, 256, 16, 16, \
            128, 256, 256, 128
    else:
        S, H, K, hd, W, E, M, D, F, Din, L = 2048, 32, 8, 128, 4096, 128, \
            64, 2048, 768, 8192, 2560
    G, touched = 2 * E, M * 13 // 16                # 52 of 128 experts

    def ops(*shapes):
        return tuple(jax.ShapeDtypeStruct(s, d) for s, d in shapes)

    # bf16 operands round to 2^-8: the kernels and the references
    # accumulate in f32 in different orders, then round their outputs
    bf16_tol = 2e-2
    return {
        "flash_attention": KernelCase(
            lambda q, k, v, interpret=False: flash_attention(
                q, k, v, causal=True, interpret=interpret),
            lambda q, k, v: flash_attention_ref(q, k, v, causal=True),
            ops(((1, S, H, hd), BF16), ((1, S, K, hd), BF16),
                ((1, S, K, hd), BF16)), bf16_tol),
        "decode_attention": KernelCase(
            lambda q, k, v, pos, interpret=False: decode_attention(
                q, k, v, pos=pos, window=W, interpret=interpret),
            lambda q, k, v, pos: decode_attention_ref(q, k, v, pos=pos,
                                                      window=W),
            ops(((4, H, hd), BF16), ((4, W, K, hd), BF16),
                ((4, W, K, hd), BF16), ((), I32)), bf16_tol,
            prep=lambda q, k, v, pos: (q, k, v, jnp.int32(W - 1))),
        # the reference: three f32 ragged_dots over layer 1's slice; the
        # kernel rounds only h and its output to bf16. The rows are
        # scaled so the output is of order 1
        "grouped_gemm": KernelCase(
            lambda *a, interpret=False: ragged_grouped_ffn(
                *a, interpret=interpret),
            lambda xs, wg, wu, wd, sizes: ragged_swiglu_ref(
                xs.astype(F32), *(w[E:].astype(F32) for w in (wg, wu, wd)),
                sizes[E:]),
            ops(((M, D), BF16), ((G, D, F), BF16), ((G, D, F), BF16),
                ((G, F, D), BF16), ((G,), I32)), bf16_tol,
            prep=lambda xs, wg, wu, wd, s: (
                xs * (D ** -0.5 * F ** -0.25), wg, wu, wd,
                _route(s, E, M, touched))),
        # f32 recurrences, all on the vector unit: only the order of the
        # h.C sum differs from the reference
        "ssm_scan": KernelCase(
            lambda *a, interpret=False: ssm_scan(*a, interpret=interpret),
            ssm_scan_ref,
            ops(((2, 256, Din), F32), ((Din, 16), F32), ((2, 256, 16), F32),
                ((2, 256, 16), F32), ((2, 256, Din), F32)), 1e-3,
            prep=lambda dt, A, B_, C_, x: (jax.nn.softplus(dt),
                                           -jnp.exp(A * 0.5), B_, C_, x)),
        "rglru_scan": KernelCase(
            lambda a, b, h0, interpret=False: rglru_scan(
                a, b, h0, interpret=interpret),
            rglru_scan_ref,
            ops(((2, 256, L), F32), ((2, 256, L), F32), ((2, L), F32)), 1e-4,
            prep=lambda a, b, h0: (jax.nn.sigmoid(a), b, h0)),
    }
