"""Time the ragged grouped expert FFN alone against XLA's ``ragged_dot``,
at the expert widths of Qwen3-30B-A3B (d_model 2048, moe_d_ff 768, 128
experts, top-8) in a stack of 4 layers (512 groups), read in place:

  PYTHONPATH=src python -m repro.kernels.grouped_gemm.bench
  PYTHONPATH=src python -m repro.kernels.grouped_gemm.bench --pairs 64 128
  PYTHONPATH=src JAX_PLATFORMS=cpu \\
      python -m repro.kernels.grouped_gemm.bench --small

A sample is one decode layer-step: ``pairs / 8`` tokens, each routed to 8
distinct experts drawn uniformly (51.6 distinct experts of 128 at 64
pairs), in layer ``sample % 4`` of the stack. One timed call runs every
sample in a ``fori_loop``, so the host's dispatch stays out of the time.
For each pair count it prints one JSON line: each side's seconds per
layer-step (median of ``--repeats`` calls), the bytes the layer-step
needs (the touched experts' three weight blocks, the rows and the
output), their bytes/s and share of the chip's HBM peak, and the
kernel's largest gap to a float32 reference.

Times come only from a TPU: elsewhere the script exits non-zero, unless
``--small``, which checks the kernel in interpret mode at small widths
and prints no time.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grouped_gemm.ops import ragged_grouped_ffn
from repro.kernels.grouped_gemm.ref import ragged_swiglu_ref

#: one v5e chip (Google Cloud, "TPU v5e"): HBM bytes/s, bf16 FLOP/s
HBM_PEAK, MXU_PEAK = 819e9, 197e12
TOP_K = 8


def routing_sizes(rng, samples, layers, experts, pairs):
    """(samples, layers * experts) rows per group: sample s routes
    ``pairs // TOP_K`` tokens to ``TOP_K`` distinct experts each, in
    layer ``s % layers``."""
    sizes = np.zeros((samples, layers * experts), np.int32)
    for s in range(samples):
        base = (s % layers) * experts
        for _ in range(pairs // TOP_K):
            sizes[s, base + rng.choice(experts, TOP_K, replace=False)] += 1
    return sizes


def looped(ffn):
    """Every sample's FFN in one program, summed so none is dead."""
    @jax.jit
    def run(xs, sizes, wg, wu, wd):
        def body(s, acc):
            return acc + ffn(xs[s], wg, wu, wd, sizes[s]).astype(jnp.float32)
        return jax.lax.fori_loop(0, xs.shape[0], body,
                                 jnp.zeros(xs.shape[1:], jnp.float32))
    return run


def seconds(fn, args, repeats):
    jax.block_until_ready(fn(*args))                 # compile and warm up
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pairs", type=int, nargs="+", default=[64, 128, 256])
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="interpret-mode agreement check at small widths")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.small:
        print(f"bench: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    interpret = dev.platform != "tpu"
    L, E, D, F = (2, 16, 256, 128) if args.small else (4, 128, 2048, 768)
    samples = 4 if args.small else args.samples
    kw, ku, kd, kx = jax.random.split(jax.random.key(args.seed), 4)
    wg = (jax.random.normal(kw, (L * E, D, F), jnp.float32)
          * D ** -0.5).astype(jnp.bfloat16)
    wu = (jax.random.normal(ku, (L * E, D, F), jnp.float32)
          * D ** -0.5).astype(jnp.bfloat16)
    wd = (jax.random.normal(kd, (L * E, F, D), jnp.float32)
          * F ** -0.5).astype(jnp.bfloat16)
    rng = np.random.default_rng(args.seed)
    kernel = looped(lambda *a: ragged_grouped_ffn(*a, interpret=interpret))
    plain = looped(ragged_swiglu_ref)
    for P in args.pairs:
        sizes = routing_sizes(rng, samples, L, E, P)
        xs = jax.random.normal(jax.random.fold_in(kx, P), (samples, P, D),
                               jnp.bfloat16)
        got = ragged_grouped_ffn(xs[0], wg, wu, wd, sizes[0],
                                 interpret=interpret).astype(jnp.float32)
        # sample 0 routes in layer 0: the reference on its slice alone
        want = ragged_swiglu_ref(*(a.astype(jnp.float32) for a in (
            xs[0], wg[:E], wu[:E], wd[:E])), sizes[0, :E])
        gap = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        touched = float(np.mean(np.sum(sizes > 0, axis=1)))
        line = {"pairs": P, "touched_experts": touched,
                "max_gap_rel": gap, "device": dev.device_kind}
        if not interpret:
            t_kernel = seconds(kernel, (xs, sizes, wg, wu, wd),
                               args.repeats) / samples
            t_plain = seconds(plain, (xs, sizes, wg, wu, wd),
                              args.repeats) / samples
            nbytes = touched * 3 * D * F * 2 + 2 * P * D * 2
            flops = 2 * 3 * P * D * F
            line.update(
                kernel_s=t_kernel, ragged_dot_s=t_plain,
                speedup=t_plain / t_kernel, bytes=nbytes,
                kernel_bytes_per_s=nbytes / t_kernel,
                ragged_dot_bytes_per_s=nbytes / t_plain,
                kernel_hbm_share=nbytes / t_kernel / HBM_PEAK,
                ragged_dot_hbm_share=nbytes / t_plain / HBM_PEAK,
                bound=("hbm" if nbytes / HBM_PEAK > flops / MXU_PEAK
                       else "mxu"))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
