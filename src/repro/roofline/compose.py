"""Scale-Down roofline composition (DESIGN C1 applied to cost analysis).

XLA's cost_analysis counts while (scan) bodies ONCE, so whole-graph numbers
under-count depth. Following the paper's methodology we decompose the step
into subsystems, dry-run each one in isolation with its exact interface
(shapes + shardings preserved), and extrapolate:

    cost(step) = n_periods x cost(period fwd[+bwd])
               + cost(embed+head[+bwd]) + cost(optimizer)

Each sub-lowering uses Runtime(cost_mode=True): inner scans are replaced by
flop-equivalent scan-free proxies (attention unchunked; time-recurrences as
one elementwise pass), so cost_analysis sees every op exactly once.
Collective bytes come from the HLO parser (with while-trip multipliers for
any remaining loops, e.g. shard_map bodies).

All numbers are per-device (the SPMD module is partitioned); roofline terms
divide by per-chip peaks.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tfm
from repro.models import build_model, input_specs
from repro.models.model import cross_entropy, decode_cache_len
from repro.models.layers import norm_apply, logits_apply, embed_apply
from repro.models.runtime import Runtime
from repro.sharding import (param_shardings, batch_shardings,
                            cache_shardings, replicated, fit_spec)


def _sh(mesh, shape_tuple, spec):
    """NamedSharding with indivisible axes dropped (e.g. batch=1 cells)."""
    return NamedSharding(mesh, fit_spec(shape_tuple, spec, mesh))
from repro.roofline.hlo import collective_summary
from repro.roofline.hw import Hardware, HW_V5E
from repro.utils import dtype_of, fold_key


def _measure(fn, arg_specs, in_sh, n_dev, static_donate=None):
    jfn = jax.jit(fn, in_shardings=in_sh)
    compiled = jfn.lower(*arg_specs).compile()
    ca = compiled.cost_analysis() or {}
    colls = collective_summary(compiled.as_text(), n_dev)
    return {
        "flops": float(ca.get("flops", 0) or 0),
        "bytes": float(ca.get("bytes accessed", 0) or 0),
        "coll_operand": colls["total_operand_bytes"],
        "coll_wire": colls["total_effective_bytes"],
    }


def _scale(c: Dict[str, float], k: float) -> Dict[str, float]:
    return {kk: v * k for kk, v in c.items()}


def _add(*cs: Dict[str, float]) -> Dict[str, float]:
    keys = cs[0].keys()
    return {k: sum(c[k] for c in cs) for k in keys}


def _period_param_specs(cfg):
    pattern = cfg.layer_pattern
    return tuple(
        jax.eval_shape(lambda pos=pos: tfm.init_block(
            jax.random.key(0), cfg, pattern[pos]))
        for pos in range(len(pattern)))


# ------------------------------------------------------------ train/prefill -
def period_cost(cfg, shape, mesh, rt: Runtime, mode: str) -> Dict[str, float]:
    """One scan period, fwd (+bwd for train), with production shardings."""
    pattern = cfg.layer_pattern
    n_dev = mesh.devices.size
    dp = rt.data_axes
    B = shape.global_batch
    S = shape.seq_len + (cfg.num_patches if cfg.family == "vlm" else 0) \
        if cfg.family == "vlm" else shape.seq_len
    dt = dtype_of(cfg.dtype)
    pspecs = _period_param_specs(cfg)
    psh = tuple(param_shardings(mesh, ps,
                                "train" if mode == "train" else "serve",
                                moe_ep=(rt.moe_impl == "a2a"))
                for ps in pspecs)
    x_spec = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    x_sh = _sh(mesh, x_spec.shape, P(dp, None, None))

    def make_fn(cost_mode):
        rt_cost = rt.with_(cost_mode=cost_mode, taps=frozenset())

        def fwd(pp, x):
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S))
            for pos in range(len(pattern)):
                x, _ = tfm.block_apply(pp[pos], cfg, pattern[pos], x,
                                       positions, rt_cost)
            return x

        if mode == "train":
            def fb(pp, x):
                y, vjp = jax.vjp(fwd, pp, x)
                dpp, dx = vjp(jnp.ones_like(y))
                return y, dpp, dx
            return fb
        return fwd

    # flops from the flop-exact lowering; bytes + collectives from the
    # traffic-faithful lowering (see Runtime.cost_mode)
    c_flops = _measure(make_fn("flops"), (pspecs, x_spec), (psh, x_sh), n_dev)
    c_mem = _measure(make_fn("mem"), (pspecs, x_spec), (psh, x_sh), n_dev)
    return {"flops": c_flops["flops"], "bytes": c_mem["bytes"],
            "coll_operand": c_mem["coll_operand"],
            "coll_wire": c_mem["coll_wire"]}


def embed_head_cost(cfg, shape, mesh, rt: Runtime,
                    mode: str) -> Dict[str, float]:
    n_dev = mesh.devices.size
    dp = rt.data_axes
    B, S = shape.global_batch, shape.seq_len
    dt = dtype_of(cfg.dtype)
    sh_mode = "train" if mode == "train" else "serve"

    model = build_model(cfg, rt)
    full = jax.eval_shape(model.init, jax.random.key(0))
    eh = {"embed": full["embed"], "final_norm": full["final_norm"]}
    if not cfg.tie_embeddings and "lm_head" in full:
        eh["lm_head"] = full["lm_head"]
    eh_sh = param_shardings(mesh, eh, sh_mode)

    tok_spec = jax.ShapeDtypeStruct((B, S), jnp.int32)
    tok_sh = _sh(mesh, tok_spec.shape, P(dp, None))
    h_spec = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
    h_sh = _sh(mesh, h_spec.shape, P(dp, None, None))

    def fwd(p, tokens, h, labels):
        x = embed_apply(p["embed"], tokens)
        hn = norm_apply(cfg, p["final_norm"], h)
        if mode == "train":
            logits = logits_apply(p, cfg, hn)
            loss = cross_entropy(logits, labels)
        else:
            # prefill emits logits for the LAST position only
            logits = logits_apply(p, cfg, hn[:, -1:])
            loss = jnp.sum(logits) * 1e-12
        # the 1e-12 term keeps the embedding live (not DCE-able) so its
        # gather + backward scatter are costed
        return loss + jnp.sum(x.astype(jnp.float32)) * 1e-12

    if mode == "train":
        def fn(p, tokens, h, labels):
            (l, ), vjp = jax.vjp(
                lambda p, h: (fwd(p, tokens, h, labels),), p, h)
            dp_, dh = vjp((jnp.ones_like(l),))
            return l, dp_, dh
    else:
        fn = fwd
    return _measure(fn, (eh, tok_spec, h_spec, tok_spec),
                    (eh_sh, tok_sh, h_sh, tok_sh), n_dev)


def optimizer_cost(cfg, mesh, rt: Runtime) -> Dict[str, float]:
    from repro.train.optim import OptConfig, adamw_update, adamw_init
    n_dev = mesh.devices.size
    model = build_model(cfg, rt)
    pspec = jax.eval_shape(model.init, jax.random.key(0))
    psh = param_shardings(mesh, pspec, "train",
                          moe_ep=(rt.moe_impl == "a2a"))
    ospec = jax.eval_shape(adamw_init, pspec)
    osh = {"m": psh, "v": psh, "count": replicated(mesh)}

    def fn(params, grads, opt):
        return adamw_update(OptConfig(), params, grads, opt)

    return _measure(fn, (pspec, pspec, ospec), (psh, psh, osh), n_dev)


# ----------------------------------------------------------------- decode ---
def decode_cost(cfg, shape, mesh, rt: Runtime) -> Dict[str, float]:
    """Per-period decode body x n_periods + embed/head, composed."""
    pattern = cfg.layer_pattern
    n_dev = mesh.devices.size
    dp = rt.data_axes
    B = shape.global_batch
    dt = dtype_of(cfg.dtype)
    cache_len = decode_cache_len(cfg, shape)
    pspecs = _period_param_specs(cfg)
    psh = tuple(param_shardings(mesh, ps, "serve") for ps in pspecs)
    cspecs = tuple(tfm.block_cache_spec(cfg, pattern[i], B, cache_len)
                   for i in range(len(pattern)))
    csh = tuple(cache_shardings(mesh, {"tail": (c,)})["tail"][0]
                for c in cspecs)
    x_spec = jax.ShapeDtypeStruct((B, 1, cfg.d_model), dt)
    x_sh = _sh(mesh, x_spec.shape, P(dp, None, None))
    rt_cost = rt.with_(cost_mode=True, taps=frozenset())

    def body(pp, caches, x):
        pos = jnp.asarray(shape.seq_len, jnp.int32)
        new = []
        for i in range(len(pattern)):
            x, c, _ = tfm.block_decode(pp[i], cfg, pattern[i], x,
                                       caches[i], pos, rt_cost)
            new.append(c)
        return x, tuple(new)

    per = _measure(body, (pspecs, cspecs, x_spec), (psh, csh, x_sh), n_dev)

    # head: final norm + logits on one token
    model = build_model(cfg, rt)
    full = jax.eval_shape(model.init, jax.random.key(0))
    eh = {"embed": full["embed"], "final_norm": full["final_norm"]}
    if not cfg.tie_embeddings and "lm_head" in full:
        eh["lm_head"] = full["lm_head"]
    eh_sh = param_shardings(mesh, eh, "serve")

    def head(p, x, tok):
        x = x + embed_apply(p["embed"], tok)
        return logits_apply(p, cfg, norm_apply(cfg, p["final_norm"], x))

    tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = _sh(mesh, tok_spec.shape, P(dp, None))
    head_c = _measure(head, (eh, x_spec, tok_spec), (eh_sh, x_sh, tok_sh),
                      n_dev)

    P_len = len(pattern)
    n_periods = cfg.num_layers // P_len
    rem = cfg.num_layers % P_len
    scale = n_periods + rem / P_len
    return _add(_scale(per, scale), head_c)


# ------------------------------------------------------- analytic memory ----
def analytic_memory_bytes(cfg: ModelConfig, shape: ShapeConfig, mesh,
                          mode: str, dp_size: int) -> float:
    """Per-device HBM traffic assuming TPU-grade fusion (the floor the
    Pallas kernels target). The HLO-derived number (recorded alongside) is
    the ceiling: the CPU backend's cost analysis counts unfused elementwise
    chains and copies 2-5x.

    Terms (bytes/device/step):
      weights  — bf16 params read once per fwd pass (+once per bwd),
                 grads written+read, opt m/v read+write (f32) for train;
      acts     — per layer ~6 residual-width tensors + FFN intermediates
                 in/out (flash attention keeps S^2 off HBM);
      cache    — decode: read+write of this step's KV/state slices.
    """
    n_dev = mesh.devices.size
    model_size = mesh.shape["model"]
    nparams = cfg.param_count()
    if mode == "train":
        p_loc = 2.0 * nparams / n_dev          # FSDP+TP: fully sharded
        weights = 2 * p_loc                    # fwd + bwd reads (gathered)
        weights += 2 * p_loc                   # grad write + read
        weights += (nparams / n_dev) * 20.0    # AdamW: p/m/v read+write
    else:
        p_loc = 2.0 * nparams / model_size     # TP only, replicated over dp
        weights = p_loc

    D = cfg.d_model
    tokens_loc = shape.global_batch * (1 if shape.kind == "decode"
                                       else shape.seq_len) / dp_size
    unit = tokens_loc * D * 2.0
    acts = 0.0
    for mixer, ffn in cfg.layer_specs:
        t = 6.0 * unit                          # norms, residuals, qkv/out
        if ffn == "mlp":
            t += 4.0 * unit * (cfg.d_ff / D) / (model_size if mode != "x"
                                                else 1)
        elif ffn == "moe":
            t += 4.0 * unit * (cfg.num_experts_per_tok * cfg.moe_d_ff / D) \
                / model_size
        if mixer == "mamba":
            t += 6.0 * unit * (cfg.d_inner / D) / model_size
        if mixer == "rglru":
            t += 6.0 * unit * ((cfg.lru_width or D) / D) / model_size
        acts += t
    if mode == "train":
        acts *= 3.0                             # bwd re-reads + writes
    cache = 0.0
    if shape.kind == "decode":
        # attention reads the full cache once; states read+write
        from repro.models.model import decode_cache_len
        W = decode_cache_len(cfg, shape)
        for mixer, _ in cfg.layer_specs:
            if mixer in ("attn",):
                cache += (2 * min(W, 10**12) * cfg.num_kv_heads
                          * cfg.head_dim * 2.0)
            elif mixer in ("swa", "local"):
                cache += (2 * min(cfg.window, W) * cfg.num_kv_heads
                          * cfg.head_dim * 2.0)
            elif mixer == "mamba":
                cache += 2 * cfg.d_inner * cfg.ssm_state * 4.0
            elif mixer == "rglru":
                cache += 2 * (cfg.lru_width or D) * 4.0
        cache *= shape.global_batch / dp_size / model_size * 2  # r+w
    return weights + acts + cache


# --------------------------------------------------- attention skip model ---
def _attn_pair_fraction(S: int, window: int) -> float:
    """Fraction of the dense S^2 score matrix a mask-skipping flash kernel
    actually computes: causal ~1/2; sliding-window ~W/S."""
    if window <= 0:
        return (S + 1) / (2.0 * S)
    W = min(window, S)
    pairs = W * (S - (W - 1) / 2.0)
    return pairs / (S * S)


def attention_dense_flops(cfg: ModelConfig, shape: ShapeConfig,
                          mode: str) -> Tuple[float, float]:
    """(dense_flops_global, skipped_flops_global) of the S^2 score+value
    einsums across all attention layers. The XLA cost lowering computes the
    dense product (masking after), so `skipped` is compute the in-repo flash
    kernel provably avoids (causal/window block skipping; see
    kernels/flash_attention and its mask tests)."""
    if shape.kind == "decode":
        return 0.0, 0.0
    B, S = shape.global_batch, shape.seq_len
    mult = 3.0 if mode == "train" else 1.0
    dense = skipped = 0.0
    for mixer, _ in cfg.layer_specs:
        if mixer not in ("attn", "swa", "local"):
            continue
        w = cfg.window if mixer in ("swa", "local") else 0
        d = 4.0 * B * cfg.num_heads * float(S) * S * cfg.head_dim * mult
        dense += d
        skipped += d * (1.0 - _attn_pair_fraction(S, w))
    return dense, skipped


# ------------------------------------------------------------- aggregation --
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); D = tokens."""
    n = cfg.param_count(active_only=cfg.num_experts > 0)
    if shape.kind == "decode":
        tokens = shape.global_batch
        return 2.0 * n * tokens
    tokens = shape.global_batch * shape.seq_len
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def compose_cell(arch_cfg: ModelConfig, shape: ShapeConfig, mesh, rt: Runtime,
                 hw: Hardware = HW_V5E) -> Dict[str, Any]:
    n_dev = mesh.devices.size
    P_len = len(arch_cfg.layer_pattern)
    n_periods = arch_cfg.num_layers // P_len
    rem = arch_cfg.num_layers % P_len
    depth_scale = n_periods + rem / P_len

    if shape.kind == "decode":
        total = decode_cost(arch_cfg, shape, mesh, rt)
    else:
        mode = shape.kind if shape.kind == "train" else "prefill"
        per = period_cost(arch_cfg, shape, mesh, rt, mode)
        eh = embed_head_cost(arch_cfg, shape, mesh, rt, mode)
        total = _add(_scale(per, depth_scale), eh)
        if mode == "train":
            total = _add(total, optimizer_cost(arch_cfg, mesh, rt))

    from repro.sharding import make_axes
    dp_size = make_axes(mesh, shape.kind).dp_size

    compute_s = total["flops"] / hw.peak_flops_bf16
    mode_ = "train" if shape.kind == "train" else "prefill"
    _, skipped = attention_dense_flops(arch_cfg, shape, mode_)
    # kernel-adjusted: the flash kernel skips fully-masked score blocks
    compute_s_kernel = max(
        compute_s - (skipped / n_dev) / hw.peak_flops_bf16, 0.0)
    memory_s_hlo = total["bytes"] / hw.hbm_bw
    mem_est = analytic_memory_bytes(
        arch_cfg, shape, mesh,
        "train" if shape.kind == "train" else "serve", dp_size)
    memory_s = mem_est / hw.hbm_bw
    # one bidirectional ring axis: 2 links active per chip
    collective_s = total["coll_wire"] / (hw.ici_link_bw * 2)
    mf = model_flops(arch_cfg, shape)
    hlo_flops_global = total["flops"] * n_dev
    bound = max(compute_s, memory_s, collective_s)
    bound_kernel = max(compute_s_kernel, memory_s, collective_s)
    terms = {
        "compute_s": compute_s,
        "compute_s_kernel": compute_s_kernel,
        "roofline_fraction_kernel": (
            (mf / n_dev / hw.peak_flops_bf16) / max(bound_kernel, 1e-30)),
        "memory_s": memory_s,               # analytic (TPU-fusion floor)
        "memory_s_hlo": memory_s_hlo,       # HLO bytes (CPU-backend ceiling)
        "collective_s": collective_s,
        "dominant": max(
            (("compute", compute_s), ("memory", memory_s),
             ("collective", collective_s)), key=lambda t: t[1])[0],
        "model_flops": mf,
        "hlo_flops_global": hlo_flops_global,
        "useful_ratio": mf / hlo_flops_global if hlo_flops_global else 0.0,
        "per_device": total,
        "step_time_bound_s": bound,
        "roofline_fraction": (
            (mf / n_dev / hw.peak_flops_bf16) / max(bound, 1e-30)),
    }
    return terms
