"""Decoder-only LM assembly with heterogeneous layer patterns.

Layers are grouped into *periods* of ``len(cfg.layer_pattern)`` and scanned
(stacked params, one period per scan step); the remainder (``num_layers %
period``) is unrolled as ``tail``. This keeps HLO size O(period) in depth —
essential for the 512-device dry-run — while supporting hybrid stacks like
RecurrentGemma's (rglru, rglru, local).

Every block emits an instrumentation ``aux`` dict controlled by rt.taps
(the P-Shell tap points, DESIGN.md C2/C3): per-layer activation checksums
(commit stream), nan/inf toggle bits and MoE router stats (coverage).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.utils import checksum, has_nan_bit, fold_key
from repro.models.runtime import Runtime
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import recurrent as rec_mod
from repro.models.layers import (
    init_norm, norm_apply, init_mlp, mlp_apply, init_embed, embed_apply,
    init_dense, logits_apply)

_ATTN_KINDS = ("attn", "swa", "local")


# ------------------------------------------------------------------ block ---
def init_block(key, cfg, spec):
    mixer, ffn = spec
    p: Dict[str, Any] = {"norm1": init_norm(cfg, cfg.d_model)}
    if mixer in _ATTN_KINDS:
        p["attn"] = attn.init_attention(fold_key(key, "attn"), cfg)
    elif mixer == "rglru":
        p["rglru"] = rec_mod.init_rglru(fold_key(key, "rglru"), cfg)
    elif mixer == "mamba":
        p["mamba"] = ssm_mod.init_mamba(fold_key(key, "mamba"), cfg)
    else:
        raise ValueError(f"unknown mixer {mixer!r}")
    if ffn is not None:
        p["norm2"] = init_norm(cfg, cfg.d_model)
        if ffn == "mlp":
            p["mlp"] = init_mlp(fold_key(key, "mlp"), cfg, cfg.d_ff)
        elif ffn == "moe":
            p["moe"] = moe_mod.init_moe(fold_key(key, "moe"), cfg)
        else:
            raise ValueError(f"unknown ffn {ffn!r}")
    return p


def _mixer_window(cfg, mixer):
    return cfg.window if mixer in ("swa", "local") else 0


def block_apply(p, cfg, spec, x, positions, rt: Runtime):
    mixer, ffn = spec
    h = norm_apply(cfg, p["norm1"], x)
    impl = {"flops": "cost", "mem": "mem"}.get(rt.cost_mode,
                                               rt.attention_impl)
    if mixer in _ATTN_KINDS:
        y = attn.attention_apply(p["attn"], cfg, h, positions,
                                 window=_mixer_window(cfg, mixer),
                                 impl=impl)
    elif mixer == "rglru":
        y = rec_mod.rglru_apply(p["rglru"], cfg, h, impl=impl)
    else:
        y = ssm_mod.mamba_apply(p["mamba"], cfg, h, impl=impl)
    x = x + y

    aux: Dict[str, Any] = {}
    if ffn is not None:
        h2 = norm_apply(cfg, p["norm2"], x)
        if ffn == "mlp":
            y2 = mlp_apply(p["mlp"], h2)
        else:
            y2, stats = moe_mod.moe_apply(
                p["moe"], cfg, h2, impl=rt.moe_impl, mesh=rt.mesh,
                data_axes=rt.data_axes, model_axis=rt.model_axis)
            if "router" in rt.taps:
                aux["moe"] = stats
            elif "coverage" in rt.taps:
                aux["moe"] = {"expert_toggles": stats["expert_toggles"]}
            aux["moe_aux_loss"] = stats["aux_loss"]
        x = x + y2
    x = rt.constrain(x)
    if "commits" in rt.taps:
        aux["checksum"] = checksum(x)
    if "coverage" in rt.taps:
        aux["nan_bit"] = has_nan_bit(x)
    return x, aux


# ----------------------------------------------------------- decode block ---
def block_cache_spec(cfg, spec, batch: int, max_len: int):
    mixer, _ = spec
    if mixer in _ATTN_KINDS:
        return attn.cache_spec(cfg, batch, max_len, _mixer_window(cfg, mixer))
    if mixer == "rglru":
        return rec_mod.rglru_state_spec(cfg, batch)
    return ssm_mod.mamba_state_spec(cfg, batch)


def block_decode(p, cfg, spec, x1, cache, pos, rt: Runtime, layer=None):
    """One-token decode of one block: (x1, cache, routing), ``routing``
    the layer-step's (3,) :func:`~repro.models.moe.routing_counts`
    (zeros for a block without experts). ``layer``: None for a block
    outside the scanned stack; else this block's stacked inputs are read
    at index ``layer``: its cache (a stack of layers' caches, returned
    whole with layer ``layer`` updated in place) and the routed experts
    :func:`_hoist_experts` took out of the scan."""
    mixer, ffn = spec
    h = norm_apply(cfg, p["norm1"], x1)
    if mixer in _ATTN_KINDS:
        y, cache = attn.decode_attention_apply(
            p["attn"], cfg, h, cache, pos,
            window=_mixer_window(cfg, mixer), impl=rt.attention_impl,
            mesh=rt.mesh, data_axes=rt.data_axes, layer=layer)
    else:
        # recurrent states are small: read the layer's, write it back whole
        state = cache if layer is None else jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False),
            cache)
        if mixer == "rglru":
            y, state = rec_mod.rglru_decode(p["rglru"], cfg, h, state)
        else:
            y, state = ssm_mod.mamba_decode(p["mamba"], cfg, h, state)
        cache = state if layer is None else jax.tree.map(
            lambda c, s: jax.lax.dynamic_update_index_in_dim(c, s, layer, 0),
            cache, state)
    x1 = x1 + y
    routing = jnp.zeros((3,), jnp.int32)
    if ffn is not None:
        h2 = norm_apply(cfg, p["norm2"], x1)
        if ffn == "mlp":
            y2 = mlp_apply(p["mlp"], h2)
        else:
            # decode uses shard-local sort dispatch (B tokens; a2a is a
            # prefill/train strategy — the sequence dim is 1 here); with a
            # mesh the scan slices the experts per layer (_hoist_experts)
            y2, stats = moe_mod.moe_apply(
                p["moe"], cfg, h2, impl="sort", mesh=rt.mesh,
                data_axes=rt.data_axes,
                layer=None if rt.mesh is not None else layer)
            pairs = h2.shape[0] * h2.shape[1] * cfg.num_experts_per_tok
            routing = moe_mod.routing_counts(stats, pairs)
        x1 = x1 + y2
    return x1, cache, routing


def fold_routing(rows):
    """Routing counts of several layer-steps (..., 3) -> (3,): pairs and
    experts touched summed, the largest group the largest."""
    rows = rows.reshape(-1, 3)
    return jnp.concatenate([jnp.sum(rows[:, :2], axis=0),
                            jnp.max(rows[:, 2:], axis=0, initial=0)])


def block_prefill(p, cfg, spec, x, positions, max_len: int, rt: Runtime,
                  layer=None):
    """Full-seq forward that also emits this block's decode cache.
    ``layer``: see :func:`_hoist_experts`."""
    mixer, ffn = spec
    h = norm_apply(cfg, p["norm1"], x)
    if mixer in _ATTN_KINDS:
        window = _mixer_window(cfg, mixer)
        B, S, _ = x.shape
        q, k, v = attn._project_qkv(p["attn"], cfg, h, h,
                                    positions, positions, rope=True)
        pos = positions[0] if positions.ndim > 1 else positions
        if S > attn._Q_CHUNK and S % attn._Q_CHUNK == 0:
            out = attn._chunked_causal(cfg, q, k, v, positions, window)
        else:
            mask = attn._causal_window_mask(pos, pos, window)
            out = attn._attend(cfg, q, k, v, mask)
        y = attn.dense_apply(p["attn"]["o"], out)
        W = min(window, max_len) if window > 0 else max_len
        if W >= S:
            pad = ((0, 0), (0, W - S), (0, 0), (0, 0))
            ck, cv = jnp.pad(k, pad), jnp.pad(v, pad)
        else:
            # ring-consistent placement of the last W keys (slot = t % W)
            slots = (jnp.arange(S - W, S)) % W
            ck = jnp.zeros((B, W) + k.shape[2:], k.dtype) \
                .at[:, slots].set(k[:, S - W:])
            cv = jnp.zeros((B, W) + v.shape[2:], v.dtype) \
                .at[:, slots].set(v[:, S - W:])
        cache = {"k": ck, "v": cv}
    elif mixer == "rglru":
        y, cache = rec_mod.rglru_prefill(p["rglru"], cfg, h)
    else:
        y, cache = ssm_mod.mamba_prefill(p["mamba"], cfg, h)
    x = x + y
    if ffn is not None:
        h2 = norm_apply(cfg, p["norm2"], x)
        if ffn == "mlp":
            y2 = mlp_apply(p["mlp"], h2)
        else:
            y2, _ = moe_mod.moe_apply(
                p["moe"], cfg, h2, impl=rt.moe_impl, mesh=rt.mesh,
                data_axes=rt.data_axes, model_axis=rt.model_axis,
                layer=layer)
        x = x + y2
    return x, cache


# --------------------------------------------------------------- assembly ---
def _partition(cfg):
    P_len = len(cfg.layer_pattern)
    n_periods = cfg.num_layers // P_len
    remainder = cfg.num_layers % P_len
    return P_len, n_periods, remainder


_EXPERT_STACKS = ("gate", "up", "down")


def _hoist_experts(blocks, spec, rt: Runtime, impl: str):
    """For serving (prefill and decode) with no mesh: split a scanned
    period position's routed-expert weights out of the scan's inputs.
    Returns (the position's stacked params without them, the whole expert
    stacks or None). A scan over a stack slices each layer's params out
    of it, and a slice of an expert stack is a copy of every expert of
    the layer, a read and a write of all of them per layer-step, where a
    decode step needs only the experts its tokens touch. The dropless
    expert layer instead reads layer ``i`` of the whole stacks in place
    (``moe_apply(..., layer=i)``). Training keeps the slices: its
    gradients come back per layer from the scan."""
    if spec[1] != "moe" or rt.mesh is not None or impl != "sort":
        return blocks, None
    moe = blocks["moe"]
    experts = {k: moe[k] for k in _EXPERT_STACKS}
    rest = {k: v for k, v in moe.items() if k not in _EXPERT_STACKS}
    return {**blocks, "moe": rest}, experts


def _with_experts(p, experts):
    return p if experts is None else {**p, "moe": {**p["moe"], **experts}}


def init_stack(key, cfg):
    """Stacked period params + unrolled tail."""
    P_len, n_periods, remainder = _partition(cfg)
    pattern = cfg.layer_pattern
    blocks = []
    for pos in range(P_len):
        keys = jax.random.split(fold_key(key, f"pos{pos}"), n_periods)
        blocks.append(jax.vmap(
            lambda k: init_block(k, cfg, pattern[pos]))(keys))
    tail = [init_block(fold_key(key, f"tail{i}"), cfg, pattern[i % P_len])
            for i in range(remainder)]
    return {"blocks": tuple(blocks), "tail": tail}


def stack_apply(stack, cfg, x, positions, rt: Runtime):
    """Forward through all layers. Returns (x, aux_tree)."""
    P_len, n_periods, remainder = _partition(cfg)
    pattern = cfg.layer_pattern

    def period_body(x, period_params):
        auxes = []
        for pos in range(P_len):
            x, aux = block_apply(period_params[pos], cfg, pattern[pos],
                                 x, positions, rt)
            auxes.append(aux)
        return x, tuple(auxes)

    aux_all: Dict[str, Any] = {}
    if n_periods > 0:
        body = rt.checkpoint(period_body)
        x, ys = jax.lax.scan(body, x, stack["blocks"])
        aux_all["scanned"] = ys          # tuple(pos) of dicts, leading n_periods
    tail_aux = []
    for i, p in enumerate(stack["tail"]):
        x, aux = block_apply(p, cfg, pattern[i % P_len], x, positions, rt)
        tail_aux.append(aux)
    aux_all["tail"] = tuple(tail_aux)
    return x, aux_all


def stack_cache_spec(cfg, batch: int, max_len: int):
    P_len, n_periods, remainder = _partition(cfg)
    pattern = cfg.layer_pattern

    def stacked(spec_tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_periods,) + s.shape, s.dtype),
            spec_tree)

    scanned = tuple(stacked(block_cache_spec(cfg, pattern[pos], batch, max_len))
                    for pos in range(P_len)) if n_periods else ()
    tail = tuple(block_cache_spec(cfg, pattern[i % P_len], batch, max_len)
                 for i in range(remainder))
    return {"scanned": scanned, "tail": tail,
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


def stack_decode(stack, cfg, x1, cache, rt: Runtime):
    """One-token decode through all layers; returns (x1, new_cache,
    routing), ``routing`` the step's :func:`fold_routing` over layers."""
    P_len, n_periods, remainder = _partition(cfg)
    pattern = cfg.layer_pattern
    pos = cache["pos"]

    new_cache = dict(cache)
    rows = []
    if n_periods > 0:
        split = [_hoist_experts(stack["blocks"][i], pattern[i], rt,
                                "sort") for i in range(P_len)]

        def period_body(carry, inp):
            x, stacks = carry
            params_p, period = inp
            new_s, routes = [], []
            for i in range(P_len):
                x, c, r = block_decode(
                    _with_experts(params_p[i], split[i][1]), cfg,
                    pattern[i], x, stacks[i], pos, rt, layer=period)
                new_s.append(c)
                routes.append(r)
            return (x, tuple(new_s)), jnp.stack(routes)

        # the cache stack rides in the carry, each layer updated in place:
        # as the scan's xs/ys it would be copied whole every token step
        (x1, new_cache["scanned"]), routes = jax.lax.scan(
            period_body, (x1, cache["scanned"]),
            (tuple(b for b, _ in split), jnp.arange(n_periods)))
        rows.append(routes.reshape(-1, 3))
    tail_new = []
    for i, p in enumerate(stack["tail"]):
        x1, c, r = block_decode(p, cfg, pattern[i % P_len], x1,
                                cache["tail"][i], pos, rt)
        tail_new.append(c)
        rows.append(r[None])
    new_cache["tail"] = tuple(tail_new)
    new_cache["pos"] = pos + 1
    routing = (fold_routing(jnp.concatenate(rows)) if rows
               else jnp.zeros((3,), jnp.int32))
    return x1, new_cache, routing


def stack_prefill(stack, cfg, x, positions, max_len: int, rt: Runtime):
    P_len, n_periods, remainder = _partition(cfg)
    pattern = cfg.layer_pattern

    cache: Dict[str, Any] = {}
    if n_periods > 0:
        split = [_hoist_experts(stack["blocks"][i], pattern[i], rt,
                                rt.moe_impl) for i in range(P_len)]

        def period_body(x, inp):
            params_p, period = inp
            caches = []
            for i in range(P_len):
                experts = split[i][1]
                x, c = block_prefill(
                    _with_experts(params_p[i], experts), cfg, pattern[i], x,
                    positions, max_len, rt,
                    layer=None if experts is None else period)
                caches.append(c)
            return x, tuple(caches)

        body = rt.checkpoint(period_body)
        x, cache["scanned"] = jax.lax.scan(
            body, x, (tuple(b for b, _ in split), jnp.arange(n_periods)))
    else:
        cache["scanned"] = ()
    tail_c = []
    for i, p in enumerate(stack["tail"]):
        x, c = block_prefill(p, cfg, pattern[i % P_len], x, positions,
                             max_len, rt)
        tail_c.append(c)
    cache["tail"] = tuple(tail_c)
    cache["pos"] = jnp.asarray(x.shape[1], jnp.int32)
    return x, cache


# -------------------------------------------------------------- LM facade ---
def init_lm(key, cfg):
    params = {
        "embed": init_embed(fold_key(key, "embed"), cfg),
        "stack": init_stack(fold_key(key, "stack"), cfg),
        "final_norm": init_norm(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        from repro.utils import dtype_of
        params["lm_head"] = init_dense(fold_key(key, "head"), cfg.d_model,
                                       cfg.vocab_size, dtype_of(cfg.dtype))
    return params


def lm_hidden(params, cfg, tokens, rt: Runtime, prefix_embeds=None,
              positions=None):
    """tokens (B,S) -> final hidden (B,S',D), aux. prefix_embeds (VLM): is
    prepended before the stack; S' = S + prefix length."""
    x = embed_apply(params["embed"], tokens,
                    positions if cfg.learned_pos else None)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x, aux = stack_apply(params["stack"], cfg, x, positions, rt)
    x = norm_apply(cfg, params["final_norm"], x)
    return x, aux


def lm_logits(params, cfg, tokens, rt: Runtime, **kw):
    h, aux = lm_hidden(params, cfg, tokens, rt, **kw)
    return logits_apply(params, cfg, h), aux
