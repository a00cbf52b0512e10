"""Mixture-of-Experts FFN with three dispatch implementations.

- ``dense``: one-hot all-experts oracle. O(T*E) compute — smoke/test configs
  only; the golden model for the other two.
- ``sort``:  with no mesh, DROPLESS sort dispatch: the T*k token-expert
  pairs are sorted by expert and the SwiGLU expert FFN runs as grouped
  products over the contiguous ragged groups (``jax.lax.ragged_dot``), so
  no pair is dropped at any load and ``dropped_frac`` is 0. Under a mesh
  it is capacity-based and SPMD-local (``_moe_sort_local``); with expert
  weights F-sharded over "model" this is Expert-TP ("etp"): no all-to-all,
  one all-reduce, zero load imbalance — the right strategy for
  few-large-expert archs (mixtral: 8 experts of d_ff 14336).
- ``a2a``:   shard_map expert parallelism over the "model" mesh axis with
  explicit all_to_all dispatch/return — the right strategy for
  many-small-expert archs (qwen3: 128 experts of d_ff 768). Capacity-based.

All impls share the same router and emit the same stats pytree, which feeds
the P-Shell commit stream (router decisions) and coverage bitmaps (expert
toggles) — DESIGN.md C3/C6. The stages run under the named scopes
``zp.moe.route``, ``zp.moe.dispatch``, ``zp.moe.experts`` and
``zp.moe.combine``, so they carry those names in the op metadata of every
program that runs the layer.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils import dtype_of, fold_key
from repro.models.layers import init_dense


def init_moe(key, cfg):
    dt = dtype_of(cfg.dtype)
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    k = functools.partial(fold_key, key)
    scale = D ** -0.5

    def w(kk, shape, s):
        return (jax.random.normal(kk, shape, jnp.float32) * s).astype(dt)

    return {
        "router": {"w": w(k("router"), (D, E), scale).astype(jnp.float32)},
        "gate": w(k("gate"), (E, D, F), scale),
        "up": w(k("up"), (E, D, F), scale),
        "down": w(k("down"), (E, F, D), F ** -0.5),
    }


@jax.named_scope("zp.moe.route")
def _route(p, cfg, x2):
    """x2: (T, D) -> gates (T,k) f32, idx (T,k) i32, probs (T,E) f32."""
    logits = (x2.astype(jnp.float32) @ p["router"]["w"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx, probs


def routing_counts(stats, pairs: int):
    """``(3,)`` int32 of one layer-step's routing: token-expert ``pairs``,
    distinct experts touched, and the largest group (pairs on one
    expert), read from the router ``stats`` of that layer-step."""
    touched = jnp.sum(stats["expert_toggles"].astype(jnp.int32))
    largest = jnp.round(jnp.max(stats["load"]) * pairs).astype(jnp.int32)
    return jnp.stack([jnp.int32(pairs), touched, largest])


def _stats(cfg, idx, probs, dropped_frac):
    """Router stats: coverage toggles + load-balance aux loss terms."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
    load = counts / jnp.maximum(jnp.sum(counts), 1.0)
    importance = jnp.mean(probs, axis=0)
    # Switch-style aux loss: E * sum(load_frac * mean_prob)
    aux_loss = E * jnp.sum(load * importance)
    return {
        "expert_toggles": counts > 0,          # (E,) coverage bits (C6)
        "load": load,                          # (E,)
        "aux_loss": aux_loss,                  # scalar
        "dropped_frac": dropped_frac,          # scalar
    }


# ------------------------------------------------------------------ dense ---
def _moe_dense(p, cfg, x2):
    E = cfg.num_experts
    gates, idx, probs = _route(p, cfg, x2)
    combine = jnp.zeros((x2.shape[0], E), jnp.float32)
    combine = combine.at[jnp.arange(x2.shape[0])[:, None], idx].add(gates)
    with jax.named_scope("zp.moe.experts"):
        g = jax.nn.silu(jnp.einsum("td,edf->tef", x2, p["gate"]))
        u = jnp.einsum("td,edf->tef", x2, p["up"])
        y_e = jnp.einsum("tef,efd->ted", g * u, p["down"])
    with jax.named_scope("zp.moe.combine"):
        y = jnp.einsum("ted,te->td", y_e.astype(jnp.float32), combine)
    return y.astype(x2.dtype), _stats(cfg, idx, probs, jnp.float32(0.0))


# ------------------------------------------------------------------- sort ---
def _capacity(cfg, n_tokens: int, n_experts: int) -> int:
    c = math.ceil(n_tokens * cfg.num_experts_per_tok * cfg.capacity_factor
                  / n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


@jax.named_scope("zp.moe.dispatch")
def _sort_dispatch(cfg, x2, idx):
    """Returns (disp (E,C,D), gather_idx (T*k,), keep (T*k,), inv_order)."""
    T, D = x2.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = _capacity(cfg, T, E)
    flat_e = idx.reshape(-1)                                  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T * k) - offsets[sorted_e]
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)         # E*C = trash row
    tok = order // k
    disp = jnp.zeros((E * C + 1, D), x2.dtype).at[slot].add(
        jnp.where(keep[:, None], x2[tok], 0))
    inv_order = jnp.argsort(order)
    return disp[:-1].reshape(E, C, D), slot, keep, inv_order, counts


@jax.named_scope("zp.moe.combine")
def _sort_combine(cfg, y_ecd, slot, keep, inv_order, gates, T, D):
    flat = jnp.concatenate(
        [y_ecd.reshape(-1, D), jnp.zeros((1, D), y_ecd.dtype)], axis=0)
    vals_sorted = flat[jnp.minimum(slot, flat.shape[0] - 1)]
    vals_sorted = jnp.where(keep[:, None], vals_sorted, 0)
    vals = vals_sorted[inv_order]                             # (T*k, D)
    k = cfg.num_experts_per_tok
    y = jnp.sum(vals.reshape(T, k, D).astype(jnp.float32)
                * gates[..., None], axis=1)
    return y


@jax.named_scope("zp.moe.experts")
def _expert_ffn(p, h_ecd):
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h_ecd, p["gate"]))
    u = jnp.einsum("ecd,edf->ecf", h_ecd, p["up"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["down"])


def _moe_sort(p, cfg, x2):
    T, D = x2.shape
    gates, idx, probs = _route(p, cfg, x2)
    disp, slot, keep, inv_order, counts = _sort_dispatch(cfg, x2, idx)
    y_ecd = _expert_ffn(p, disp)
    y = _sort_combine(cfg, y_ecd, slot, keep, inv_order, gates, T, D)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return y.astype(x2.dtype), _stats(cfg, idx, probs, dropped)


# --------------------------------------------------------------- dropless ---
def _moe_dropless(p, cfg, x2, layer=None):
    """Every token-expert pair computed: the pairs sorted by expert form
    contiguous ragged groups, one grouped product per expert weight.

    With ``layer``, ``p``'s expert weights are a stack of ``n`` layers'
    (``(n, E, D, F)``) and this is layer ``layer`` of it: the stack is
    read in place as ``n * E`` groups, all empty but this layer's, so only
    the experts the tokens touch are read. (A slice of the stack would be
    a copy of every expert of the layer first.)"""
    T, D = x2.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gates, idx, probs = _route(p, cfg, x2)
    w = {n: p[n] for n in ("gate", "up", "down")}
    with jax.named_scope("zp.moe.dispatch"):
        flat_e = idx.reshape(-1)                              # (T*k,)
        order = jnp.argsort(flat_e, stable=True)
        sizes = jnp.bincount(flat_e, length=E).astype(jnp.int32)
        xs = x2[order // k]                                   # (T*k, D)
        if layer is not None:
            n = w["gate"].shape[0]
            w = {a: v.reshape((n * E,) + v.shape[2:]) for a, v in w.items()}
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n * E,), jnp.int32), sizes, (layer * E,))
    with jax.named_scope("zp.moe.experts"):
        g = jax.nn.silu(jax.lax.ragged_dot(xs, w["gate"], sizes))
        u = jax.lax.ragged_dot(xs, w["up"], sizes)
        ys = jax.lax.ragged_dot(g * u, w["down"], sizes)      # (T*k, D)
    with jax.named_scope("zp.moe.combine"):
        vals = ys[jnp.argsort(order)].reshape(T, k, D)
        y = jnp.sum(vals.astype(jnp.float32) * gates[..., None], axis=1)
    return y.astype(x2.dtype), _stats(cfg, idx, probs, jnp.float32(0.0))


# -------------------------------------------------------------------- a2a ---
def _moe_a2a_local(p, cfg, x_block, axis: str, all_axes):
    """Per-device body under shard_map. x_block: (B_loc, S_loc, D)."""
    B, S, D = x_block.shape
    E = cfg.num_experts
    ep = jax.lax.axis_size(axis)
    e_loc = E // ep                              # local experts per device
    x2 = x_block.reshape(B * S, D)
    gates, idx, probs = _route(p, cfg, x2)
    disp, slot, keep, inv_order, counts = _sort_dispatch(cfg, x2, idx)
    C = disp.shape[1]

    send = disp.reshape(ep, e_loc * C, D)
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)        # (ep, e_loc*C, D)
    # rows grouped per local expert: (e_loc, ep*C, D)
    h = recv.reshape(ep, e_loc, C, D).transpose(1, 0, 2, 3) \
            .reshape(e_loc, ep * C, D)
    y_loc = _expert_ffn(p, h)                    # local experts' output
    back = y_loc.reshape(e_loc, ep, C, D).transpose(1, 0, 2, 3) \
               .reshape(ep, e_loc * C, D)
    ret = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                             tiled=True)         # (ep, e_loc*C, D)
    y_ecd = ret.reshape(E, C, D)
    y = _sort_combine(cfg, y_ecd, slot, keep, inv_order, gates, B * S, D)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    st = _stats(cfg, idx, probs, dropped)
    # make stats truly replicated: reduce over every mesh axis
    st = {kk: ((jax.lax.pmax(v.astype(jnp.int32), all_axes) > 0)
               if v.dtype == jnp.bool_
               else jax.lax.pmean(v, all_axes))
          for kk, v in st.items()}
    return y.reshape(B, S, D).astype(x_block.dtype), st


def _moe_a2a(p, cfg, x, mesh, data_axes, model_axis):
    """shard_map EP: tokens seq-split over model axis, experts EP-owned.

    Requires num_experts % model_axis_size == 0 (many-small-expert archs,
    e.g. qwen3 128e over 16). Few-large-expert archs (mixtral 8e) use the
    Expert-TP strategy instead: ``impl="sort"`` under pjit with the expert
    d_ff dim sharded over "model" — no a2a, a single all-reduce, and zero
    load imbalance (DESIGN.md §5).
    """
    E = cfg.num_experts
    ep = mesh.shape[model_axis]
    if E % ep != 0:
        raise ValueError(
            f"a2a EP needs num_experts ({E}) % model axis ({ep}) == 0; "
            "use impl='sort' (Expert-TP) for few-expert archs")
    wspec = P(model_axis, None, None)            # pure EP on the expert dim
    pspec = {"router": {"w": P(None, None)},
             "gate": wspec, "up": wspec, "down": wspec}
    xspec = P(data_axes, model_axis, None)       # tokens seq-split over model
    all_axes = tuple(mesh.axis_names)

    def body(p_blk, x_blk):
        return _moe_a2a_local(p_blk, cfg, x_blk, model_axis, all_axes)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, xspec),
        out_specs=(xspec, {"expert_toggles": P(), "load": P(),
                           "aux_loss": P(), "dropped_frac": P()}),
        check_vma=False)
    return fn(p, x)


def _moe_sort_local(p, cfg, x, mesh, data_axes, model_axis="model"):
    """sort dispatch made SPMD-local (Expert-TP), fully-manual shard_map.

    §Perf finding #1: a global argsort over a data-sharded token dim makes
    GSPMD all-gather every token to every device (capacity and the down-proj
    all-reduce blow up by dp_size). Manual sharding keeps the dispatch
    token-local. Expert weights are d_ff-sharded over "model"; every model
    shard routes its (replicated) tokens identically, computes its F/|model|
    slice of each selected expert, and one psum over "model" completes the
    down-projection (silu is elementwise over F, so F-sharding is exact and
    load balance is perfect — the right strategy for few-large-expert archs).
    """
    import numpy as np
    dp = tuple(a for a in data_axes if a in mesh.axis_names)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    B, S, D = x.shape
    if not dp or B % dp_size:
        y, st = _moe_sort(p, cfg, x.reshape(B * S, D))
        return y.reshape(B, S, D), st

    wspec = {"router": {"w": P(None, None)},
             "gate": P(None, None, model_axis),
             "up": P(None, None, model_axis),
             "down": P(None, model_axis, None)}
    all_axes = tuple(mesh.axis_names)

    def body(p_blk, x_blk):
        b, s, d = x_blk.shape
        y, st = _moe_sort(p_blk, cfg, x_blk.reshape(b * s, d))
        # §Perf change #2: bf16 on the wire (each partial is already an
        # f32 accumulation over F/|model| terms; Megatron-style)
        y = jax.lax.psum(y.astype(x_blk.dtype), model_axis)
        st = {k: (jax.lax.pmax(v.astype(jnp.int32), all_axes) > 0)
              if v.dtype == jnp.bool_
              else jax.lax.pmean(v.astype(jnp.float32), all_axes)
              for k, v in st.items()}
        return y.reshape(b, s, d), st

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(wspec, P(dp, None, None)),
        out_specs=(P(dp, None, None), {k: P() for k in
                                       ("expert_toggles", "load",
                                        "aux_loss", "dropped_frac")}),
        check_vma=False)
    return fn(p, x)


# ------------------------------------------------------------------ entry ---
def moe_apply(p, cfg, x, *, impl: str = "sort", mesh=None,
              data_axes=("data",), model_axis: str = "model", layer=None):
    """x: (B, S, D) -> (y, stats). With no mesh, ``sort`` is dropless.
    ``layer`` (dropless only): ``p``'s expert weights are a stack of
    layers' and this is that layer of it (see ``_moe_dropless``)."""
    B, S, D = x.shape
    if layer is not None and (impl != "sort" or mesh is not None):
        raise ValueError("a layer of an expert stack is read in place only "
                         "by the dropless path (impl='sort', no mesh)")
    if impl == "a2a":
        if mesh is None:
            raise ValueError("a2a MoE dispatch requires a mesh")
        return _moe_a2a(p, cfg, x, mesh, data_axes, model_axis)
    if impl == "sort" and mesh is not None:
        return _moe_sort_local(p, cfg, x, mesh, data_axes)
    x2 = x.reshape(B * S, D)
    if impl == "dense":
        y, st = _moe_dense(p, cfg, x2)
    elif impl == "sort":
        y, st = _moe_dropless(p, cfg, x2, layer)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")
    return y.reshape(B, S, D), st
