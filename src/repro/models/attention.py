"""GQA attention: full / sliding-window / local, train+prefill+decode paths.

The training/prefill path is q-chunked (scan over query blocks) so that the
S x S score tensor is never materialized — the pure-jnp analogue of the
flash-attention Pallas kernel in ``repro.kernels.flash_attention`` (which is
the TPU target; this path is what the CPU dry-run lowers).

Decode uses a ring-buffer KV cache: bounded at ``cfg.window`` for swa/local
mixers, full-length otherwise. Keys are stored post-RoPE at their absolute
positions, so ring overwrites stay position-correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.utils import dtype_of, fold_key
from repro.models.layers import init_dense, dense_apply, apply_rope

NEG_INF = -1e30
_Q_CHUNK = 1024  # q-block size for the chunked path


def init_attention(key, cfg, cross: bool = False):
    dt = dtype_of(cfg.dtype)
    D, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "q": init_dense(fold_key(key, "q"), D, H * hd, dt, cfg.use_bias),
        "k": init_dense(fold_key(key, "k"), D, K * hd, dt, cfg.use_bias),
        "v": init_dense(fold_key(key, "v"), D, K * hd, dt, cfg.use_bias),
        "o": init_dense(fold_key(key, "o"), H * hd, D, dt, cfg.use_bias,
                        scale=(H * hd) ** -0.5),
    }
    if cfg.use_qk_norm and not cross:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def _headnorm(scale, x, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _project_qkv(p, cfg, xq, xkv, q_positions, kv_positions, rope: bool):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense_apply(p["q"], xq).reshape(B, Sq, H, hd)
    k = dense_apply(p["k"], xkv).reshape(B, Skv, K, hd)
    v = dense_apply(p["v"], xkv).reshape(B, Skv, K, hd)
    if "q_norm" in p:
        q = _headnorm(p["q_norm"]["scale"], q, cfg.norm_eps)
        k = _headnorm(p["k_norm"]["scale"], k, cfg.norm_eps)
    if rope and cfg.use_rope:
        q = apply_rope(q, q_positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _attend(cfg, q, k, v, mask):
    """q: (B,Sq,H,hd) k/v: (B,T,K,hd) mask: (Sq,T) or (B,Sq,T) or None."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,btkh->bkgqt", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (hd ** -0.5)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,btkh->bqkgh", w.astype(v.dtype), v)
    return out.reshape(B, Sq, H * hd)


def _causal_window_mask(q_pos, kv_pos, window: int):
    """(Sq, T) bool: kv visible to q. q_pos/kv_pos: int32 vectors."""
    m = kv_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    return m


# ------------------------------------------------------------ train path ----
def attention_apply(p, cfg, x, positions, *, window: int = 0,
                    causal: bool = True, impl: str = "xla"):
    """Self-attention over the full sequence (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, rope=True)

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_logit_softcap,
            interpret=(impl == "pallas_interpret"))
        out = out.reshape(B, S, -1)
    elif impl == "cost":
        # roofline flop proxy: unchunked (no scan; identical flop count)
        pos = positions[0] if positions.ndim > 1 else positions
        mask = _causal_window_mask(pos, pos, window) if causal else None
        out = _attend(cfg, q, k, v, mask)
    elif impl == "mem":
        # roofline memory proxy: same HBM traffic as the flash kernel
        # (reads q,k,v; writes (B,S,H*hd)) with negligible flops
        K = k.shape[2]
        G = q.shape[2] // K
        out = (q + jnp.repeat(k + v, G, axis=2) * 1e-6).reshape(B, S, -1)
    elif causal and S > _Q_CHUNK and S % _Q_CHUNK == 0:
        out = _chunked_causal(cfg, q, k, v, positions, window)
    else:
        pos = positions[0] if positions.ndim > 1 else positions
        mask = _causal_window_mask(pos, pos, window) if causal else None
        out = _attend(cfg, q, k, v, mask)
    return dense_apply(p["o"], out)


def _chunked_causal(cfg, q, k, v, positions, window: int):
    """Scan over query chunks; scores are (B,K,G,Cq,T) per chunk only."""
    B, S, H, hd = q.shape
    C = _Q_CHUNK
    n = S // C
    pos = positions[0] if positions.ndim > 1 else positions
    qc = q.reshape(B, n, C, H, hd).transpose(1, 0, 2, 3, 4)
    posc = pos.reshape(n, C)

    def body(_, inp):
        qi, pi = inp
        mask = _causal_window_mask(pi, pos, window)
        return None, _attend(cfg, qi, k, v, mask)

    _, outs = jax.lax.scan(body, None, (qc, posc))
    return outs.transpose(1, 0, 2, 3).reshape(B, S, H * hd)


# ----------------------------------------------------------- cross attn -----
def cross_attention_apply(p, cfg, x, kv_cache):
    """Decoder cross-attention over precomputed encoder k/v (no RoPE/mask)."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = dense_apply(p["q"], x).reshape(B, S, H, hd)
    out = _attend(cfg, q, kv_cache["ck"], kv_cache["cv"], None)
    return dense_apply(p["o"], out)


def make_cross_kv(p, cfg, enc_out):
    B, T, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {"ck": dense_apply(p["k"], enc_out).reshape(B, T, K, hd),
            "cv": dense_apply(p["v"], enc_out).reshape(B, T, K, hd)}


# ------------------------------------------- distributed flash-decode -------
def _decode_attention_sharded(cfg, q, k_new, v_new, cache, pos, *,
                              mesh, data_axes, model_axis="model",
                              softcap: float = 0.0):
    """§Perf change #3: decode over a sequence-sharded KV cache WITHOUT
    gathering it. Each model shard holds W/|model| cache slots; it computes
    partial flash statistics (max, exp-sum, weighted values) over its slots
    and a 3-way psum combines them — wire per layer drops from O(cache)
    to O(B*H*hd). q/k_new/v_new are gathered over "model" at the shard_map
    boundary (~0.5 MB). The ring-slot update lands on whichever shard owns
    slot pos % W."""
    import numpy as np
    dp = tuple(a for a in data_axes if a in mesh.axis_names)
    B = q.shape[0]
    W_total = cache["k"].shape[1]
    msize = mesh.shape[model_axis]
    H, hd = q.shape[2], q.shape[3]
    K = k_new.shape[2]
    G = H // K
    W_loc = W_total // msize

    def body(qb, kn, vn, ck, cv, pos):
        b = qb.shape[0]                 # per-device batch block
        r = jax.lax.axis_index(model_axis)
        slot = jnp.mod(pos, W_total)
        lslot = slot - r * W_loc
        mine = jnp.logical_and(lslot >= 0, lslot < W_loc)
        li = jnp.clip(lslot, 0, W_loc - 1)
        ck_new = jax.lax.dynamic_update_slice(ck, kn, (0, li, 0, 0))
        cv_new = jax.lax.dynamic_update_slice(cv, vn, (0, li, 0, 0))
        ck = jnp.where(mine, ck_new, ck)
        cv = jnp.where(mine, cv_new, cv)

        gslots = r * W_loc + jnp.arange(W_loc)
        valid = jnp.logical_or(gslots <= pos, pos + 1 >= W_total)
        qg = qb.reshape(b, 1, K, G, hd)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, ck,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)            # (B,K,G,1,1)
        M = jax.lax.pmax(m, model_axis)
        p = jnp.exp(s - M)
        den = jax.lax.psum(jnp.sum(p, axis=-1), model_axis)
        num = jax.lax.psum(
            jnp.einsum("bkgqt,btkh->bqkgh", p.astype(cv.dtype), cv),
            model_axis)
        out = (num / den[:, :, :, :, None].transpose(0, 3, 1, 2, 4)) \
            .reshape(b, 1, H * hd)
        if (H * hd) % msize == 0:
            sz = (H * hd) // msize
            out = jax.lax.dynamic_slice_in_dim(out, r * sz, sz, 2)
        return out.astype(qb.dtype), ck, cv

    cache_spec_ = P(dp if B % _dp_size(mesh, dp) == 0 else None,
                    model_axis, None, None)
    rep4 = P(dp if B % _dp_size(mesh, dp) == 0 else None, None, None, None)
    # emit the output H*hd-sharded over "model" (a free slice of the
    # replicated value) so the o-proj contracts locally + tiny all-reduce;
    # leaving it replicated makes XLA's cost model gather the 2D o-proj
    # WEIGHT instead at small batch (observed: 63 MB f32 per layer at B=1)
    out_slice = model_axis if (H * hd) % msize == 0 else None
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep4, rep4, rep4, cache_spec_, cache_spec_, P()),
        out_specs=(P(cache_spec_[0], None, out_slice),
                   cache_spec_, cache_spec_),
        check_vma=False)
    out, ck, cv = fn(q, k_new, v_new, cache["k"], cache["v"], pos)
    return out, {"k": ck, "v": cv}


def _dp_size(mesh, dp):
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


# ----------------------------------------------------------- decode path ----
def cache_spec(cfg, batch: int, max_len: int, window: int):
    """Shape spec of one attention layer's KV cache."""
    W = min(window, max_len) if window > 0 else max_len
    K, hd = cfg.num_kv_heads, cfg.head_dim
    dt = dtype_of(cfg.dtype)
    return {"k": jax.ShapeDtypeStruct((batch, W, K, hd), dt),
            "v": jax.ShapeDtypeStruct((batch, W, K, hd), dt)}


def init_cache(cfg, batch: int, max_len: int, window: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, max_len, window))


def _write_token(c, t, slot, layer):
    """Write the new token ``t`` (B,1,K,hd) at ring slot ``slot`` of the
    cache ``c``: one layer's (B,W,K,hd), or with ``layer`` a stack of
    layers' (n,B,W,K,hd), updated in place at ``layer``."""
    if layer is None:
        return jax.lax.dynamic_update_slice(c, t, (0, slot, 0, 0))
    return jax.lax.dynamic_update_slice(c, t[None], (layer, 0, slot, 0, 0))


def decode_attention_apply(p, cfg, x, cache, pos, *, window: int = 0,
                           impl: str = "xla", mesh=None,
                           data_axes=("data",), layer=None):
    """One-token decode. x: (B,1,D); pos: scalar int32 (current index).

    Appends the new k/v at ring slot ``pos % W`` then attends over the cache.
    Keys stored post-RoPE at absolute positions (relative-correct under ring).
    With ``layer``, ``cache`` holds a stack of layers' caches (leading
    axis) and this is layer ``layer`` of it: the token is written into the
    stack in place and the whole stack comes back, so a scan that carries
    the stack never copies it. With a mesh, uses the distributed
    flash-decode path (sequence-sharded cache, psum-combined softmax stats
    — §Perf change #3), on the layer's slice written back whole.
    """
    B = x.shape[0]
    W = cache["k"].shape[-3]
    pvec = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x, x, pvec, pvec, rope=True)
    if mesh is not None and impl == "xla" \
            and W % mesh.shape.get("model", 1) == 0:
        one = cache if layer is None else jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False),
            cache)
        out, one = _decode_attention_sharded(
            cfg, q, k, v, one, pos, mesh=mesh, data_axes=data_axes,
            softcap=cfg.attn_logit_softcap)
        if layer is not None:
            one = jax.tree.map(
                lambda c, o: jax.lax.dynamic_update_index_in_dim(
                    c, o, layer, 0), cache, one)
        return dense_apply(p["o"], out), one
    slot = jnp.mod(pos, W)
    cache = {"k": _write_token(cache["k"], k, slot, layer),
             "v": _write_token(cache["v"], v, slot, layer)}

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.decode_attention import ops as da_ops
        # the kernel reads the layer of the carried stack in place
        out = da_ops.decode_attention(
            q[:, 0], cache["k"], cache["v"], pos=pos, window=W, layer=layer,
            softcap=cfg.attn_logit_softcap,
            interpret=(impl == "pallas_interpret"))[:, None]
        out = out.reshape(B, 1, -1)
    else:
        ck, cv = (c if layer is None else
                  jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
                  for c in (cache["k"], cache["v"]))
        slots = jnp.arange(W)
        valid = slots <= pos  # ring full once pos+1 >= W: all true anyway
        mask = jnp.broadcast_to(valid[None, :], (1, W))
        out = _attend(cfg, q, ck, cv, mask)
    return dense_apply(p["o"], out), cache
