"""Plain reference of the dense decoders the benchmark runs.

Written from the published description of a Llama/Qwen2-style decoder
(RMSNorm, grouped-query attention with rotary positions applied to
split halves, a SwiGLU MLP, an untied or tied output head), in
``jax.numpy`` and float32 at ``HIGHEST`` matmul precision, with no cache,
kernel or batching trick. It imports nothing of the program and takes
only the benchmark's own weights (``weights.py``) and inputs.

``quant`` selects the control: ``None`` is the reference; ``"fp8"``
rounds both operands of every matrix product to float8 (e4m3, one scale
per tensor), the step below the bfloat16 the configurations state.

Departures of the program from the published models are mirrored here
and listed in each configuration file under ``departures``: no q/k/v
bias, a stubbed vision frontend (one dense projection of patch
embeddings) for the VLM.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def mm(eq, a, b, quant=None):
    a = _q(a.astype(jnp.float32), quant)
    b = _q(b.astype(jnp.float32), quant)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """x (B, S, H, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(lp, x, positions, spec, quant=None):
    """One decoder layer on float32 activations ``x`` (B, S, D)."""
    B, S, _ = x.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    h = rmsnorm(x, lp["norm1"], spec.norm_eps)
    q = mm("bsd,de->bse", h, lp["wq"], quant).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, lp["wk"], quant).reshape(B, S, K, hd)
    v = mm("bsd,de->bse", h, lp["wv"], quant).reshape(B, S, K, hd)
    q, k = rope(q, positions, spec.rope_theta), rope(k, positions,
                                                     spec.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, S, H * hd)
    x = x + mm("bse,ed->bsd", o, lp["wo"], quant)
    h = rmsnorm(x, lp["norm2"], spec.norm_eps)
    g = mm("bsd,df->bsf", h, lp["w_gate"], quant)
    u = mm("bsd,df->bsf", h, lp["w_up"], quant)
    return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["w_down"], quant)


def layer(w, i):
    return {k: v[i] for k, v in w["layers"].items()}


def embed(w, tokens, patches=None, quant=None):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    if patches is not None:
        pre = mm("bpc,cd->bpd", patches, w["patch_proj"], quant)
        x = jnp.concatenate([pre, x], axis=1)
    return x


def head(w, spec, h, quant=None):
    h = rmsnorm(h, w["final_norm"], spec.norm_eps)
    wt = w["embed"].T if spec.tie_embeddings else w["lm_head"]
    return mm("bsd,dv->bsv", h, wt, quant)


def hidden(w, spec, tokens, patches=None, quant=None):
    x = embed(w, tokens, patches, quant)
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for i in range(spec.layers):
        x = block(layer(w, i), x, pos, spec, quant)
    return x


def loss(w, spec, batch, quant=None):
    """Mean next-token cross-entropy over the text positions."""
    h = hidden(w, spec, batch["tokens"], batch.get("patches"), quant)
    h = h[:, h.shape[1] - batch["labels"].shape[1]:]
    logits = head(w, spec, h, quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


# ------------------------------------------------------------- training ---
#: the optimizer a configuration file states (its ``optimizer`` block)
Opt = collections.namedtuple(
    "Opt", "lr b1 b2 eps weight_decay grad_clip warmup_steps")


@functools.partial(jax.jit, static_argnames=("spec", "opt", "quant"))
def train_step(w, m, v, count, batch, *, spec, opt, quant=None):
    """One AdamW step as the configuration's ``optimizer`` block states it:
    global-norm clipping, bias-corrected moments in float32, decoupled
    weight decay on matrices, linear warm-up; parameters kept in their
    served dtype. Returns (loss, w, m, v)."""
    lval, g = jax.value_and_grad(
        lambda p: loss(p, spec, batch, quant))(w)
    gl = jax.tree.leaves(g)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in gl))
    clip = jnp.minimum(1.0, opt.grad_clip / (gnorm + 1e-9))
    lr = opt.lr * jnp.minimum(1.0, (count + 1) / max(opt.warmup_steps, 1))
    t = (count + 1).astype(jnp.float32)
    c1, c2 = 1.0 - opt.b1 ** t, 1.0 - opt.b2 ** t

    def upd(p, gi, mi, vi):
        gi = gi.astype(jnp.float32) * clip
        mi = opt.b1 * mi + (1 - opt.b1) * gi
        vi = opt.b2 * vi + (1 - opt.b2) * gi * gi
        d = (mi / c1) / (jnp.sqrt(vi / c2) + opt.eps)
        pf = p.astype(jnp.float32)
        if p.ndim >= 2:
            d = d + opt.weight_decay * pf
        return (pf - lr * d).astype(p.dtype), mi, vi

    out = jax.tree.map(upd, w, g, m, v)
    treedef = jax.tree.structure(w)
    leaves = treedef.flatten_up_to(out)
    unz = [treedef.unflatten([x[j] for x in leaves]) for j in range(3)]
    return lval, unz[0], unz[1], unz[2]


# -------------------------------------------------------------- serving ---
@functools.partial(jax.jit, static_argnames=("spec", "quant", "start"))
def next_token_logits(w, tokens, *, spec, start, quant=None):
    """Logits predicting tokens[:, start+1:], from a full causal forward
    over ``tokens`` (B, T) with no cache (positions ``start .. T-2``)."""
    h = hidden(w, spec, tokens, None, quant)
    return head(w, spec, h[:, start:-1], quant)


# ------------------------------------------------------- layer by layer ---
@functools.partial(jax.jit, static_argnames=("spec", "quant", "act_dtype"))
def layer_checksums(w, xs, positions, *, spec, act_dtype, quant=None):
    """Per-layer output checksums (mean |y|, rms y) of the layer chain fed
    ``xs`` (N, B, S, D). Activations between layers are rounded to
    ``act_dtype``, the type the configuration stores them in. Returns
    (N, layers, 2) float32."""
    def one(x):
        rows = []
        x = x.astype(jnp.float32)
        for i in range(spec.layers):
            y = block(layer(w, i), x, positions, spec, quant)
            y = y.astype(act_dtype).astype(jnp.float32)
            rows.append(jnp.stack([jnp.mean(jnp.abs(y)),
                                   jnp.sqrt(jnp.mean(y * y))]))
            x = y
        return jnp.stack(rows)
    return jax.lax.map(one, xs)
