"""Plain reference of the sparse-expert decoder the benchmark runs
(Qwen3-MoE), beside ``reference.py``'s dense one and built from its
parts (``mm``, ``rmsnorm``, ``rope``, ``layer``, ``head``): float32 at ``HIGHEST`` matmul
precision, no cache, kernel or batching trick, and nothing of the
program.

Written from the published description (Qwen3 Technical Report,
arXiv:2505.09388; the ``qwen3_moe`` config): each layer is RMSNorm,
grouped-query attention whose queries and keys are RMS-normalised per
head before rotary positions, then RMSNorm and a routed SwiGLU expert
layer. The router's softmax over all experts keeps each token's
``top_k`` largest probabilities, renormalised to sum to 1
(``norm_topk_prob``); there is no shared expert. The expert layer is
computed densely: every expert on every token, in blocks of experts, each
expert's output weighted by the token's combine weight (zero where the
expert was not chosen). No sort, no gather, no capacity.

``quant="fp8"`` is the control: both operands of every matrix product
rounded to float8 (``reference.mm``), the step below bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chip.reference import head, layer, mm, rmsnorm, rope

#: experts per block of the dense expert layer (a divisor of the count is used)
EXPERT_BLOCK = 16


def attention(lp, x, positions, spec, quant=None):
    B, S, _ = x.shape
    H, K, hd = spec.heads, spec.kv_heads, spec.head_dim
    h = rmsnorm(x, lp["norm1"], spec.norm_eps)
    q = mm("bsd,de->bse", h, lp["wq"], quant).reshape(B, S, H, hd)
    k = mm("bsd,de->bse", h, lp["wk"], quant).reshape(B, S, K, hd)
    v = mm("bsd,de->bse", h, lp["wv"], quant).reshape(B, S, K, hd)
    q = rmsnorm(q, lp["q_norm"], spec.norm_eps)
    k = rmsnorm(k, lp["k_norm"], spec.norm_eps)
    q, k = rope(q, positions, spec.rope_theta), rope(k, positions,
                                                     spec.rope_theta)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
    causal = positions[:, None, :, None] >= positions[:, None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bhqk,bkhd->bqhd", p, v, quant).reshape(B, S, H * hd)
    return x + mm("bse,ed->bsd", o, lp["wo"], quant)


def combine_weights(probs, k: int):
    """(T, E) router probabilities -> (T, E) combine weights: each row's
    ``k`` largest kept and renormalised, every other entry 0."""
    rest, top = probs, None
    for _ in range(k):
        top = jnp.max(rest, axis=-1, keepdims=True)
        rest = jnp.where(rest >= top, -jnp.inf, rest)
    keep = jnp.where(probs >= top, probs, 0.0)
    return keep / jnp.sum(keep, axis=-1, keepdims=True)


def experts(lp, x, spec, quant=None):
    """The routed expert layer on float32 ``x`` (B, S, D), residual added."""
    B, S, D = x.shape
    h = rmsnorm(x, lp["norm2"], spec.norm_eps).reshape(B * S, D)
    probs = jax.nn.softmax(mm("td,de->te", h, lp["router"], quant), axis=-1)
    c = combine_weights(probs, spec.top_k)                  # (T, E)
    eb = math.gcd(EXPERT_BLOCK, spec.experts)

    def blocks(a):
        return a.reshape((spec.experts // eb, eb) + a.shape[1:])

    def body(y, blk):
        wg, wu, wd, cb = blk
        g = mm("td,edf->tef", h, wg, quant)
        u = mm("td,edf->tef", h, wu, quant)
        a = jax.nn.silu(g) * u * cb.T[..., None]
        return y + mm("tef,efd->td", a, wd, quant), None

    y, _ = jax.lax.scan(body, jnp.zeros((B * S, D), jnp.float32),
                        (blocks(lp["w_gate"]), blocks(lp["w_up"]),
                         blocks(lp["w_down"]), blocks(c.T)))
    return x + y.reshape(B, S, D)


def hidden(w, spec, tokens, quant=None):
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    B, S, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    for i in range(spec.layers):
        lp = layer(w, i)
        x = experts(lp, attention(lp, x, pos, spec, quant), spec, quant)
    return x


@functools.partial(jax.jit, static_argnames=("spec", "quant", "start"))
def next_token_logits(w, tokens, *, spec, start, quant=None):
    """Logits predicting tokens[:, start+1:], from a full causal forward
    over ``tokens`` (B, T) with no cache (positions ``start .. T-2``)."""
    return head(w, spec, hidden(w, spec, tokens, quant)[:, start:-1], quant)
