"""Unified model facade: one interface over all 10 assigned architectures.

``build_model(cfg, rt)`` returns a Model with:
  init(key) -> params
  loss(params, batch) -> (scalar, (metrics, aux))      [train objective]
  logits(params, batch) -> (logits, aux)
  prefill(params, batch, max_len) -> (cache, last_logits)
  decode_step(params, cache, tokens1) -> (cache, logits)   [serve_step]
  decode_step_routed(params, cache, tokens1) -> (cache, logits, routing)
  cache_spec(batch, max_len) -> ShapeDtypeStruct tree

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every
model *data* input of a workload cell (dry-run contract; modality frontends
are stubs: whisper gets frame embeddings, internvl2 gets patch embeddings).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.runtime import Runtime
from repro.models import transformer as tfm
from repro.models import encdec as ed
from repro.models.layers import (init_dense, dense_apply, norm_apply,
                                 embed_apply, logits_apply)
from repro.utils import dtype_of, fold_key


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """logits (B,T,V) f32; labels (B,T) i32 -> mean NLL."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def _collect_moe_aux(aux) -> jax.Array:
    vals = []
    for part in ("scanned", "tail"):
        for blk in aux.get(part, ()):
            if "moe_aux_loss" in blk:
                vals.append(jnp.mean(blk["moe_aux_loss"]))
    if not vals:
        return jnp.float32(0.0)
    return jnp.mean(jnp.stack(vals))


class Model:
    def __init__(self, cfg: ModelConfig, rt: Runtime = Runtime()):
        self.cfg = cfg
        self.rt = rt

    # ----------------------------------------------------------- params ---
    def init(self, key):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ed.init_encdec(key, cfg)
        params = tfm.init_lm(key, cfg)
        if cfg.family == "vlm":
            params["patch_proj"] = init_dense(
                fold_key(key, "patch_proj"), cfg.patch_embed_dim,
                cfg.d_model, dtype_of(cfg.dtype))
        return params

    def param_specs(self, key=None):
        return jax.eval_shape(self.init, jax.random.key(0))

    # ---------------------------------------------------------- forward ---
    def _prefix(self, params, batch):
        if self.cfg.family == "vlm" and "patches" in batch:
            return dense_apply(params["patch_proj"], batch["patches"])
        return None

    def logits(self, params, batch):
        cfg, rt = self.cfg, self.rt
        if cfg.family == "encdec":
            return ed.encdec_logits(params, cfg, batch, rt)
        return tfm.lm_logits(params, cfg, batch["tokens"], rt,
                             prefix_embeds=self._prefix(params, batch))

    def loss(self, params, batch):
        cfg = self.cfg
        logits, aux = self.logits(params, batch)
        if cfg.family == "vlm":
            P = logits.shape[1] - batch["labels"].shape[1]
            logits = logits[:, P:]
        ce = cross_entropy(logits, batch["labels"])
        moe_aux = _collect_moe_aux(aux)
        loss = ce + self.rt.aux_loss_coef * moe_aux
        metrics = {"loss": loss, "ce": ce, "moe_aux": moe_aux}
        return loss, (metrics, aux)

    # ------------------------------------------------------------ serve ---
    def cache_spec(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.family == "encdec":
            return ed.encdec_cache_spec(cfg, batch, max_len)
        return tfm.stack_cache_spec(cfg, batch, max_len)

    def prefill(self, params, batch, max_len: int):
        cfg, rt = self.cfg, self.rt
        if cfg.family == "encdec":
            return ed.encdec_prefill(params, cfg, batch, max_len, rt)
        tokens = batch["tokens"]
        x = embed_apply(params["embed"], tokens,
                        None if not cfg.learned_pos else
                        jnp.broadcast_to(
                            jnp.arange(tokens.shape[1], dtype=jnp.int32),
                            tokens.shape))
        prefix = self._prefix(params, batch)
        if prefix is not None:
            x = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, cache = tfm.stack_prefill(params["stack"], cfg, x, positions,
                                     max_len, rt)
        x = norm_apply(cfg, params["final_norm"], x)
        logits = logits_apply(params, cfg, x[:, -1:])
        return cache, logits

    def decode_step(self, params, cache, tokens1):
        """serve_step: one new token against the standing cache."""
        cache, logits, _ = self.decode_step_routed(params, cache, tokens1)
        return cache, logits

    def decode_step_routed(self, params, cache, tokens1):
        """:meth:`decode_step` plus the step's expert routing, a (3,)
        int32: token-expert pairs and distinct experts touched, each
        summed over the MoE layers, and the largest group of any layer
        (zeros for a model without experts)."""
        cfg, rt = self.cfg, self.rt
        if cfg.family == "encdec":
            cache, logits = ed.encdec_decode_step(params, cfg, cache,
                                                  tokens1, rt)
            return cache, logits, jnp.zeros((3,), jnp.int32)
        pos = cache["pos"]
        B = tokens1.shape[0]
        x = embed_apply(params["embed"], tokens1,
                        jnp.full((B, 1), pos, jnp.int32)
                        if cfg.learned_pos else None)
        x, cache, routing = tfm.stack_decode(params["stack"], cfg, x, cache,
                                             rt)
        x = norm_apply(cfg, params["final_norm"], x)
        return cache, logits_apply(params, cfg, x), routing


def build_model(cfg: ModelConfig, rt: Runtime = Runtime()) -> Model:
    return Model(cfg, rt)


# ------------------------------------------------------------ input specs ---
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model data input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32, dt = jnp.int32, dtype_of(cfg.dtype)
    tok = lambda s: jax.ShapeDtypeStruct(s, i32)

    if shape.kind == "decode":
        specs: Dict[str, Any] = {"tokens": tok((B, 1))}
        return specs

    if cfg.family == "encdec":
        specs = {
            "frames": jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model),
                                           dt),
            "tokens": tok((B, S)),
        }
    elif cfg.family == "vlm":
        P = cfg.num_patches
        specs = {
            "patches": jax.ShapeDtypeStruct((B, P, cfg.patch_embed_dim), dt),
            "tokens": tok((B, S - P)),
        }
    else:
        specs = {"tokens": tok((B, S))}

    if shape.kind == "train":
        specs["labels"] = tok(specs["tokens"].shape)
    return specs


def decode_cache_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Cache length for a decode cell: seq_len context + slack for the new
    token, rounded up to 256 so the sequence dim shards evenly over the
    "model" axis (ring caches clamp to the window internally)."""
    return -(-(shape.seq_len + 8) // 256) * 256
