"""GPipe-style pipeline parallelism over a "pipe" mesh axis.

Layer periods are split contiguously across stages (the stacked period dim
is sharded over "pipe"); microbatches stream through a fill-drain schedule
implemented with lax.scan + collective_permute inside shard_map. Reverse-mode
AD through collective_permute yields the mirrored backward pipeline, so the
same function trains.

Bubble fraction is the GPipe (S-1)/(T+S-1); the §Perf log treats microbatch
count as a knob. PP composes with TP/FSDP by carving "pipe" out of the data
axis (e.g. (4, 4, 16) = pipe x data x model from one 256-chip pod).

Restrictions (checked): homogeneous layer pattern, num_layers divisible by
n_stages, embed/head replicated across stages (computed outside the loop).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.layers import norm_apply, logits_apply, embed_apply
from repro.models.model import cross_entropy
from repro.models.runtime import Runtime


def _check(cfg, n_stages: int):
    if len(cfg.layer_pattern) != 1:
        raise ValueError("PP requires a homogeneous layer pattern")
    if cfg.num_layers % n_stages:
        raise ValueError("num_layers must divide by n_stages")


def make_pp_loss(cfg, mesh, n_stages: int, n_micro: int,
                 pipe_axis: str = "pipe", rt: Runtime = None):
    """Returns loss_fn(params, batch) running the stack as a GPipe pipeline.
    Stage s owns periods [s*L/S, (s+1)*L/S); the stacked period dim of the
    block params is sharded over ``pipe_axis``."""
    _check(cfg, n_stages)
    rt = rt or Runtime()
    spec = cfg.layer_pattern[0]

    def stage_fn(blocks_stage, x, positions):
        def body(x, p):
            y, _ = tfm.block_apply(p, cfg, spec, x, positions, rt)
            return y, None
        x, _ = jax.lax.scan(body, x, blocks_stage)
        return x

    def pipeline(blocks, x_mb, positions):
        """Inside shard_map, manual over pipe_axis.
        blocks: this stage's (periods/S, ...) stack; x_mb: (n_micro, mb, S, D)
        (meaningful input at stage 0). Returns (n_micro, mb, S, D) final
        hidden, valid on every stage (psum-broadcast from the last)."""
        stage = jax.lax.axis_index(pipe_axis)
        T = n_micro + n_stages - 1
        mbshape = x_mb.shape[1:]

        def tick(carry, t):
            prev_act, outputs = carry
            mb_idx = t - stage
            active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
            idx = jnp.clip(jnp.where(stage == 0, t, mb_idx), 0, n_micro - 1)
            x_in = jnp.where(stage == 0, x_mb[idx], prev_act)
            y = stage_fn(blocks, x_in, positions)
            y = jnp.where(active, y, jnp.zeros_like(y))
            is_last = stage == n_stages - 1
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(jnp.logical_and(active, is_last), y,
                          jax.lax.dynamic_index_in_dim(outputs, idx, 0,
                                                       keepdims=False)),
                idx, 0)
            nxt = jax.lax.ppermute(
                y, pipe_axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            return (nxt, outputs), None

        init = (jnp.zeros(mbshape, x_mb.dtype),
                jnp.zeros((n_micro,) + mbshape, x_mb.dtype))
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(T))
        # broadcast the last stage's outputs to every stage
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, pipe_axis)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        mb = B // n_micro
        x = embed_apply(params["embed"], tokens)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
        x_mb = x.reshape(n_micro, mb, S, -1)

        blocks = params["stack"]["blocks"][0]
        run = jax.shard_map(
            functools.partial(pipeline),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(pipe_axis), blocks),
                      P(), P()),
            out_specs=P(),
            axis_names={pipe_axis},
            check_vma=False)
        h = run(blocks, x_mb, positions).reshape(B, S, -1)
        h = norm_apply(cfg, params["final_norm"], h)
        logits = logits_apply(params, cfg, h)
        return cross_entropy(logits, labels)

    return loss_fn
