"""Commit-stream definitions: how model ``aux`` feeds the P-Shell (C3).

Per-layer activation checksums are the architectural commit records (the
Dromajo-comparison analogue of "PC + instruction metadata + writeback");
MoE router toggles and nan bits are the coverage coverpoints (C6).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.pshell import (ShellConfig, FifoSpec, fifo_push_many,
                               csr_accum, csr_write)


def _per_layer(aux: Dict[str, Any], key: str):
    """Collect per-layer leaves named ``key`` in layer order.
    Returns (L_present, ...) array or None."""
    rows = []
    scanned = aux.get("scanned", ())
    if scanned:
        present = [pos for pos in scanned if key in pos]
        if present:
            # (n_periods, P_len_present, ...) -> interleave period-major
            stk = jnp.stack([pos[key] for pos in scanned if key in pos],
                            axis=1)
            rows.append(stk.reshape((-1,) + stk.shape[2:]))
    for blk in aux.get("tail", ()):
        if key in blk:
            rows.append(blk[key][None])
    if not rows:
        return None
    return jnp.concatenate(rows, axis=0)


def layer_checksums(aux) -> jnp.ndarray:
    """(L, 2) f32 commit checksums in layer order (period-major interleave;
    exact order is stable per-arch, which is all the verifier needs)."""
    out = _per_layer(aux, "checksum")
    if out is None:
        raise ValueError("no 'checksum' taps in aux — enable 'commits' tap")
    return out


def moe_toggles(aux):
    scanned = aux.get("scanned", ())
    rows = []
    for pos in scanned:
        if "moe" in pos and "expert_toggles" in pos["moe"]:
            t = pos["moe"]["expert_toggles"]
            rows.append(t.reshape((-1,) + t.shape[2:])
                        if t.ndim > 2 else t)
    for blk in aux.get("tail", ()):
        if "moe" in blk and "expert_toggles" in blk["moe"]:
            rows.append(blk["moe"]["expert_toggles"][None])
    if not rows:
        return None
    return jnp.concatenate(rows, axis=0)          # (n_moe_layers, E)


def nan_bits(aux):
    return _per_layer(aux, "nan_bit")


def default_shell_config(cfg, sample_interval: int = 1,
                         commit_depth: int | None = None) -> ShellConfig:
    """Parameterize the shell for one architecture (the paper's
    'users parameterize the P-Shell' step).

    FIFO depths are sized PER GROUP: each fused window ingests
    ``sample_interval`` steps before the host drains, and every step pushes
    L commit rows, so the commits FIFO must hold >= sample_interval * L
    entries for lossless capture (interval=1 == cycle-accurate). Undersize
    it (``commit_depth``) and overflow is dropped deterministically with
    exact credit accounting — never blocking the device."""
    L = cfg.num_layers + cfg.encoder_layers
    depth = commit_depth or max(4, sample_interval) * max(L, 1)
    csrs = {
        "steps": jax.ShapeDtypeStruct((), jnp.int32),
        "loss_last": jax.ShapeDtypeStruct((), jnp.float32),
        "nan_bits": jax.ShapeDtypeStruct((max(L, 1),), jnp.int32),
    }
    fifos = {
        # payload: [layer_id, abs_mean, rms] (repro.utils.checksum)
        "commits": FifoSpec(depth=depth, shape=(3,), dtype=jnp.float32),
    }
    if cfg.num_experts:
        n_moe = sum(1 for _, f in cfg.layer_specs if f == "moe")
        csrs["expert_toggles"] = jax.ShapeDtypeStruct(
            (n_moe, cfg.num_experts), jnp.int32)
        fifos["router"] = FifoSpec(
            depth=max(4, sample_interval) * max(n_moe, 1),
            shape=(3,), dtype=jnp.float32)  # [layer, aux_loss, dropped_frac]
    return ShellConfig(csrs=csrs, fifos=fifos,
                       sample_interval=sample_interval)


def make_ingest(cfg):
    """ingest(shell, aux, metrics) -> shell. Pure and shape-static, so it is
    safe both as a per-step jit epilogue and as a lax.scan body stage inside
    a fused step group (no host callbacks, no data-dependent shapes; FIFO
    overflow is resolved with credit arithmetic, not control flow)."""
    def ingest(shell, aux, metrics):
        cks = layer_checksums(aux)                        # (L, 2)
        L = cks.shape[0]
        payload = jnp.concatenate(
            [jnp.arange(L, dtype=jnp.float32)[:, None],
             cks.astype(jnp.float32)], axis=1)
        shell = fifo_push_many(shell, "commits", payload)
        nb = nan_bits(aux)
        if nb is not None:
            pad = shell["csr"]["nan_bits"].shape[0] - nb.shape[0]
            bits = jnp.pad(nb.astype(jnp.int32), (0, pad))
            shell = csr_accum(shell, "nan_bits", bits, op="or")
        tg = moe_toggles(aux)
        if tg is not None and "expert_toggles" in shell["csr"]:
            shell = csr_accum(shell, "expert_toggles",
                              tg.astype(jnp.int32), op="or")
        if "loss" in metrics:
            shell = csr_write(shell, "loss_last",
                              metrics["loss"].astype(jnp.float32))
        shell = csr_accum(shell, "steps", jnp.int32(1), op="add")
        return shell

    return ingest
