"""Small shared utilities."""
from __future__ import annotations

import os
import pathlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def checksum(x: jax.Array) -> jax.Array:
    """Cheap commit-stream checksum of a tensor: (mean|x|, rms) in f32.

    Used by the P-Shell commit stream (DESIGN.md C3) and the subsystem
    verify boards: tolerant cross-impl comparison DUT-vs-oracle, and
    bitwise comparison DUT-vs-DUT. Both statistics are positive and of
    the activation's own scale, so a relative comparison is well
    conditioned; a signed mean sits near zero for normalized activations
    and turns rounding differences between two compiled programs into a
    large relative error.
    """
    xf = x.astype(jnp.float32)
    return jnp.stack([jnp.mean(jnp.abs(xf)),
                      jnp.sqrt(jnp.mean(jnp.square(xf)))])


def has_nan_bit(x: jax.Array) -> jax.Array:
    """Single-bit 'activation overflow' coverage toggle (f32 nan/inf)."""
    xf = x.astype(jnp.float32)
    return jnp.any(~jnp.isfinite(xf))


def tree_bytes(tree) -> int:
    return sum(np.prod(l.shape) * l.dtype.itemsize
               for l in jax.tree.leaves(tree) if hasattr(l, "shape"))


def tree_count(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree)
               if hasattr(l, "shape"))


def fold_key(key: jax.Array, *names: str) -> jax.Array:
    """Fold each name into ``key``. Names go in by CRC-32, not ``hash()``:
    Python salts string hashes per process, which would make a seeded
    init differ from one run to the next."""
    for n in names:
        key = jax.random.fold_in(key, zlib.crc32(n.encode()) & 0x7FFFFFFF)
    return key


#: the checkout's fixed compile-cache directory (listed in .gitignore)
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory. A set ``JAX_COMPILATION_CACHE_DIR`` wins and is
    left alone (JAX reads it itself); otherwise the cache lives at the
    checkout's fixed ``.jax_cache/``. A later run finds the entries only
    at the same path, so it is never temporary, per-process or
    time-based. Called from ``main``, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
