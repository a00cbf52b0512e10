"""Seeded weights and inputs, made by the benchmark and not by the program.

``make_weights(spec, seed)`` draws every weight of a dense decoder (and
the patch projection of a VLM) on the device in one jitted call, in the
type it is served in: matrices in the configuration's dtype, norm scales
in float32. The layout is the benchmark's own (``canonical``): per-layer
tensors stacked on a leading layer axis. ``to_program`` renames that
layout into the program's parameter tree, and checks the result against
the program's own ``init`` shapes, so a program whose layout changes
fails loudly here instead of being fed misplaced weights.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_up",
              "w_down")


def seed_key(seed: int):
    """A PRNG key for any non-negative whole number: the low 31 bits seed
    the key and the rest is folded in, so seeds past 2**31 stay distinct."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def sub_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def np_rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(name.encode())])


def shapes(spec) -> dict:
    """Canonical leaf shapes of a :class:`~chip.harness.ModelSpec`."""
    L, D, F, V = spec.layers, spec.d_model, spec.d_ff, spec.vocab
    Hq, Hk = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    out = {
        "embed": (V, D),
        "final_norm": (D,),
        "layers": {"norm1": (L, D), "wq": (L, D, Hq), "wk": (L, D, Hk),
                   "wv": (L, D, Hk), "wo": (L, Hq, D), "norm2": (L, D),
                   "w_gate": (L, D, F), "w_up": (L, D, F),
                   "w_down": (L, F, D)},
    }
    if not spec.tie_embeddings:
        out["lm_head"] = (D, V)
    if spec.patches:
        out["patch_proj"] = (spec.patch_dim, D)
    return out


def _draw(key, name, shape, dtype):
    k = sub_key(key, name)
    if name.endswith("norm") or name in ("norm1", "norm2"):
        # norm scales around 1 (not exactly 1), so a dropped scale shows
        return (1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32))
    if name == "embed":
        return jax.random.normal(k, shape, jnp.float32).astype(dtype)
    fan_in = shape[-2]
    w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
    return w.astype(dtype)


def make_weights(spec, seed: int):
    """All weights from ``seed``, drawn on the default device in one jit."""
    tree = shapes(spec)
    dtype = jnp.dtype(spec.dtype)

    def build(key):
        out = {}
        for name, shp in tree.items():
            if name == "layers":
                out["layers"] = {k: _draw(key, k, s, dtype)
                                 for k, s in shp.items()}
            else:
                out[name] = _draw(key, name, shp, dtype)
        return out

    return jax.jit(build)(seed_key(seed))


def to_program(canon, model):
    """The program's parameter tree holding ``canon``'s arrays (no copy).
    Raises if the program's ``init`` tree differs in structure or shapes."""
    lay = canon["layers"]
    block = {
        "norm1": {"scale": lay["norm1"]},
        "attn": {"q": {"w": lay["wq"]}, "k": {"w": lay["wk"]},
                 "v": {"w": lay["wv"]}, "o": {"w": lay["wo"]}},
        "norm2": {"scale": lay["norm2"]},
        "mlp": {"gate": {"w": lay["w_gate"]}, "up": {"w": lay["w_up"]},
                "down": {"w": lay["w_down"]}},
    }
    params = {"embed": {"tok": canon["embed"]},
              "stack": {"blocks": (block,), "tail": []},
              "final_norm": {"scale": canon["final_norm"]}}
    if "lm_head" in canon:
        params["lm_head"] = {"w": canon["lm_head"]}
    if "patch_proj" in canon:
        params["patch_proj"] = {"w": canon["patch_proj"]}
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("the program's parameter tree changed: "
                         f"{jax.tree.structure(want)}")
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if w.shape != g.shape or w.dtype != g.dtype:
            raise ValueError(f"program leaf {w} != benchmark leaf {g}")
    return params


def from_program(params) -> dict:
    """Inverse of :func:`to_program`: canonical names over a program tree."""
    blk = params["stack"]["blocks"][0]
    out = {"embed": params["embed"]["tok"],
           "final_norm": params["final_norm"]["scale"],
           "layers": {"norm1": blk["norm1"]["scale"],
                      "wq": blk["attn"]["q"]["w"],
                      "wk": blk["attn"]["k"]["w"],
                      "wv": blk["attn"]["v"]["w"],
                      "wo": blk["attn"]["o"]["w"],
                      "norm2": blk["norm2"]["scale"],
                      "w_gate": blk["mlp"]["gate"]["w"],
                      "w_up": blk["mlp"]["up"]["w"],
                      "w_down": blk["mlp"]["down"]["w"]}}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]["w"]
    if "patch_proj" in params:
        out["patch_proj"] = params["patch_proj"]["w"]
    return out


def leaf_labels(canon) -> list:
    """Leaf names in :func:`leaf_norms` order: keys sorted, stacked layer
    leaves split per layer (``layers.wq[3]``)."""
    out = []
    for k in sorted(canon):
        if k == "layers":
            for lk in LAYER_KEYS:
                n = canon[k][lk].shape[0]
                out.extend(f"layers.{lk}[{i}]" for i in range(n))
        else:
            out.append(k)
    return out


def norms(tree, minus=None):
    """Per-leaf L2 norms (float32 math) of a canonical ``tree`` (or of
    ``tree - minus``), in :func:`leaf_labels` order, as one device vector.
    Traceable: callers jit it once."""
    if minus is not None:
        tree = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                            - b.astype(jnp.float32), tree, minus)
    vals = []
    for k in sorted(tree):
        if k == "layers":
            for lk in LAYER_KEYS:
                a = tree[k][lk].astype(jnp.float32)
                vals.append(jnp.sqrt(jnp.sum(
                    jnp.square(a), axis=tuple(range(1, a.ndim)))))
        else:
            vals.append(jnp.sqrt(jnp.sum(jnp.square(
                tree[k].astype(jnp.float32))))[None])
    return jnp.concatenate(vals)


_norms = jax.jit(norms)


def leaf_norms(canon, minus=None) -> np.ndarray:
    """:func:`norms`, computed on the device and fetched."""
    return np.asarray(_norms(canon, minus), np.float64)
