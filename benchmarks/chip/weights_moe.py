"""Seeded weights of a sparse-expert decoder (``flops_moe.MoeSpec``), made
by the benchmark and not by the program, as ``weights.py`` makes a dense
decoder's: drawn on the device in one jitted call, matrices in the
configuration's dtype, norm scales in float32, per-layer tensors stacked
on a leading layer axis and each expert matrix on an expert axis after
it. The router is drawn in the configuration's dtype and widened to
float32, the type the program holds it in. ``to_program`` renames that
layout into the program's parameter tree and checks it against the
program's own ``init`` shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chip.weights import _draw, np_rng, seed_key  # noqa: F401 (np_rng: the decode kind draws its prompts with it)


def shapes(spec) -> dict:
    L, D, V = spec.layers, spec.d_model, spec.vocab
    E, F, hd = spec.experts, spec.expert_ff, spec.head_dim
    Hq, Hk = spec.heads * hd, spec.kv_heads * hd
    return {
        "embed": (V, D),
        "final_norm": (D,),
        "lm_head": (D, V),
        "layers": {"norm1": (L, D), "wq": (L, D, Hq), "wk": (L, D, Hk),
                   "wv": (L, D, Hk), "q_norm": (L, hd), "k_norm": (L, hd),
                   "wo": (L, Hq, D), "norm2": (L, D), "router": (L, D, E),
                   "w_gate": (L, E, D, F), "w_up": (L, E, D, F),
                   "w_down": (L, E, F, D)},
    }


def make_weights(spec, seed: int):
    """All weights from ``seed``, drawn on the default device in one jit."""
    if spec.tie_embeddings:
        raise ValueError("a tied output head is not laid out here")
    tree = shapes(spec)
    dtype = jnp.dtype(spec.dtype)

    def build(key):
        out = {k: _draw(key, k, s, dtype) for k, s in tree.items()
               if k != "layers"}
        lay = {k: _draw(key, k, s, dtype)
               for k, s in tree["layers"].items()}
        lay["router"] = lay["router"].astype(jnp.float32)
        out["layers"] = lay
        return out

    return jax.jit(build)(seed_key(seed))


def to_program(canon, model):
    """The program's parameter tree holding ``canon``'s arrays (no copy).
    Raises if the program's ``init`` tree differs in structure or shapes."""
    lay = canon["layers"]
    block = {
        "norm1": {"scale": lay["norm1"]},
        "attn": {"q": {"w": lay["wq"]}, "k": {"w": lay["wk"]},
                 "v": {"w": lay["wv"]}, "o": {"w": lay["wo"]},
                 "q_norm": {"scale": lay["q_norm"]},
                 "k_norm": {"scale": lay["k_norm"]}},
        "norm2": {"scale": lay["norm2"]},
        "moe": {"router": {"w": lay["router"]}, "gate": lay["w_gate"],
                "up": lay["w_up"], "down": lay["w_down"]},
    }
    params = {"embed": {"tok": canon["embed"]},
              "stack": {"blocks": (block,), "tail": []},
              "final_norm": {"scale": canon["final_norm"]},
              "lm_head": {"w": canon["lm_head"]}}
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
