"""Shapes, operations and bytes of a sparse-expert decoder (Qwen3-MoE:
every layer GQA attention with q/k head norms, then a routed SwiGLU
expert layer with no shared expert), from its configuration alone.

Counted as in ``flops.py``: matrix products only, 2 operations per
multiply-add, attention over the causal pairs. An expert layer does the
work of the ``top_k`` experts each token is routed to, plus the router;
the experts a token is not routed to do none. A decode step must read
the weights of the experts its tokens touch, which the program's routing
counter (``moe.routing``) says, and no others.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class MoeSpec:
    """A sparse-expert decoder's shapes, read from a configuration file."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    expert_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    dtype: str
    tie_embeddings: bool = False


def moe_spec(doc: dict) -> MoeSpec:
    return MoeSpec(
        name=doc["name"], layers=int(doc["num_hidden_layers"]),
        d_model=int(doc["hidden_size"]),
        heads=int(doc["num_attention_heads"]),
        kv_heads=int(doc["num_key_value_heads"]),
        head_dim=int(doc["head_dim"]), experts=int(doc["num_experts"]),
        top_k=int(doc["num_experts_per_tok"]),
        expert_ff=int(doc["moe_intermediate_size"]),
        vocab=int(doc["vocab_size"]), rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        dtype=str(doc["torch_dtype"]),
        tie_embeddings=bool(doc["tie_word_embeddings"]))


def attention_params(spec) -> int:
    D = spec.d_model
    Hq, Hk = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    return D * Hq + 2 * D * Hk + Hq * D


def router_params(spec) -> int:
    return spec.d_model * spec.experts


def expert_params(spec) -> int:
    """One expert's SwiGLU: gate, up and down."""
    return 3 * spec.d_model * spec.expert_ff


def active_layer_params(spec) -> int:
    """Matrix parameters one token multiplies in a layer."""
    return (attention_params(spec) + router_params(spec)
            + spec.top_k * expert_params(spec))


def _attend(spec, batch: int, pairs: int) -> int:
    return 4 * batch * spec.heads * spec.head_dim * pairs


def prefill_flops(spec, batch: int, prompt: int) -> int:
    """A prefill of ``batch`` prompts of ``prompt`` tokens: every layer
    over every position, the output head at the last position only."""
    f = spec.layers * (2 * batch * prompt * active_layer_params(spec)
                       + _attend(spec, batch, prompt * (prompt + 1) // 2))
    return f + 2 * batch * spec.d_model * spec.vocab


def decode_token_flops(spec, batch: int, pos: int) -> int:
    """One decode step of ``batch`` rows attending to ``pos + 1`` cached
    positions (the new token included)."""
    f = 2 * batch * (spec.layers * active_layer_params(spec)
                     + spec.d_model * spec.vocab)
    return f + spec.layers * _attend(spec, batch, pos + 1)


def decode_token_bytes(spec, batch: int, pos: int, touched: float,
                       itemsize: int = 2) -> float:
    """Bytes a decode step must move at least once: per layer the
    attention weights, the router (float32) and the weights of the
    ``touched`` distinct experts its tokens are routed to; the output
    head; one embedding row per row of the batch; the KV cache up to
    ``pos`` read and the new key and value written."""
    layer = ((attention_params(spec) + touched * expert_params(spec))
             * itemsize + router_params(spec) * 4)
    b = (spec.layers * layer + spec.d_model * spec.vocab * itemsize
         + batch * spec.d_model * itemsize)
    kv = 2 * spec.layers * batch * spec.kv_heads * spec.head_dim * itemsize
    return b + kv * (pos + 1) + kv
