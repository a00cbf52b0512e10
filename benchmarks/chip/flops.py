"""Operations and bytes that the algorithm needs, from a configuration's
shapes alone (a :class:`~chip.harness.ModelSpec`).

Only matrix products are counted, at 2 operations per multiply-add.
Attention counts the causal half of the score matrix (``causal=True``):
the pairs a query may see, not the masked square the program computes.
Norms, softmax, rotary positions and the optimizer's elementwise pass are
left out. So every count here is at most what the chip has to do, and a
share of a peak built on it cannot pass 100% because of the count.
A training step counts forward and backward (three forward passes), with
no recomputation.
"""
from __future__ import annotations


def layer_matmul_params(spec) -> int:
    D, F = spec.d_model, spec.d_ff
    Hq, Hk = spec.heads * spec.head_dim, spec.kv_heads * spec.head_dim
    return D * Hq + 2 * D * Hk + Hq * D + 3 * D * F


def _pairs(seq: int, causal: bool) -> int:
    return seq * (seq + 1) // 2 if causal else seq * seq


def attention_flops(spec, batch: int, seq: int, causal: bool = True) -> int:
    """Scores and weighted values of one layer over ``seq`` positions."""
    return 4 * batch * spec.heads * spec.head_dim * _pairs(seq, causal)


def layer_forward_flops(spec, batch: int, seq: int,
                        causal: bool = True) -> int:
    return (2 * batch * seq * layer_matmul_params(spec)
            + attention_flops(spec, batch, seq, causal))


def forward_flops(spec, batch: int, seq: int, head_positions: int,
                  causal: bool = True) -> int:
    """Whole model forward over ``seq`` positions (patches included), with
    the output head applied at ``head_positions`` positions per row."""
    f = spec.layers * layer_forward_flops(spec, batch, seq, causal)
    f += 2 * batch * head_positions * spec.d_model * spec.vocab
    if spec.patches:
        f += 2 * batch * spec.patches * spec.patch_dim * spec.d_model
    return f


def train_step_flops(spec, batch: int, seq: int, causal: bool = True) -> int:
    """One training step: forward and backward (3 x forward); the loss
    needs the head at the text positions only."""
    text = seq - spec.patches
    return 3 * forward_flops(spec, batch, seq, text, causal)


def decode_token_flops(spec, batch: int, pos: int) -> int:
    """One decode step of ``batch`` rows attending to ``pos + 1`` cached
    positions (the new token included)."""
    f = 2 * batch * (spec.layers * layer_matmul_params(spec)
                     + spec.d_model * spec.vocab)
    f += spec.layers * 4 * batch * spec.heads * spec.head_dim * (pos + 1)
    return f


def decode_token_bytes(spec, batch: int, pos: int, itemsize: int = 2) -> int:
    """Bytes a decode step must move at least once: every matrix weight,
    the output head, one embedding row per row of the batch, the KV cache
    up to ``pos`` read, and the new key and value written."""
    w = spec.layers * layer_matmul_params(spec) + spec.d_model * spec.vocab
    b = w * itemsize + batch * spec.d_model * itemsize
    kv = 2 * spec.layers * batch * spec.kv_heads * spec.head_dim * itemsize
    return b + kv * (pos + 1) + kv
