"""Traffic kind ``moe_decode``: the ``decode`` kind's boards, back to back
on one slot, on a sparse-expert decoder (Qwen3-MoE).

The boards, the window, the accounting and the draw of the board to check
are ``decode``'s: each board copies the weights, prefills a
``prompt``-token prompt of ``batch`` rows inside the window and decodes
``gen`` greedy tokens through the cache in windows of ``window_tokens``.
What is compared differs: ``logit_gap_mean``, the mean over every served
token of the drawn board of the gap by which its reference logit lies
below the reference's best. ``decode``'s ``logit_gap`` (the largest such
gap) is kept in the detail only: top-k routing flips an expert wherever
two router probabilities lie closer than bfloat16 rounding (about 1.4% of
token-layers at this cell's widths), and the worst token of a board then
lies as far below the best as under the float8 control, so no limit on
the largest gap separates the two (PERF.md, section 2).
What is this family's own: the program's configuration (``family="moe"``,
every layer attention then routed experts, q/k head norms), the seeded
weights (``weights_moe.py``), the plain reference (``reference_moe.py``)
and the record of the program's routing counter (``moe.routing`` in the
farm telemetry), which the per-layer readers use.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from chip import flops_moe, reference_moe, weights_moe
from chip.harness import HarnessError, kind_module

# a private copy of the decode kind's module (``kind_module`` executes the
# file anew), whose boards draw this family's weights
_decode = kind_module("decode", Path(__file__).resolve().parents[1])
_decode.weights = weights_moe


def program_config(spec):
    """The program's ``ModelConfig`` for a :class:`flops_moe.MoeSpec`."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=spec.name, family="moe", num_layers=spec.layers,
        d_model=spec.d_model, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, head_dim=spec.head_dim, d_ff=0,
        vocab_size=spec.vocab, layer_pattern=(("attn", "moe"),),
        num_experts=spec.experts, num_experts_per_tok=spec.top_k,
        moe_d_ff=spec.expert_ff, use_qk_norm=True,
        rope_theta=spec.rope_theta, norm_eps=spec.norm_eps,
        tie_embeddings=spec.tie_embeddings, dtype=spec.dtype)


class Kind(_decode.Kind):
    def __init__(self, cell, seed, fault=None):
        super().__init__(cell, seed, fault=fault)
        doc = cell.config
        if not (doc["norm_topk_prob"] and int(doc["decoder_sparse_step"]) == 1
                and not doc["mlp_only_layers"]
                and doc["hidden_act"] == "silu"):
            raise HarnessError("the program runs every layer sparse with "
                               "renormalised top-k SwiGLU experts")
        self.spec = flops_moe.moe_spec(doc)
        self.cfg = program_config(self.spec)

    def account(self, rec, report, mgr, t_start, t_end) -> dict:
        out = super().account(rec, report, mgr, t_start, t_end)
        routing = mgr.telemetry.report().get("moe", {}).get("routing")
        self.routing = routing if routing and routing["steps"] else None
        out.update(moe_spec=self.spec, moe_routing=self.routing)
        return out

    def token_gaps(self, seqs, quant=None):
        """Per sequence, the gap of every served token (``decode``'s
        ``gaps``, before its maximum): below the reference's best, of the
        served token (``quant=None``) or of the control's first choice."""
        P = int(self.mix["prompt"])
        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, served in seqs:
                toks = jnp.asarray(np.concatenate([prompt, served])[None])
                ref = np.asarray(reference_moe.next_token_logits(
                    self.canon, toks, spec=self.spec, start=P - 1),
                    np.float64)[0]                            # (gen, V)
                pick = served
                if quant is not None:
                    pick = np.asarray(jnp.argmax(
                        reference_moe.next_token_logits(
                            self.canon, toks, spec=self.spec, start=P - 1,
                            quant=quant)[0], axis=-1))
                out.append(ref.max(axis=-1) - ref[np.arange(len(pick)), pick])
        return out

    def check(self, rec) -> dict:
        """``None`` (not correct) when no board finished in the window."""
        gaps = self.token_gaps(self.sample())
        self.largest = max((float(g.max()) for g in gaps), default=None)
        return {"logit_gap_mean": _mean(gaps)}

    def control(self) -> dict:
        return {"logit_gap_mean": _mean(self.token_gaps(self.sample(),
                                                        quant="fp8"))}

    def detail(self) -> dict:
        return {"moe_routing": getattr(self, "routing", None),
                "logit_gap": getattr(self, "largest", None)}


def _mean(gaps):
    return float(np.concatenate(gaps).mean()) if gaps else None
