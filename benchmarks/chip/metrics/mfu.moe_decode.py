"""Active FLOPs per generated token of a sparse-expert decoder at the
mix's mean cache position (chip/flops_moe.py: attention, router and the
``top_k`` routed experts) x tokens per second / peak bf16 FLOP/s. Prefill
work is not counted, so this is a lower bound on the share of the whole
step's peak in use."""
from chip import flops_moe


def read(rec):
    spec = rec.get("moe_spec")
    if not spec or not rec.get("peak") or not rec.get("rate"):
        return None
    mix = rec["mix"]
    B = int(mix["batch"])
    pos = int(mix["prompt"]) + int(mix["gen"]) // 2
    f = flops_moe.decode_token_flops(spec, B, pos) / B
    return 100.0 * f * rec["rate"] / rec["peak"]["bf16_flops_per_s"]
