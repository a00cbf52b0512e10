"""Decode FLOPs per generated token at the mix's mean cache position
(chip/flops.py) x tokens per second / peak bf16 FLOP/s. Prefill work is
not counted, so this is a lower bound on the share of the peak in use."""
from chip import flops


def read(rec):
    if not rec.get("peak") or not rec.get("rate"):
        return None
    mix = rec["mix"]
    B = int(mix["batch"])
    pos = int(mix["prompt"]) + int(mix["gen"]) // 2
    f = flops.decode_token_flops(rec["spec"], B, pos) / B
    return 100.0 * f * rec["rate"] / rec["peak"]["bf16_flops_per_s"]
