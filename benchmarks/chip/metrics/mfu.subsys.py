"""One layer's forward FLOPs per layer-step (chip/flops.py) x layer-steps
per second / peak bf16 FLOP/s."""
from chip import flops


def read(rec):
    if not rec.get("peak") or not rec.get("rate"):
        return None
    mix = rec["mix"]
    f = flops.layer_forward_flops(rec["spec"], int(mix["batch"]),
                                  int(mix["seq"]))
    return 100.0 * f * rec["rate"] / rec["peak"]["bf16_flops_per_s"]
