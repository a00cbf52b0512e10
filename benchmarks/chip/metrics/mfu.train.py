"""DUT model FLOPs per train step (chip/flops.py, forward + backward,
no recomputation) x verified steps per second / peak bf16 FLOP/s."""
from chip import flops


def read(rec):
    if not rec.get("peak") or not rec.get("rate"):
        return None
    mix = rec["mix"]
    f = flops.train_step_flops(rec["spec"], int(mix["batch"]),
                               int(mix["seq"]))
    return 100.0 * f * rec["rate"] / rec["peak"]["bf16_flops_per_s"]
