"""Share of the HBM roofline in the decode windows: the bytes each token
step must move (chip/flops.py: weights, head, KV cache read at the mix's
mean position) over peak HBM bandwidth, divided by the measured device
time of the decode-window program (jit name ``engine``) per step."""
from chip import flops


def read(rec):
    t, peak = rec.get("trace"), rec.get("peak")
    if not t or not peak:
        return None
    secs = sum(v for k, v in t["modules"].items() if k.endswith("engine"))
    calls = sum(v for k, v in t["module_counts"].items()
                if k.endswith("engine"))
    if secs <= 0 or calls <= 0:
        return None
    mix = rec["mix"]
    pos = int(mix["prompt"]) + int(mix["gen"]) // 2
    steps = calls * int(mix["window_tokens"])
    need = steps * flops.decode_token_bytes(rec["spec"], int(mix["batch"]),
                                            pos)
    return 100.0 * need / peak["hbm_bytes_per_s"] / secs
