"""Share of the HBM roofline in the decode windows of a sparse-expert
decoder: the bytes each token step must move (chip/flops_moe.py, with the
distinct experts touched per layer-step read from the program's routing
counter ``moe.routing``, and the KV cache at the mix's mean position)
over peak HBM bandwidth, divided by the measured device time of the
decode-window program (jit name ``engine``) per step."""
from chip import flops_moe


def read(rec):
    t, peak = rec.get("trace"), rec.get("peak")
    spec, routing = rec.get("moe_spec"), rec.get("moe_routing")
    if not t or not peak or not spec or not routing:
        return None
    secs = sum(v for k, v in t["modules"].items() if k.endswith("engine"))
    calls = sum(v for k, v in t["module_counts"].items()
                if k.endswith("engine"))
    if secs <= 0 or calls <= 0:
        return None
    mix = rec["mix"]
    pos = int(mix["prompt"]) + int(mix["gen"]) // 2
    touched = routing["touched"] / (routing["steps"] * spec.layers)
    steps = calls * int(mix["window_tokens"])
    need = steps * flops_moe.decode_token_bytes(spec, int(mix["batch"]),
                                                pos, touched)
    return 100.0 * need / peak["hbm_bytes_per_s"] / secs
