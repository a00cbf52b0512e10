"""The prefill's share of device time in a decode cell: device seconds of
the prefill program (jit name ``prefill_step``) over the device's busy
seconds in the traced window."""


def read(rec):
    t = rec.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    secs = sum(v for k, v in t["modules"].items() if "prefill_step" in k)
    if secs <= 0:
        return None
    return 100.0 * secs / t["busy_s"]
