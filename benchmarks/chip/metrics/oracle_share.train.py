"""The oracle's share of device time: seconds of the step-locked oracle
program (jit name ``train_step``) over the seconds of every program run
on the device in the traced window."""


def read(rec):
    t = rec.get("trace")
    if not t:
        return None
    total = sum(t["modules"].values())
    oracle = sum(v for k, v in t["modules"].items() if "train_step" in k)
    if total <= 0 or oracle <= 0:
        return None
    return 100.0 * oracle / total
