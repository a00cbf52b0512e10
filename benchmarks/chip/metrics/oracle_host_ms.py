"""Host time per drained window of the step-locked oracle outside its
wait on the device: the ``oracle.dispatch`` and ``oracle.compare`` phases
of the last farm run (``repro.farm.telemetry.last_report()``), summed over
slots, over the windows the run drained. None where the program has no
oracle phases."""

NAMES = ("oracle.dispatch", "oracle.compare")


def read(rec):
    try:
        from repro.farm.telemetry import last_report
    except ImportError:
        return None
    rep = last_report()
    devs = list((rep or {}).get("devices", {}).values())
    windows = sum(d.get("windows", 0) for d in devs)
    found = [(d.get("phases") or {}).get(name) for d in devs
             for name in NAMES]
    if not windows or not any(found):
        return None
    return sum(v["wall_ms"] for v in found if v) / windows
