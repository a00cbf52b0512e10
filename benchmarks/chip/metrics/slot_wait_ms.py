"""Host time per drained window in which the farm's slot threads wait on
the device: the ``slot.fetch`` and ``oracle.wait`` phases of the last farm
run (``repro.farm.telemetry.last_report()``), summed over slots, over the
windows the run drained. None where the program has no phases."""


def read(rec):
    try:
        from repro.farm.telemetry import last_report
    except ImportError:
        return None
    rep = last_report()
    devs = list((rep or {}).get("devices", {}).values())
    windows = sum(d.get("windows", 0) for d in devs)
    phases = [d.get("phases") or {} for d in devs]
    if not windows or not any(phases):
        return None
    return sum(p.get(name, {}).get("wall_ms", 0.0) for p in phases
               for name in ("slot.fetch", "oracle.wait")) / windows
