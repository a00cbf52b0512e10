"""Host time per drained window in which the farm's slot threads work: the
top-level ``slot.*`` phases of the last farm run
(``repro.farm.telemetry.last_report()``) less ``slot.fetch`` and
``oracle.wait``, summed over slots, over the windows the run drained. None
where the program has no phases."""

WAIT = ("slot.fetch", "oracle.wait")


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
    top = sum(v["wall_ms"] for p in phases for name, v in p.items()
              if name.startswith("slot."))
    wait = sum(p.get(name, {}).get("wall_ms", 0.0) for p in phases
               for name in WAIT)
    return (top - wait) / windows
