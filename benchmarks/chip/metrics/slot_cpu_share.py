"""Thread CPU time over wall time of the farm's slot threads while they
work: the top-level ``slot.*`` phases of the last farm run
(``repro.farm.telemetry.last_report()``) less ``slot.fetch`` and
``oracle.wait`` (nested in ``slot.verify``), summed over slots. Below 100%
the threads waited where they should work: on the interpreter lock or on
each other. None where the program has no phases."""


def read(rec):
    try:
        from repro.farm.telemetry import last_report
    except ImportError:
        return None
    rep = last_report() or {}
    wall = cpu = 0.0
    for dev in rep.get("devices", {}).values():
        phases = dev.get("phases") or {}
        for name, v in phases.items():
            if name.startswith("slot.") and name != "slot.fetch":
                wall += v["wall_ms"]
                cpu += v["cpu_ms"]
        wait = phases.get("oracle.wait")
        if wait is not None:
            wall -= wait["wall_ms"]
            cpu -= wait["cpu_ms"]
    if wall <= 0:
        return None
    return 100.0 * cpu / wall
