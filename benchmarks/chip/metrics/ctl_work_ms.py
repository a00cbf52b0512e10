"""Host time per drained window of the farm's control thread: its
``ctl.*`` phases (results ingest, admission, sweep) in the last farm run
(``repro.farm.telemetry.last_report()``), over the windows the run
drained. None where the program has no phases."""


def read(rec):
    try:
        from repro.farm.telemetry import last_report
    except ImportError:
        return None
    rep = last_report() or {}
    windows = sum(d.get("windows", 0)
                  for d in rep.get("devices", {}).values())
    ctl = rep.get("control", {}).get("phases") or {}
    work = sum(v["wall_ms"] for name, v in ctl.items()
               if name.startswith("ctl."))
    if not windows or not ctl:
        return None
    return work / windows
