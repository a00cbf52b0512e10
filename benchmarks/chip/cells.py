"""What every traffic kind shares. A mix file's ``kind`` names the
generator, ``kinds/<kind>.py``, found by name (``harness.kind_class``);
its ``Kind`` class builds the cell's boards from the mix's parameters
and the program's own factories, warms every shape the window uses,
accounts the window, and checks what it produced against the plain
reference. A new kind of traffic is a new file there.

A ``Kind`` gets its weights and inputs from the seed (``weights.py``)
and gives the same seed the same work. It provides ``setup()``,
``farm(rec)`` (or ``jobs(rec, mgr)``), ``check(rec)`` and ``control()``
(the numbers compared for ``correct``, from the window and from the
control), ``release()`` and ``detail()``, and names its ``rate_metric``.
"""
from __future__ import annotations

import numpy as np

from chip.harness import HarnessError, program_config
from chip.stats import pct


def rel_gap(prog, ref, floor=None):
    """|prog - ref| / max(|ref|, floor), elementwise."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    den = np.abs(ref) if floor is None else np.maximum(np.abs(ref), floor)
    return np.abs(prog - ref) / den


class Base:
    rate_metric = ""

    def __init__(self, cell, seed, fault=None):
        self.cell, self.seed, self.fault = cell, int(seed), fault
        self.spec, self.mix = cell.spec, cell.mix
        self.cfg = program_config(self.spec)

    def farm(self, rec, **kw):
        """The program's farm on ``slots`` slots, with ``jobs(rec, mgr,
        **kw)`` submitted."""
        from repro.farm import FarmManager
        mgr = FarmManager(slots=int(self.mix["slots"]), mode="async",
                          evict_stragglers=False)
        for job in self.jobs(rec, mgr, **kw):
            mgr.submit(job)
        return mgr

    def account(self, rec, report, mgr, t_start, t_end) -> dict:
        """Rate, tail and counts over the windows whose verified drain
        landed inside ``[t_start, t_end]``."""
        rows = [r for r in rec.rows if r.t1 <= t_end]
        if len(rows) < 20:
            raise HarnessError(f"only {len(rows)} windows drained inside "
                               "the window; a tail needs more")
        ok = sum(r.units for r in rows if not r.failed)
        bad = sum(r.units for r in rows if r.failed)
        lat = [(r.t1 - r.t0) * 1e3 for r in rows]
        tel = mgr.telemetry
        n_tel = sum(tel.windows.values())
        host = (sum(sum(v) for v in tel.dispatch_ms.values())
                + sum(sum(v) for v in tel.drain_wall_ms.values()))
        broken = [n for n, j in report["jobs"].items()
                  if j["status"] in ("failed", "quarantined")]
        self.rows = rows
        return {"kind": self.mix["kind"], "rate": ok / (t_end - t_start),
                "units": ok, "attempted": ok + bad,
                "failed": bad + len(broken), "windows": len(rows),
                "window_p95_ms": pct(lat, 0.95),
                "host_ms_per_window": host / n_tel if n_tel else None}

    def release(self):
        pass

    def detail(self) -> dict:
        return {}
