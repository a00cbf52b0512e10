"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

Device planes are those named ``/device:...`` other than the host CPU.
On each, the ``XLA Ops`` line holds one event per device operation and
``XLA Modules`` one per compiled program run (``jit_<name>(<id>)``).
Busy time is the union of operation intervals, so operations that
overlap are counted once. The traced window is the benchmark's own
``bench.window`` host span; idle gaps inside it are named by the
benchmark's host span (``bench.*``) that overlaps them most.
"""
from __future__ import annotations

import collections
import glob
import os

from chip.stats import merged, union_length

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str) -> dict:
    """Events of a trace file as plain tuples (seconds):
    ``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "spans": [(name, start, end), ...]}``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return events(pd)


def events(pd) -> dict:
    devices, spans = {}, []
    for plane in pd.planes:
        if _is_device(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            op_line = lines.get("XLA Ops")
            mod_line = lines.get("XLA Modules")
            if op_line is None and mod_line is None:
                continue
            devices[plane.name] = {
                "ops": _tuples(op_line if op_line is not None else mod_line),
                "modules": _tuples(mod_line) if mod_line else [],
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend(t for t in _tuples(ln)
                             if t[0].startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def _tuples(line) -> list:
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def _clip(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def module_name(name: str) -> str:
    return name.split("(")[0]


def reduce(ev: dict, top: int = 10) -> dict:
    """Device numbers over the ``bench.window`` span (or, lacking it, the
    span of all device events): ``window_s``; ``busy_s`` averaged over
    device planes; per-name device seconds of operations and of programs
    (summed over planes, then averaged); the ``top`` longest idle gaps of
    the first device plane, each named by a host span."""
    if not ev["devices"]:
        raise ValueError("the trace holds no device plane")
    win = [(s, e) for n, s, e in ev["spans"] if n == WINDOW_SPAN]
    if win:
        lo, hi = min(s for s, _ in win), max(e for _, e in win)
    else:
        allev = [t for d in ev["devices"].values() for t in d["ops"]]
        lo, hi = min(t[1] for t in allev), max(t[2] for t in allev)
    n = len(ev["devices"])
    busy, ops = 0.0, collections.Counter()
    mods, calls = collections.Counter(), collections.Counter()
    for dev in ev["devices"].values():
        o = _clip(dev["ops"], lo, hi)
        busy += union_length((s, e) for _, s, e in o)
        for name, s, e in o:
            ops[name] += (e - s) / n
        for name, s, e in _clip(dev["modules"], lo, hi):
            mods[module_name(name)] += (e - s) / n
            calls[module_name(name)] += 1.0 / n
    first = ev["devices"][sorted(ev["devices"])[0]]
    gaps = idle_gaps(_clip(first["ops"], lo, hi), lo, hi)
    spans = [(n_, s, e) for n_, s, e in ev["spans"] if n_ != WINDOW_SPAN]
    named = sorted(((name_gap(g, spans), g[1] - g[0]) for g in gaps),
                   key=lambda t: -t[1])[:top]
    return {"window_s": hi - lo, "busy_s": busy / n, "devices": n,
            "ops": dict(ops), "modules": dict(mods),
            "module_counts": dict(calls),
            "device_ops": [[k, v] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v] for k, v in named]}


def idle_gaps(ops, lo: float, hi: float) -> list:
    """``(start, end)`` of every stretch of ``[lo, hi]`` with no op."""
    gaps, cur = [], lo
    for s, e in merged((s, e) for _, s, e in ops):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def name_gap(gap, spans) -> str:
    """The host span overlapping ``gap`` most (innermost on ties)."""
    best, key = "no_bench_span", (0.0, -float("inf"))
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, s - e) > key:
            best, key = name, (ov, s - e)
    return best
