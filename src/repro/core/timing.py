"""Event-driven timing models (DESIGN C4) — the VPS-side interface timing.

In ZynqParrot, I/O timing models live in host software: the DUT emits a
request, the VPS computes the predicted latency of the modelled interface
(e.g. an HBM part), and hardware timers enforce it. Here the "interfaces"
are the TPU's memory system, MXU, and ICI links; the events are the per-op
(or per-layer) costs extracted from the compiled HLO; and the timeline
simulator predicts step time under an overlap model (XLA async collectives
overlapping compute — what a real TPU runtime does).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional

from repro.roofline.hw import Hardware, peaks

#: the chip these analytic models predict for
TARGET_KIND = "TPU v5 lite"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    kind: str                   # compute | memory | collective | host
    duration_s: float
    stream: Optional[str] = None  # default: kind-based stream


class InterfaceTimer:
    """Latency model per interface — the HBM-request timing analogue.
    ``hw`` defaults to the peaks of the modelled target chip."""

    def __init__(self, hw: Optional[Hardware] = None):
        self.hw = hw or peaks(TARGET_KIND)

    def compute(self, flops: float) -> float:
        return flops / self.hw.peak_flops_bf16

    def memory(self, nbytes: float) -> float:
        return nbytes / self.hw.hbm_bw

    def collective(self, wire_bytes: float) -> float:
        # effective wire bytes already account for the ring algorithm; the
        # chip pushes them through its ICI links
        return wire_bytes / (self.hw.ici_link_bw * self.hw.ici_links)

    def event(self, name: str, kind: str, quantity: float) -> Event:
        dur = {"compute": self.compute, "memory": self.memory,
               "collective": self.collective}[kind](quantity)
        return Event(name=name, kind=kind, duration_s=dur)


class Timeline:
    """Two-stream virtual clock: the compute stream serializes compute and
    memory events (a TPU core does one or the other per op — the roofline
    max is applied per event group); the collective stream runs async.
    ``overlap=True`` models XLA async collectives (start early, joined at
    the next dependency); ``overlap=False`` is the fully-serialized bound.
    """

    def __init__(self, overlap: bool = True):
        self.overlap = overlap

    def simulate(self, groups: Iterable[Dict[str, float]]) -> Dict:
        """groups: per-layer dicts {compute_s, memory_s, collective_s}.
        Per group: core time = max(compute, memory) [roofline]; total =
        sum over groups of max(core, collective) if overlapped else
        core + collective."""
        total = 0.0
        per_kind = {"compute": 0.0, "memory": 0.0, "collective": 0.0}
        bound_counts = {"compute": 0, "memory": 0, "collective": 0}
        for g in groups:
            c = g.get("compute_s", 0.0)
            m = g.get("memory_s", 0.0)
            k = g.get("collective_s", 0.0)
            core = max(c, m)
            step = max(core, k) if self.overlap else core + k
            total += step
            per_kind["compute"] += c
            per_kind["memory"] += m
            per_kind["collective"] += k
            dominant = max(("compute", c), ("memory", m), ("collective", k),
                           key=lambda t: t[1])[0]
            bound_counts[dominant] += 1
        dominant = max(per_kind, key=per_kind.get)
        return {"total_s": total, "per_kind": per_kind,
                "bound_counts": bound_counts, "dominant": dominant,
                "overlap": self.overlap}
