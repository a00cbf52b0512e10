"""ZP-Farm: multi-device co-emulation farm manager (DESIGN C8 scaled out).

The FireSim-manager analog for this repo: a job queue + device placement +
per-device watchdogs + straggler eviction, with one dispatcher thread per
device slot driving its boards' ``ClientDriver`` pipelines, plus the
ZP-Chaos hardening layer —
:class:`FailurePolicy` (retry budgets, quarantine, slot circuit breakers)
and the deterministic fault-injection harness (``repro.farm.chaos``)."""
from repro.core.schedule import LaneBatch  # noqa: F401
from repro.farm.ledger import (  # noqa: F401
    FarmLedger, JobReplay, LedgerState, choose_resume)
from repro.farm.manager import (  # noqa: F401
    FailurePolicy, FarmError, FarmJob, FarmManager, JobSnapshot,
    lane_compatible)
from repro.farm.placement import (  # noqa: F401
    DeviceSlot, enumerate_slots, pick_slot, place, place_stack)
from repro.farm.registry import (  # noqa: F401
    REGISTRY, FactoryRegistry, JobSpec, register)
from repro.farm.telemetry import FarmTelemetry  # noqa: F401
