"""Shared fixtures for the farm tests."""
import threading

import pytest

from repro.core.watchdog import Watchdog


class _Judged(Watchdog):
    """A watchdog that sets ``judged`` at the first straggler sweep AFTER
    one that named a straggler: the control plane has finished that sweep
    by then, so the named board's eviction is already signalled."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.judged = threading.Event()
        self._named = False

    def stragglers(self, *args, **kw):
        if self._named:
            self.judged.set()
        out = super().stragglers(*args, **kw)
        self._named = self._named or bool(out)
        return out


class VirtualWalls:
    """Deterministic straggler timing for a farm run.

    ``clock`` goes to ``FarmManager(clock=...)``. Each thread reads its
    own virtual time, which moves only when that thread calls ``spend``,
    so a window wall measured on a slot thread is exactly what its
    engines spent, however loaded the host is. ``watchdog`` goes to
    ``FarmManager(watchdog=...)``; a board that calls ``wait_for_verdict``
    from its own verify blocks until the control plane has flagged a
    straggler, so it cannot finish before the verdict lands."""

    def __init__(self):
        self._local = threading.local()
        self.watchdog = _Judged(timeout_s=600.0)

    def clock(self) -> float:
        return getattr(self._local, "now", 0.0)

    def spend(self, dt: float):
        self._local.now = self.clock() + dt

    def wait_for_verdict(self):
        assert self.watchdog.judged.wait(timeout=120.0), \
            "the control plane never named a straggler"


@pytest.fixture
def virtual_walls():
    return VirtualWalls()
