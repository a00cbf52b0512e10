"""The program's one span-and-counter primitive.

``phase(name)`` marks a stretch of host work. It opens
``TraceAnnotation("zp.<name>")``, so the span lies on the
profiler's clock, on the same timeline as the device's ops, and adds its
wall time (``time.perf_counter``) and the calling thread's CPU time
(``time.thread_time``) to the :class:`Profiler` bound to that thread
(:meth:`Profiler.bind`, thread-local). With no profiler bound it only opens
the annotation. A thread's CPU time below its wall time in a phase is time
spent waiting: on the device, on a lock, or on the interpreter lock.

One process-wide ``jax.monitoring`` listener counts every backend compile,
with its seconds, into the compiling thread's bound profiler. It also
records the ``(job, window)`` context that thread last set
(:func:`set_context`): a compile inside a window after a job's window 0 is
that job's recompile.

A profiler has one writer, the thread it is bound to, and takes no lock on
the hot path; :meth:`Profiler.report` copies it.

Phase names (README, "Phases"): ``slot.start``, ``slot.stack``,
``slot.dispatch``, ``slot.fetch``, ``slot.verify``, ``slot.commit`` and
``slot.post`` partition a slot thread's work; ``oracle.dispatch``,
``oracle.wait`` and ``oracle.compare`` nest inside ``slot.verify``;
``ctl.ingest``, ``ctl.admit`` and ``ctl.sweep`` are the farm's control
thread; ``farm.run`` wraps a whole ``FarmManager.run``.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

#: JAX's duration event for one backend compile (a persistent-cache hit
#: reports it too, with the load's seconds)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: recompiles a profiler logs; older ones are counted as dropped
MAX_RECOMPILES = 256

_local = threading.local()
_listener_lock = threading.Lock()
_listener_on = False


@dataclasses.dataclass
class StallStack:
    """Normalized attribution over categories (a 'cycle stack')."""
    seconds: Dict[str, float]

    def fractions(self) -> Dict[str, float]:
        tot = sum(self.seconds.values()) or 1.0
        return {k: v / tot for k, v in self.seconds.items()}

    def dominant(self) -> str:
        return max(self.seconds, key=self.seconds.get)


class Profiler:
    """Phase and compile accumulator of one thread (see module docstring).
    ``recompiles`` keeps the newest :data:`MAX_RECOMPILES`;
    ``recompiles_dropped`` counts the entries that aged out."""

    def __init__(self):
        self._acc: Dict[str, list] = {}     # name -> [n, wall_s, cpu_s]
        self.compiles = 0
        self.compile_s = 0.0
        self.recompiles: deque = deque(maxlen=MAX_RECOMPILES)
        self.recompiles_dropped = 0
        self.context: Optional[tuple] = None    # (job, window) in hand

    @contextmanager
    def bind(self):
        """Make this the calling thread's profiler for the block (the
        previous binding is restored after it)."""
        _install_compile_listener()
        prev = getattr(_local, "profiler", None)
        _local.profiler = self
        try:
            yield self
        finally:
            _local.profiler = prev

    def phase(self, name: str) -> "_Span":
        """``with prof.phase(name): ...`` — the span ``zp.<name>``, timed
        into this profiler whichever thread runs it."""
        return _Span(self._acc, name)

    def _compiled(self, seconds: float):
        self.compiles += 1
        self.compile_s += seconds
        job, window = self.context or (None, None)
        if window is not None and window > 0:
            if len(self.recompiles) == self.recompiles.maxlen:
                self.recompiles_dropped += 1
            self.recompiles.append({"job": job, "window": int(window),
                                    "s": seconds})

    def report(self) -> dict:
        """``{"phases": {name: {"n", "wall_ms", "cpu_ms"}}, "compiles":
        {"n", "s"}, "recompiles": [{"job", "window", "s"}, ...],
        "recompiles_dropped"}``, copied."""
        acc = dict(self._acc)
        return {
            "phases": {name: {"n": n, "wall_ms": wall * 1e3,
                              "cpu_ms": cpu * 1e3}
                       for name, (n, wall, cpu) in sorted(acc.items())},
            "compiles": {"n": self.compiles, "s": self.compile_s},
            "recompiles": [dict(r) for r in list(self.recompiles)],
            "recompiles_dropped": self.recompiles_dropped,
        }


class _Span:
    """One phase: the annotation, then both clocks, added on exit."""
    __slots__ = ("acc", "name", "note", "w0", "c0")

    def __init__(self, acc: Dict[str, list], name: str):
        self.acc = acc
        self.name = name

    def __enter__(self):
        self.note = TraceAnnotation("zp." + self.name)
        self.note.__enter__()
        self.w0 = time.perf_counter()
        self.c0 = time.thread_time()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.w0
        cpu = time.thread_time() - self.c0
        self.note.__exit__(*exc)
        acc = self.acc.get(self.name)
        if acc is None:
            self.acc[self.name] = [1, wall, cpu]
        else:
            acc[0] += 1
            acc[1] += wall
            acc[2] += cpu
        return False


def phase(name: str):
    """``with phase("slot.fetch"): ...`` — the span ``zp.<name>``, timed
    into the calling thread's bound profiler when there is one."""
    prof = getattr(_local, "profiler", None)
    if prof is None:
        return TraceAnnotation("zp." + name)
    return prof.phase(name)


def set_context(job, window: Optional[int] = None):
    """Name the job and window the calling thread is working on, for the
    compile listener (``window=None``: between windows)."""
    prof = getattr(_local, "profiler", None)
    if prof is not None:
        prof.context = (job, window)


def _on_duration(event: str, duration: float, **_):
    if event != COMPILE_EVENT:
        return
    prof = getattr(_local, "profiler", None)
    if prof is not None:
        prof._compiled(duration)


def _install_compile_listener():
    global _listener_on
    with _listener_lock:
        if not _listener_on:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listener_on = True
