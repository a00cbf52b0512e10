"""Scheduler-driven roofline capture (ROADMAP item).

The static roofline (``roofline/compose.py``) predicts per-step cost from
dry-run lowerings; this module closes the loop with MEASURED windows: an
``on_dispatch``/``on_drain`` callback pair attachable to any
``WindowScheduler`` client — train, verify, serve, or any farm job —
records each window's wall time (dispatch-to-drain, pipelined) and pairs
it with the window dispatch's HLO cost from the compiled engine's
``cost_analysis``, so every windowed workload emits (HLO cost, measured
time) rows into the roofline composer without a bespoke harness.

Wall-time semantics under overlap: the drain of window *i* runs after
window *i+1*'s dispatch, so a row's ``wall_s`` is "time until window *i*'s
results were in hand" — the honest pipelined number, matching the serve
client's latency definition. Achieved-flops rates derived from it are a
LOWER bound on device throughput.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.roofline.hw import Hardware, device_peaks


def save_measured(report: Dict[str, Any], arch: str, source: str,
                  out_dir: str = "experiments/measured") -> str:
    """Persist a :meth:`WindowCapture.report` as a measured-windows record
    for ``roofline.report`` — the measured counterpart of the dry-run
    records, rendered next to the static composition tables."""
    d = pathlib.Path(out_dir)
    d.mkdir(parents=True, exist_ok=True)
    path = d / f"{arch}_{source}.json"
    path.write_text(json.dumps({"arch": arch, "source": source, **report},
                               indent=1, default=float))
    return str(path)


def compiled_cost(compiled) -> Dict[str, float]:
    """flops / bytes-accessed from a compiled executable's
    ``cost_analysis`` (normalized across jaxlib versions)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):      # older jaxlibs return [dict]
        ca = ca[0] if ca else {}
    return {"flops": float(ca.get("flops", 0) or 0),
            "bytes": float(ca.get("bytes accessed", 0) or 0)}


def engine_cost(jitted_engine, *sample_args) -> Dict[str, float]:
    """HLO cost of one window dispatch: lower + compile the jitted engine
    on sample args and read ``cost_analysis`` (flops / bytes accessed).
    Nothing executes — this is the dry-run path the static roofline uses.
    For a running workload prefer :meth:`WindowCapture.attach_engine`,
    which reads the cost off the run's own FIRST compile instead of
    paying this second lowering."""
    return compiled_cost(jitted_engine.lower(*sample_args).compile())


def _arg_signature(args):
    """Hashable (structure, per-leaf shape/dtype/sharding) key — one AOT
    executable per distinct window signature (the tail window of a
    non-divisible stream compiles once more, exactly as jit would).
    Metadata only: leaves include the previous window's still-in-flight
    state, so nothing here may materialize a value (a ``getattr`` default
    of ``np.asarray(x)`` would evaluate EAGERLY and block the pipeline on
    every dispatch)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for x in leaves:
        dt = getattr(x, "dtype", None)
        if dt is None:                      # python-scalar leaf
            dt = np.asarray(x).dtype
        sig.append((tuple(np.shape(x)), str(dt),
                    str(getattr(x, "sharding", None))))
    return treedef, tuple(sig)


class CostCapturingEngine:
    """Engine wrapper that makes the run's own FIRST jit compile the HLO
    cost source (ROADMAP: cost attribution by default, no second
    lowering). Dispatch goes through the jitted engine's AOT executable —
    ``lower().compile()`` on first use per argument signature, the exact
    compile a plain jitted call would have paid, with donation semantics
    preserved — and ``cost_analysis`` is read off that executable instead
    of a dedicated dry-run compile. ``cost`` holds the first (full-size)
    window's flops/bytes once compiled."""

    def __init__(self, jitted_engine):
        self._jitted = jitted_engine
        self._exec: Dict[Any, Any] = {}
        self.cost: Optional[Dict[str, float]] = None

    def __call__(self, *args):
        key = _arg_signature(args)
        ex = self._exec.get(key)
        if ex is None:
            ex = self._jitted.lower(*args).compile()
            self._exec[key] = ex
            if self.cost is None:
                self.cost = compiled_cost(ex)
        return ex(*args)


class WindowCapture:
    """Per-window (HLO cost, measured wall time) recorder.

    Attach to a scheduler run via :meth:`callbacks` (chains with existing
    hooks), or hand it to a ``FarmJob(capture=...)`` — the farm fires the
    pair per window and calls :meth:`reset` on eviction so a requeued
    job's replayed windows are not double-recorded.

    Cost attribution: :meth:`attach_cost` records the HLO cost of one
    full-size window dispatch (and the window size it was measured at);
    tail windows scale linearly by size. Without a cost source the rows
    still carry wall times (cost fields stay None).

    Peak fractions use ``hw``, by default the peaks of the device the run
    is on (:func:`~repro.roofline.hw.device_peaks`): off the TPU they are
    reported as ``None``, and a TPU kind with no peak row raises.
    """

    def __init__(self, hw: Optional[Hardware] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.hw = hw or device_peaks(jax.devices()[0])
        self.clock = clock
        self.rows: List[Dict[str, Any]] = []
        self._t: Dict[int, float] = {}
        self._cost: Optional[Dict[str, float]] = None
        self._cost_window: int = 0
        self._scope = None              # ScopePlane, via attach_scope

    def attach_scope(self, plane):
        """Join a ZP-Scope plane's device-side counters to this capture:
        :meth:`report` then carries the scope's counter table next to the
        measured windows, so achieved-rate rows and on-device
        tokens-per-window sit in one record."""
        self._scope = plane
        return self

    # ------------------------------------------------------------- cost ---
    def attach_cost(self, jitted_engine, *sample_args,
                    window_size: int = 1):
        """Record the per-window HLO cost from the engine's compiled
        lowering (``window_size`` = steps in the sample window, for tail
        scaling)."""
        self.set_cost(engine_cost(jitted_engine, *sample_args),
                      window_size=window_size)
        return self

    def set_cost(self, cost: Dict[str, float], window_size: int = 1):
        self._cost = dict(cost)
        self._cost_window = max(1, window_size)
        return self

    def attach_engine(self, jitted_engine):
        """Wrap a jitted ``(state, shell, stack)`` engine so the run's own
        first compile supplies this capture's per-window HLO cost — no
        second lowering (contrast :meth:`attach_cost`, the dry-run path).
        The window size for tail scaling is read from the first dispatched
        stack's leading dimension. Returns the wrapped engine; hand THAT
        to the scheduler."""
        wrapped = CostCapturingEngine(jitted_engine)

        def engine(state, shell, stack):
            publish = self._cost is None
            out = wrapped(state, shell, stack)
            if publish and wrapped.cost is not None:
                leaves = jax.tree_util.tree_leaves(stack)
                g = int(np.shape(leaves[0])[0]) if leaves else 1
                self.set_cost(wrapped.cost, window_size=max(1, g))
            return out

        return engine

    # -------------------------------------------------------- callbacks ---
    def on_dispatch(self, plan, state):
        self._t[plan.index] = self.clock()

    def on_drain(self, plan, records, ys):
        t0 = self._t.pop(plan.index, None)
        row: Dict[str, Any] = {
            "window": plan.index, "start": plan.start, "size": plan.size,
            "wall_s": None if t0 is None else self.clock() - t0,
            "flops": None, "bytes": None,
        }
        if self._cost is not None:
            scale = plan.size / self._cost_window
            row["flops"] = self._cost["flops"] * scale
            row["bytes"] = self._cost["bytes"] * scale
        self.rows.append(row)

    def callbacks(self, on_dispatch: Optional[Callable] = None,
                  on_drain: Optional[Callable] = None):
        """(on_dispatch, on_drain) pair for ``WindowScheduler.run``,
        chained in front of any existing callbacks."""
        def dispatch(plan, state):
            self.on_dispatch(plan, state)
            if on_dispatch is not None:
                on_dispatch(plan, state)

        def drain(plan, records, ys):
            self.on_drain(plan, records, ys)
            if on_drain is not None:
                on_drain(plan, records, ys)

        return dispatch, drain

    def reset(self, upto: Optional[int] = None):
        """Drop in-flight timestamps and recorded rows from window
        ``upto`` onward (farm eviction: the requeued job resumes at its
        snapshot cursor, so rows for committed windows stay and only the
        discarded tail is re-recorded; ``None`` clears everything — the
        no-snapshot full replay)."""
        if upto:
            self.rows = [r for r in self.rows if r["window"] < upto]
        else:
            self.rows.clear()
        self._t.clear()

    # ----------------------------------------------------------- report ---
    def report(self) -> Dict[str, Any]:
        """Aggregate rows into roofline composer terms: measured seconds
        per step, achieved flops/bytes rates, and the fraction of the
        hardware peaks they reach."""
        timed = [r for r in self.rows if r["wall_s"] is not None]
        wall = sum(r["wall_s"] for r in timed)
        steps = sum(r["size"] for r in timed)
        out: Dict[str, Any] = {
            "windows": len(self.rows),
            "steps": sum(r["size"] for r in self.rows),
            "wall_s": wall,
            "s_per_step": wall / steps if steps else None,
        }
        costed = [r for r in timed if r["flops"] is not None]
        if costed and wall > 0:
            flops = sum(r["flops"] for r in costed)
            bts = sum(r["bytes"] for r in costed)
            cw = sum(r["wall_s"] for r in costed)
            out.update({
                "hlo_flops": flops,
                "hlo_bytes": bts,
                "achieved_flops_s": flops / cw,
                "achieved_bytes_s": bts / cw,
                "peak_flops_fraction": (None if self.hw is None else
                                        flops / cw / self.hw.peak_flops_bf16),
                "peak_hbm_fraction": (None if self.hw is None else
                                      bts / cw / self.hw.hbm_bw),
            })
        if self._scope is not None:
            sc = self._scope.report()
            sc.pop("history", None)     # the measured record keeps the
            # counter table, not the per-sample stream
            out["scope"] = sc
        return out
