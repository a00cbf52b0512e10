"""ZP-Scope on the farm: non-interference (scope on/off bit-identity
on several slots and on one, solo and lane-coalesced), the fleet scope
report,
and THE acceptance scenario — a genuinely slow board evicted from its
device-side throughput counters while host wall-clock noise makes the
legacy wall channel misleading."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Client, WindowScheduler, iter_windows
from repro.core.scope import ScopeSpec
from repro.farm import FarmJob, FarmManager
from repro.farm.manager import lane_compatible
from repro.launch.farm import run_scope_smoke

jax.config.update("jax_platform_name", "cpu")


# --------------------------------------------------------- the smoke gate --
@pytest.mark.parametrize("slots,lanes", [
    pytest.param(2, 1, id="async-1"), pytest.param(1, 1, id="one-slot-1"),
    pytest.param(2, 2, id="async-2")])
def test_scope_smoke_bit_identity(slots, lanes):
    """The CI gate's own checker: scope-on outputs/states bit-identical
    to scope-off, no scope keys leaking, non-empty fleet report."""
    out = run_scope_smoke(lanes=lanes, every_n=2, slots=slots, n_steps=8)
    assert out["ok"], out["problems"]
    assert out["scope"]["samples"] > 0


# ------------------------------------------------------------ coalescing --
def test_lane_coalescing_requires_equal_scope_spec():
    """Two boards with different read rates cannot share one fused
    counter tree — the coalescer must leave them apart."""
    def mk(scope):
        return FarmJob(name="j", engine=_engine, windows=_windows(0),
                       state=jnp.float32(0), shell={}, stack_fn=_stack,
                       lane_key="k", scope=scope)
    a, b = mk(ScopeSpec(every_n_windows=2)), mk(ScopeSpec(every_n_windows=4))
    assert lane_compatible(a, b) == "scope spec"
    assert lane_compatible(mk(ScopeSpec()), mk(None)) == "scope spec"
    assert lane_compatible(mk(ScopeSpec(every_n_windows=2)),
                           mk(ScopeSpec(every_n_windows=2))) is None


# ----------------------------------------------------------- toy workload --
@jax.jit
def _body(state, stack):
    return state + jnp.sum(stack), stack * 2.0


def _engine(state, shell, stack):
    s, ys = _body(state, stack)
    return s, shell, ys


@jax.jit
def _heavy_body(state, stack):
    s, ys = _body(state, stack)
    return s, jnp.tile(ys[:, None], (1, 8))


def _windows(seed, n_items=8, group=2):
    items = [np.float32(seed * 100 + i) for i in range(n_items)]
    return list(iter_windows(items, group))


def _stack(items):
    return jnp.asarray(np.stack(items))


# ------------------------------------------------------------ fleet report --
def test_farm_scope_report_and_work_channel_feed():
    """Scoped jobs populate the fleet scope report (cumulative counters
    per job) AND the watchdog's device-side work-rate channel, while the
    published results stay scope-free."""
    base = {}
    mgr0 = FarmManager(slots=2, evict_stragglers=False)
    for i in range(2):
        mgr0.submit(FarmJob(name=f"job{i}", engine=_engine,
                            windows=_windows(i), state=jnp.float32(0),
                            shell={}, stack_fn=_stack))
    mgr0.run()
    base = {n: np.asarray(mgr0.results[n][0]) for n in ("job0", "job1")}

    mgr = FarmManager(slots=2, evict_stragglers=False)
    for i in range(2):
        mgr.submit(FarmJob(name=f"job{i}", engine=_engine,
                           windows=_windows(i), state=jnp.float32(0),
                           shell={}, stack_fn=_stack,
                           scope=ScopeSpec(every_n_windows=1)))
    rep = mgr.run()
    sc = rep["telemetry"]["scope"]
    assert set(sc["jobs"]) == {"job0", "job1"}
    for row in sc["jobs"].values():
        assert row["windows"] == 4 and row["steps"] == 8
        assert row["tokens_per_window"] == pytest.approx(2.0)
    assert sc["samples"] >= 2
    assert mgr.scope_report() == sc
    # work-rate channel fed from the on-device counters
    assert any(len(v) for v in mgr.wd.work_rates.values())
    # results bit-identical to the unscoped farm, shells scope-free
    for n in base:
        np.testing.assert_array_equal(base[n],
                                      np.asarray(mgr.results[n][0]))
        sh = mgr.results[n][1]
        assert "zp_scope" not in (sh if isinstance(sh, dict) else {})


# ----------------------------------------------- the acceptance scenario --
def test_device_counters_evict_true_straggler_not_heavy_board():
    """Host wall time is a polluted signal: board "heavy" legitimately
    does 8x the device work per window (8x tokens) and so has ~4x the
    wall — under the legacy wall channel it reads as a straggler. Board
    "slow" retires the SAME tokens as the normal boards but burns ~8x
    their wall — the true per-token straggler. With every board scoped,
    the watchdog judges seconds-per-token from the on-device counters:
    only "slow" is evicted, requeued, and still delivers outputs
    bit-identical to an undisturbed run_many pass."""
    def make_slow(sleep_s, engine=_engine):
        def eng(state, shell, stack):
            time.sleep(sleep_s)
            return engine(state, shell, stack)
        return eng

    def heavy_engine(state, shell, stack):
        time.sleep(0.04)
        s, ys = _heavy_body(state, stack)
        return s, shell, ys

    def submit_all(mgr, scope):
        col = {}
        engines = {"norm0": make_slow(0.01), "norm1": make_slow(0.01),
                   "heavy": heavy_engine, "slow": make_slow(0.08)}
        for i, (name, eng) in enumerate(engines.items()):
            col[name] = []
            mgr.submit(FarmJob(
                name=name, engine=eng, windows=_windows(i, n_items=24),
                state=jnp.float32(0), shell={}, stack_fn=_stack,
                scope=scope,
                on_drain=(lambda p, r, y, n=name:
                          col[n].append(np.asarray(y)))))
        return col

    def heavy_nosleep(state, shell, stack):
        s, ys = _heavy_body(state, stack)
        return s, shell, ys

    # the undisturbed reference: the same boards through a plain run_many
    # pass (a sleep changes no output, so the engines here do not sleep)
    names = ["norm0", "norm1", "heavy", "slow"]
    base = {n: [] for n in names}
    finals = WindowScheduler(
        interval=1, overlap=True, drain_fn=None, stack_fn=None).run_many(
        [Client(heavy_nosleep if n == "heavy" else _engine,
                _windows(i, n_items=24), jnp.float32(0), {},
                stack_fn=_stack, drain_fn=None)
         for i, n in enumerate(names)],
        on_drain=lambda k, p, r, y: base[names[k]].append(np.asarray(y)))

    # Warm the scoped path end to end with a throwaway farm over both ys
    # structures. The farm writes off window-0 compile as bitstream-build
    # time, but under overlap pipelining the first-use compile WAIT leaks
    # into window-1 walls — and this test is about steady-state rates,
    # not compile accounting.

    warm = FarmManager(slots=2, evict_stragglers=False)
    for i, eng in enumerate((_engine, heavy_nosleep)):
        warm.submit(FarmJob(name=f"warm{i}", engine=eng,
                            windows=_windows(9 + i, n_items=6),
                            state=jnp.float32(0), shell={},
                            stack_fn=_stack,
                            scope=ScopeSpec(every_n_windows=1)))
    warm.run()

    mgr = FarmManager(slots=4, straggler_factor=2.0,
                      straggler_min_s=0.01)
    col = submit_all(mgr, scope=ScopeSpec(every_n_windows=1))
    rep = mgr.run()

    ev = rep["telemetry"]["evictions"]
    assert ev, "the slow board was never flagged"
    assert {e["job"] for e in ev} == {"slow"}
    assert all(e["why"] == "straggler" for e in ev)
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    # the eviction was judged on the device-side work-rate channel
    assert any(len(v) for v in mgr.wd.work_rates.values())
    # exactly-once delivery, bit-identical to the undisturbed reference
    for name in base:
        assert len(col[name]) == len(base[name]) == 12
        for a, b in zip(base[name], col[name]):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(
            np.asarray(finals[names.index(name)][0]),
            np.asarray(mgr.results[name][0]))
