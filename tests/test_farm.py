"""ZP-Farm tests: placement fallback, farm-vs-run_many bit-identity,
dynamic admission, watchdog straggler eviction, forced eviction + requeue
output preservation, drain-veto fault handling, and the scheduler-driven
roofline capture."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Client, WindowScheduler, iter_windows
from repro.core.watchdog import Watchdog
from repro.farm import FarmError, FarmJob, FarmManager, enumerate_slots

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------- toy workload --
@jax.jit
def _body(state, stack):
    return state + jnp.sum(stack), stack * 2.0


def _engine(state, shell, stack):
    s, ys = _body(state, stack)
    return s, shell, ys


def _windows(seed, n_items=6, group=2):
    items = [np.float32(seed * 100 + i) for i in range(n_items)]
    return list(iter_windows(items, group))


def _stack(items):
    return jnp.asarray(np.stack(items))


def _submit(mgr, n_jobs=3, engines=None):
    col = {}
    for s in range(n_jobs):
        name = f"job{s}"
        col[name] = []
        mgr.submit(FarmJob(
            name=name, engine=(engines or {}).get(s, _engine),
            windows=_windows(s), state=jnp.float32(0), shell={},
            stack_fn=_stack,
            on_drain=(lambda p, r, y, n=name: col[n].append(np.asarray(y)))))
    return col


def _baseline():
    """The same three clients straight through run_many (no farm)."""
    sched = WindowScheduler(interval=2, overlap=True, drain_fn=None,
                            stack_fn=None)
    out = {}
    states = sched.run_many(
        [Client(_engine, _windows(s), jnp.float32(0), {}, stack_fn=_stack,
                drain_fn=None) for s in range(3)],
        on_drain=lambda k, p, r, y: out.setdefault(k, []).append(
            np.asarray(y)))
    return out, states


# ------------------------------------------------------------- placement --
def test_enumerate_slots_single_device_fallback():
    """On a single-device host, min_slots virtual seats round-robin over
    the device with distinct watchdog keys; with enough devices it is one
    slot per device."""
    fake = [object(), object()]
    slots = enumerate_slots(min_slots=5, devices=fake)
    assert len(slots) == 5
    assert [s.device for s in slots] == [fake[0], fake[1]] * 2 + [fake[0]]
    assert len({s.name for s in slots}) == 5        # distinct worker keys
    slots = enumerate_slots(min_slots=1, devices=fake)
    assert len(slots) == 2 and "#" not in slots[0].name


def test_farm_single_device_bit_identical_to_run_many():
    """CPU fallback contract: the farm (round-robin virtual slots on one
    device) delivers bit-identical outputs and final states to a plain
    WindowScheduler.run_many pass over the same clients."""
    base, states = _baseline()
    mgr = FarmManager(slots=3)
    col = _submit(mgr)
    rep = mgr.run()
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    for s in range(3):
        got = col[f"job{s}"]
        assert len(got) == len(base[s]) == 3
        for a, b in zip(base[s], got):
            np.testing.assert_array_equal(a, b)
        assert float(mgr.results[f"job{s}"][0]) == float(states[s][0])


def test_farm_runs_three_concurrent_jobs_and_queues_extras():
    """≥3 concurrent boards on the available slots; a fourth job waits in
    the queue and admits dynamically when a slot frees."""
    mgr = FarmManager(slots=3)
    col = _submit(mgr, n_jobs=4)
    rep = mgr.run()
    t = rep["telemetry"]
    assert t["occupancy_peak"] == 3 and t["slots"] == 3
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    assert all(len(col[f"job{s}"]) == 3 for s in range(4))


def test_farm_forced_eviction_requeues_and_preserves_outputs():
    """Eviction + requeue contract: partial outputs are discarded, the
    window stream replays on a DIFFERENT slot, and every job's delivered
    outputs are bit-identical to the no-eviction baseline."""
    base, _ = _baseline()
    mgr = FarmManager(slots=3)
    col = _submit(mgr)
    mgr.force_evict("job1")
    rep = mgr.run()
    ev = rep["telemetry"]["evictions"]
    assert len(ev) == 1 and ev[0]["job"] == "job1"
    assert rep["jobs"]["job1"]["requeues"] == 1
    assert rep["jobs"]["job1"]["slot"] != ev[0]["slot"]  # another device
    for s in range(3):
        got = col[f"job{s}"]
        assert len(got) == 3                    # exactly-once delivery
        for a, b in zip(base[s], got):
            np.testing.assert_array_equal(a, b)


def test_farm_watchdog_detects_and_evicts_straggler():
    """A genuinely slow board trips Watchdog.stragglers via the per-slot
    dispatch-cost observations and is evicted + requeued, outputs intact."""
    def slow(state, shell, stack):
        time.sleep(0.05)
        return _engine(state, shell, stack)

    base, _ = _baseline()
    mgr = FarmManager(slots=3, straggler_factor=2.0)
    col = _submit(mgr, engines={1: slow})
    rep = mgr.run()
    ev = rep["telemetry"]["evictions"]
    assert [e["job"] for e in ev] == ["job1"] and ev[0]["why"] == "straggler"
    assert rep["jobs"]["job1"]["status"] == "done"
    for s in range(3):
        for a, b in zip(base[s], col[f"job{s}"]):
            np.testing.assert_array_equal(a, b)


def test_farm_drain_veto_faults_job_and_fails_after_budget():
    """A verify rejection counts a drain veto and takes the evict+requeue
    path; a job that keeps failing verification exhausts its requeue
    budget and is reported failed (strict run raises), without disturbing
    the other boards."""
    def bad_verify(plan, records, ys):
        raise AssertionError("expected-output mismatch")

    mgr = FarmManager(slots=3)
    col = _submit(mgr)
    mgr.jobs[1].verify = bad_verify
    with pytest.raises(FarmError, match="job1"):
        mgr.run()
    rep = mgr.report()
    assert rep["jobs"]["job1"]["status"] == "failed"
    assert "veto" in rep["jobs"]["job1"]["error"]
    assert rep["jobs"]["job1"]["requeues"] == 1      # one retry happened
    assert rep["telemetry"]["drain_vetoes"] >= 2     # both attempts vetoed
    assert rep["jobs"]["job0"]["status"] == "done"
    assert rep["jobs"]["job2"]["status"] == "done"
    assert len(col["job0"]) == 3 and len(col["job2"]) == 3
    assert col["job1"] == []                # faulted outputs never delivered


def test_farm_single_slot_serial_farm_completes():
    """slots=1 degenerates to a serial queue (the bench's baseline): every
    job still completes with correct outputs via dynamic admission."""
    base, _ = _baseline()
    mgr = FarmManager(slots=1)
    col = _submit(mgr)
    rep = mgr.run()
    assert rep["telemetry"]["occupancy_peak"] == 1
    for s in range(3):
        for a, b in zip(base[s], col[f"job{s}"]):
            np.testing.assert_array_equal(a, b)


# -------------------------------------------------------------- watchdog --
def test_stragglers_single_sampled_worker_is_not_a_fleet():
    """A single sampled worker can never be a straggler (no fleet to
    compare against) — the median-of-one case is documented, not UB."""
    t = [0.0]
    wd = Watchdog(timeout_s=10.0, clock=lambda: t[0])
    for _ in range(4):
        wd.heartbeat("only")
        t[0] += 5.0
    assert wd.stragglers(factor=1.0) == []
    # workers that merely beat once (no durations) don't count as fleet
    wd.heartbeat("newcomer")
    assert wd.stragglers(factor=1.0) == []


def test_stragglers_two_worker_fleet_uses_lower_median():
    """With two workers the fleet reference is the LOWER median, so a
    dominant straggler cannot mask itself."""
    wd = Watchdog(timeout_s=10.0)
    for _ in range(3):
        wd.observe("fast", 1.0)
        wd.observe("slow", 10.0)
    assert wd.stragglers(factor=2.0) == ["slow"]
    # forget() clears the slot's history (requeue contract)
    wd.forget("slow")
    assert wd.stragglers(factor=2.0) == []


def test_observe_and_gapless_heartbeat_channels():
    """observe() feeds durations without touching liveness; gap=False
    heartbeats feed liveness without polluting durations."""
    t = [0.0]
    wd = Watchdog(timeout_s=2.0, clock=lambda: t[0])
    wd.heartbeat("w", gap=False)
    t[0] += 100.0                       # huge gap between liveness beats
    wd.heartbeat("w", gap=False)
    assert list(wd.durations.get("w", [])) == []
    wd.observe("w", 0.5)
    assert list(wd.durations["w"]) == [0.5]
    t[0] += 3.0
    assert wd.dead_workers() == ["w"]   # observe() alone is not liveness


# ------------------------------------------------------ roofline capture --
def test_window_capture_records_cost_and_wall_pairs():
    """The on_dispatch/on_drain pair records one row per window with
    measured wall time and size-scaled HLO cost (tail window included)."""
    from repro.roofline import WindowCapture

    items = [np.ones((4,), np.float32) * i for i in range(5)]
    sched = WindowScheduler(interval=2, overlap=True, drain_fn=None,
                            stack_fn=_stack)
    cap = WindowCapture()
    cap.attach_cost(_body, jnp.float32(0), _stack(items[:2]), window_size=2)

    def engine(state, shell, stack):
        s, ys = _body(state, stack)
        return s, shell, ys

    od, odr = cap.callbacks()
    sched.run(engine, sched.windows(items), jnp.float32(0), {},
              on_dispatch=od, on_drain=odr)
    assert [r["size"] for r in cap.rows] == [2, 2, 1]
    assert all(r["wall_s"] > 0 for r in cap.rows)
    assert cap.rows[0]["flops"] > 0
    # tail window cost scales by size
    assert cap.rows[2]["flops"] == pytest.approx(cap.rows[0]["flops"] / 2)
    rep = cap.report()
    assert rep["windows"] == 3 and rep["steps"] == 5
    assert rep["achieved_flops_s"] > 0
    # a CPU run has no device roofline share: never another chip's peaks
    assert rep["peak_flops_fraction"] is None
    assert rep["peak_hbm_fraction"] is None


def test_window_capture_attaches_to_farm_job_and_resets_on_evict():
    """A FarmJob capture records exactly the delivered windows: eviction
    resets it, so the replayed attempt's rows are not double-counted."""
    from repro.roofline import WindowCapture

    mgr = FarmManager(slots=2)
    cap = WindowCapture()
    mgr.submit(FarmJob(name="a", engine=_engine, windows=_windows(0),
                       state=jnp.float32(0), shell={}, stack_fn=_stack,
                       capture=cap))
    mgr.submit(FarmJob(name="b", engine=_engine, windows=_windows(1),
                       state=jnp.float32(0), shell={}, stack_fn=_stack))
    mgr.force_evict("a")
    mgr.run()
    assert [r["window"] for r in cap.rows] == [0, 1, 2]   # one attempt only
