"""Phase spans (repro.core.profiler) on the farm's slot, oracle and control
threads: the slot phases partition a slot thread's assignment wall, work
lands in the phase that names it, the oracle's steps fill its phases, the
spans reach a profiler trace on its clock, a window-shape change is logged
as that job's recompile, last_report() is the newest run's, and the solo
scheduler uses the same phase names."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DrainBarrier, Profiler, WindowScheduler, iter_windows
from repro.core.coemu import CommitStreamVerifier
from repro.core.profiler import phase
from repro.farm import FarmError, FarmJob, FarmManager
from repro.farm import telemetry as farm_telemetry

jax.config.update("jax_platform_name", "cpu")

SLOT_PHASES = {"slot.start", "slot.stack", "slot.dispatch", "slot.fetch",
               "slot.verify", "slot.commit", "slot.post"}


@jax.jit
def _body(state, stack):
    return state + jnp.sum(stack), stack * 2.0


def _engine(state, shell, stack):
    s, ys = _body(state, stack)
    return s, shell, ys


def _stack(items):
    return jnp.asarray(np.stack(items))


def _job(name, seed, n_items=8, group=2, verify=None, **kw):
    items = [np.float32(seed * 100 + i) for i in range(n_items)]
    return FarmJob(name=name, engine=_engine,
                   windows=list(iter_windows(items, group)),
                   state=jnp.float32(0), shell={}, stack_fn=_stack,
                   verify=verify, **kw)


def _farm(n_jobs=4, slots=2, verify=None, **kw):
    mgr = FarmManager(slots=slots)
    for j in range(n_jobs):
        mgr.submit(_job(f"job{j}", j, verify=verify, **kw))
    return mgr


def _spanned_ms(dev):
    return sum(p["wall_ms"] for name, p in dev["phases"].items()
               if name.startswith("slot."))


def test_slot_phases_cover_the_assignment_wall():
    """The top-level slot.* phases cover at least 95% of each slot thread's
    wall from pickup to terminal message; unspanned_ms is the rest."""
    rep = _farm(verify=lambda p, r, y: time.sleep(0.005)).run()
    devices = rep["telemetry"]["devices"]
    assert len(devices) == 2
    for dev in devices.values():
        assert set(dev["phases"]) >= {"slot.start", "slot.stack",
                                      "slot.dispatch", "slot.fetch",
                                      "slot.verify", "slot.post"}
        assert set(dev["phases"]) <= SLOT_PHASES
        spanned = _spanned_ms(dev)
        assert dev["unspanned_ms"] >= 0.0
        assert dev["unspanned_ms"] <= 0.05 * (spanned + dev["unspanned_ms"])
        assert dev["phases"]["slot.dispatch"]["n"] == dev["windows"]
    ctl = rep["telemetry"]["control"]["phases"]
    assert set(ctl) == {"ctl.ingest", "ctl.admit", "ctl.sweep"}
    assert rep["telemetry"]["clock_origin"] is not None


def test_verify_work_lands_in_slot_verify_not_fetch():
    """A verify hook that sleeps 30 ms per window is charged to
    slot.verify; slot.fetch, the wait on the device, stays small."""
    rep = _farm(n_jobs=2, verify=lambda p, r, y: time.sleep(0.03)).run()
    for dev in rep["telemetry"]["devices"].values():
        n = dev["windows"]
        assert n > 0
        assert dev["phases"]["slot.verify"]["n"] == n
        assert dev["phases"]["slot.verify"]["wall_ms"] >= 30.0 * n * 0.95
        # a sleep burns no CPU: the verify phase waited
        assert dev["phases"]["slot.verify"]["cpu_ms"] \
            < 0.5 * dev["phases"]["slot.verify"]["wall_ms"]
        assert dev["phases"]["slot.fetch"]["wall_ms"] < 30.0 * n * 0.5


def _toy_oracle():
    def oracle_step(state, batch):
        b = jnp.float32(batch)
        aux = {"scanned": (),
               "tail": ({"checksum": jnp.stack([b, b * 2.0])},)}
        return state + b, {}, aux
    return oracle_step


def test_commit_stream_verifier_fills_oracle_phases():
    """Each replayed step is one oracle.dispatch, oracle.wait and
    oracle.compare."""
    batches = [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    v = CommitStreamVerifier(_toy_oracle(), jnp.float32(0), batches,
                             layers=1)
    prof = Profiler()
    with prof.bind():
        for w in range(3):
            rows = np.asarray([[0.0, b, 2.0 * b]
                               for b in batches[2 * w:2 * w + 2]])
            v(2 * w + 1, {"fifos": {"commits": {"data": rows}}})
    phases = prof.report()["phases"]
    assert set(phases) == {"oracle.dispatch", "oracle.wait",
                           "oracle.compare"}
    for p in phases.values():
        assert p["n"] == len(batches)
        assert p["wall_ms"] >= 0.0 and p["cpu_ms"] >= 0.0


def _host_events(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    assert len(path) == 1
    with open(path[0], "rb") as f:
        pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name.startswith("zp.")]
    return out


def test_trace_holds_slot_spans_inside_the_farm_run(tmp_path):
    """A CPU profiler trace of a farm run holds one zp.farm.run host event
    and, inside it, as many zp.slot.* events of each name as the phase
    tables count."""
    mgr = _farm(verify=lambda p, r, y: None)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = mgr.run()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    runs = [e for e in events if e[0] == "zp.farm.run"]
    assert len(runs) == 1
    _, lo, hi = runs[0]
    want = {}
    for dev in rep["telemetry"]["devices"].values():
        for name, p in dev["phases"].items():
            want["zp." + name] = want.get("zp." + name, 0) + p["n"]
    got = {}
    for name, start, end in events:
        if name.startswith("zp.slot."):
            assert lo <= start <= end <= hi
            got[name] = got.get(name, 0) + 1
    assert got == want


def test_window_shape_change_logs_one_recompile():
    """A job whose window shape changes at window 3 compiles again there:
    the telemetry logs exactly one recompile, at (slot, job, 3)."""
    body = jax.jit(lambda state, x: (state + jnp.sum(x), x * 3.0))

    def engine(state, shell, items):
        s, ys = body(state, items[0])
        return s, shell, ys

    windows = ([[np.ones((3, 5), np.float32)]] * 3
               + [[np.ones((3, 7), np.float32)]] * 3)
    mgr = FarmManager(slots=1)
    mgr.submit(FarmJob(name="reshaped", engine=engine, windows=windows,
                       state=jnp.float32(0), shell={}))
    rep = mgr.run()
    tel = rep["telemetry"]
    (slot,) = tel["devices"]
    assert tel["compiles"]["recompiles"] == [
        {"slot": slot, "job": "reshaped", "window": 3,
         "s": pytest.approx(tel["compiles"]["recompiles"][0]["s"])}]
    assert tel["compiles"]["n"] >= 2      # window 0's compile and window 3's
    assert tel["devices"][slot]["compiles"]["n"] >= 2
    assert "recompiles reshaped@3" in mgr.telemetry.summary()


def test_last_report_is_the_newest_run():
    """last_report() is the telemetry of the last FarmManager.run, also
    when that run raised."""
    first = _farm(n_jobs=2).run()
    second = _farm(n_jobs=3).run()
    assert farm_telemetry.last_report() is second["telemetry"]
    assert farm_telemetry.last_report() is not first["telemetry"]

    def reject(plan, records, ys):
        raise AssertionError("never accepted")

    failing = FarmManager(slots=1)
    failing.submit(_job("bad", 9, verify=reject, max_requeues=0))
    with pytest.raises(FarmError):
        failing.run()
    last = farm_telemetry.last_report()
    assert last is not second["telemetry"]
    assert last["drain_vetoes"] >= 1


def test_control_report_counts_admission():
    """The control table carries the async admission counter: integer
    ticks, jobs examined and jobs assigned, where every assignment is one
    attempt of one job, and a fault-free farm examines only what it
    seats (straggler eviction off, so that no board is requeued)."""
    mgr = FarmManager(slots=2, evict_stragglers=False)
    for j in range(6):
        mgr.submit(_job(f"job{j}", j))
    rep = mgr.run()
    adm = rep["telemetry"]["control"]["admission"]
    assert set(adm) == {"ticks", "examined", "assigned"}
    assert all(isinstance(v, int) for v in adm.values())
    assert adm["assigned"] == sum(j.attempts for j in mgr.jobs) == 6
    assert adm["examined"] == adm["assigned"]
    assert adm["ticks"] >= 3


def test_window_scheduler_profile_uses_slot_phase_names():
    """The solo WindowScheduler times the slot thread's phases into the
    profiler it is given: stack, dispatch, fetch, verify and commit."""
    prof = Profiler()
    sched = WindowScheduler(overlap=True, drain_fn=lambda s: ({}, s),
                            reset=lambda s: s, stack_fn=_stack, timer=prof)
    saved = []
    items = [np.float32(i) for i in range(8)]
    sched.run(_engine, iter_windows(items, 2), jnp.float32(0), {},
              on_drain=lambda plan, rec, ys: None,
              barriers=(DrainBarrier(every=4,
                                     action=lambda s, b: saved.append(b)),))
    phases = prof.report()["phases"]
    assert set(phases) == {"slot.stack", "slot.dispatch", "slot.fetch",
                           "slot.verify", "slot.commit"}
    assert phases["slot.dispatch"]["n"] == 4
    assert phases["slot.verify"]["n"] == 4
    assert phases["slot.commit"]["n"] == len(saved) == 2
    # the profiler is bound for the pass only
    with phase("slot.stack"):
        pass
    assert prof.report()["phases"]["slot.stack"]["n"] == phases[
        "slot.stack"]["n"]
