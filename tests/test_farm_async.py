"""ZP-Farm slot-thread tests: per-slot dispatcher threads vs a plain
``WindowScheduler.run_many`` pass over the same clients — bit-identical
outputs (plain runs, forced eviction + requeue, checkpoint DrainBarrier
veto mid-stream), wall-time straggler eviction,
thread confinement of each job's dispatches, hung-board abandonment, the
per-slot host-overhead telemetry, and admission that stops walking the
queue once no seat is free."""
import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Client, DrainBarrier, WindowScheduler, iter_windows
from repro.core.watchdog import Watchdog
from repro.farm import FarmError, FarmJob, FarmManager
from repro.farm.manager import _SlotWorker
from repro.farm.placement import enumerate_slots

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


def _submit(mgr, n_jobs=3, engines=None, n_items=6, seed_base=0, **extra):
    col = {}
    for s in range(n_jobs):
        name = f"job{s}"
        col[name] = []
        mgr.submit(FarmJob(
            name=name, engine=(engines or {}).get(s, _engine),
            windows=_windows(seed_base + s, n_items=n_items),
            state=jnp.float32(0), shell={}, stack_fn=_stack,
            on_drain=(lambda p, r, y, n=name: col[n].append(np.asarray(y))),
            **extra))
    return col


def _reference(n_jobs=3, n_items=6, seed_base=0, barriers=()):
    """The same jobs straight through a plain ``WindowScheduler.run_many``
    pass on this thread, no farm: per-job outputs and final states."""
    sched = WindowScheduler(interval=1, overlap=True, drain_fn=None,
                            stack_fn=None)
    col = {f"job{s}": [] for s in range(n_jobs)}
    finals = sched.run_many(
        [Client(_engine, _windows(seed_base + s, n_items=n_items),
                jnp.float32(0), {}, stack_fn=_stack, drain_fn=None,
                barriers=barriers) for s in range(n_jobs)],
        on_drain=lambda k, p, r, y: col[f"job{k}"].append(np.asarray(y)))
    states = {f"job{k}": np.asarray(st) for k, (st, _) in enumerate(finals)}
    return col, states


# ----------------------------------------------------------- determinism --
@pytest.mark.parametrize("seed_base", [0, 7])
def test_async_bit_identical_to_run_many(seed_base):
    """The headline contract: the threaded farm delivers byte-for-byte the
    outputs and final states of a plain run_many pass, for every job."""
    ref_col, ref_states = _reference(seed_base=seed_base)
    mgr = FarmManager(slots=3)
    col = _submit(mgr, seed_base=seed_base)
    rep = mgr.run()
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    for name in ref_col:
        assert len(col[name]) == len(ref_col[name]) == 3
        for a, b in zip(ref_col[name], col[name]):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(ref_states[name],
                                      np.asarray(mgr.results[name][0]))


def test_async_forced_eviction_requeues_and_preserves_outputs():
    """Eviction under threads: partial outputs discarded, replay on a
    DIFFERENT slot, delivered outputs bit-identical to the run_many
    reference, exactly once."""
    base, _ = _reference()
    mgr = FarmManager(slots=3)
    col = _submit(mgr)
    mgr.force_evict("job1")
    rep = mgr.run()
    ev = rep["telemetry"]["evictions"]
    assert len(ev) == 1 and ev[0]["job"] == "job1"
    assert ev[0]["why"] == "forced"
    assert rep["jobs"]["job1"]["requeues"] == 1
    assert rep["jobs"]["job1"]["slot"] != ev[0]["slot"]  # another seat
    for name in base:
        got = col[name]
        assert len(got) == 3                    # exactly-once delivery
        for a, b in zip(base[name], got):
            np.testing.assert_array_equal(a, b)


def test_async_barrier_veto_midstream_then_requeue_commits_once():
    """A per-job checkpoint DrainBarrier is VETOED when the drain verifier
    rejects the window behind it; the evicted job replays on another slot
    and the replay's commits (and outputs) match the run_many
    reference's."""
    def barrier(commits):
        return (DrainBarrier(every=4, action=lambda state, step:
                             commits.append((step, float(state)))),)

    ref_commits = []
    ref, _ = _reference(n_jobs=1, barriers=barrier(ref_commits))
    commits = []
    failed = {"n": 0}

    def verify(plan, records, ys):
        # reject the window starting at step 2 — first attempt only
        if plan.start == 2 and failed["n"] == 0:
            failed["n"] += 1
            raise AssertionError("synthetic commit divergence")

    got = []
    mgr = FarmManager(slots=3)
    mgr.submit(FarmJob(
        name="ckpt", engine=_engine, windows=_windows(0),
        state=jnp.float32(0), shell={}, stack_fn=_stack,
        verify=verify,
        on_drain=lambda p, r, y: got.append(np.asarray(y)),
        barriers=barrier(commits)))
    rep = mgr.run()
    assert rep["jobs"]["ckpt"]["status"] == "done"
    assert rep["jobs"]["ckpt"]["requeues"] == 1
    assert rep["telemetry"]["drain_vetoes"] == 1
    assert "veto" in rep["telemetry"]["evictions"][0]["why"]
    # attempt 1 faulted at the window behind boundary 4: its commit was
    # vetoed, so the ONLY commit is the clean replay's, with the same
    # committed state as the reference's
    assert commits == ref_commits
    assert len(commits) == 1 and commits[0][0] == 4
    assert len(got) == len(ref["job0"]) == 3
    for a, b in zip(ref["job0"], got):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- wall-time signals --
def test_async_watchdog_evicts_wall_time_straggler(virtual_walls):
    """A slow board is flagged from its MEASURED window wall (observed on
    its own slot thread) and evicted mid-stream; outputs are preserved via
    requeue + replay. The walls are virtual (1 ms an engine call, 50 ms on
    the slow board's first attempt), and the slow board holds its window-1
    verify until the verdict, so it cannot finish before it is judged."""
    vw = virtual_walls
    base, _ = _reference(n_items=10)
    mgr = FarmManager(slots=3, straggler_factor=2.0, clock=vw.clock,
                      watchdog=vw.watchdog)

    def engine(state, shell, stack):
        vw.spend(0.001)
        return _engine(state, shell, stack)

    def slow(state, shell, stack):
        vw.spend(0.05 if mgr.jobs[1].attempts == 1 else 0.001)
        return _engine(state, shell, stack)

    def hold(plan, records, ys):
        if plan.index == 1 and mgr.jobs[1].attempts == 1:
            vw.wait_for_verdict()

    col = _submit(mgr, engines={0: engine, 1: slow, 2: engine}, n_items=10)
    mgr.jobs[1].verify = hold
    rep = mgr.run()
    ev = rep["telemetry"]["evictions"]
    assert [e["job"] for e in ev] == ["job1"]
    assert ev[0]["why"] == "straggler"
    assert rep["jobs"]["job1"]["status"] == "done"
    for name in base:
        assert len(col[name]) == len(base[name]) == 5
        for a, b in zip(base[name], col[name]):
            np.testing.assert_array_equal(a, b)


def test_async_thread_confinement_and_per_thread_tagging():
    """Every dispatch of one job attempt runs on exactly one slot thread
    (never the control thread), concurrent jobs really do run on distinct
    threads, and the watchdog's duration samples are tagged with the slot
    thread that observed them."""
    seen = {}
    lock = threading.Lock()

    def make_engine(name):
        def engine(state, shell, stack):
            with lock:
                seen.setdefault(name, set()).add(
                    threading.current_thread().name)
            return _engine(state, shell, stack)
        return engine

    mgr = FarmManager(slots=3)
    _submit(mgr, engines={s: make_engine(f"job{s}") for s in range(3)})
    rep = mgr.run()
    main = threading.current_thread().name
    assert all(len(t) == 1 for t in seen.values())      # one thread per job
    assert all(main not in t for t in seen.values())    # never the control
    assert len(set().union(*seen.values())) == 3        # truly concurrent
    for name, j in rep["jobs"].items():
        tagged = mgr.wd.threads.get(j["slot"])
        assert tagged is not None and tagged.startswith("farm-")


def test_async_hung_board_abandoned_and_job_requeued():
    """True wall-time liveness: a board hung mid-dispatch stops beating,
    is written off past the watchdog timeout (its slot leaves the pool —
    a Python thread cannot be killed), and its job requeues elsewhere."""
    release = threading.Event()
    hung = {"n": 0}

    def hang_once(state, shell, stack):
        if hung["n"] == 0:
            hung["n"] += 1
            release.wait(timeout=30.0)
        return _engine(state, shell, stack)

    base, _ = _reference(n_jobs=2)
    mgr = FarmManager(slots=2,
                      watchdog=Watchdog(timeout_s=0.3),
                      evict_stragglers=False)
    col = _submit(mgr, n_jobs=2, engines={1: hang_once})
    try:
        rep = mgr.run()
    finally:
        release.set()               # let the abandoned thread unwind
    assert rep["jobs"]["job1"]["status"] == "done"
    assert rep["jobs"]["job1"]["requeues"] == 1
    ev = rep["telemetry"]["evictions"]
    assert any("hung" in e["why"] for e in ev)
    lost_slot = next(e["slot"] for e in ev if "hung" in e["why"])
    assert rep["jobs"]["job1"]["slot"] != lost_slot
    for name in base:
        for a, b in zip(base[name], col[name]):
            np.testing.assert_array_equal(a, b)
    for w in mgr._workers.values():     # no thread leaks into other tests
        w.join(timeout=5.0)


def test_async_queue_depth_two_spreads_before_stacking():
    """With slot_queue_depth=2, admission is least-loaded-first: three
    equal jobs land on three DIFFERENT slots (full parallelism), not two
    pre-staged behind one board."""
    mgr = FarmManager(slots=3, slot_queue_depth=2)
    _submit(mgr)
    rep = mgr.run()
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    assert len({j["slot"] for j in rep["jobs"].values()}) == 3
    assert rep["telemetry"]["occupancy_peak"] == 3


# ----------------------------------------------------------- telemetry ----
def test_async_telemetry_reports_host_overhead_channels():
    """The async report attributes per-slot host overhead: queue wait,
    dispatch wall, drain wall, and idle gaps all carry samples, and the
    printable summary includes the host line."""
    mgr = FarmManager(slots=2)
    _submit(mgr, n_jobs=4)              # 4 jobs on 2 slots: queuing + idle
    rep = mgr.run()
    t = rep["telemetry"]
    assert t["occupancy_peak"] == 2 and t["slots"] == 2
    for slot, d in t["devices"].items():
        assert d["windows"] > 0
        assert d["queue_wait_ms"]["n"] > 0
        assert d["dispatch_ms"]["n"] > 0
        assert d["drain_ms"]["n"] > 0
        assert d["queue_depth_max"] >= 1
    # 4 jobs over 2 slots: at least one slot went idle between assignments
    assert any(d["idle_ms"]["n"] > 0 for d in t["devices"].values())
    assert "host:" in mgr.telemetry.summary()


# ----------------------------------------------------- admission walk ----
def _control_state(n_jobs, n_slots=4, full=(0, 1, 2, 3), running=True):
    """An async farm's control-plane state with no slot thread started:
    ``n_jobs`` queued, the slots in ``full`` at capacity (depth 1) and, if
    ``running``, runs in flight. One admission tick can then be driven by
    hand and its decisions read off the slot inboxes."""
    mgr = FarmManager(slots=n_slots)
    for j in range(n_jobs):
        mgr.submit(FarmJob(name=f"q{j}", engine=_engine,
                           windows=_windows(j, n_items=2),
                           state=jnp.float32(0), shell={}, stack_fn=_stack))
    mgr.slots = enumerate_slots(min_slots=n_slots)
    mgr._workers = {s.name: _SlotWorker(mgr, s, 1) for s in mgr.slots}
    mgr._slot_load = {s.name: int(s.index in full) for s in mgr.slots}
    if running:
        mgr._running = {-1 - i: object() for i in range(len(full))}
    return mgr


def _seated(mgr):
    """slot -> names of the jobs the tick put in its inbox."""
    out = {}
    for name, w in mgr._workers.items():
        while not w.inbox.empty():
            out.setdefault(name, []).append(w.inbox.get_nowait().job.name)
    return out


def _admission(mgr):
    return mgr.telemetry.report()["control"]["admission"]


@pytest.mark.parametrize("fourth", ["full", "lost", "benched", "probing"])
def test_admission_tick_with_every_seat_full_examines_nothing(fourth):
    """All seats full and runs in flight: a tick over 2000 queued jobs
    takes none of them up and leaves the queue exactly as it was. An idle
    slot that is lost, benched or out on a probe offers no seat."""
    if fourth == "full":
        mgr = _control_state(2000)
    else:
        mgr = _control_state(2000, full=(0, 1, 2))
        name = mgr.slots[3].name
        if fourth == "benched":
            mgr._benched[name] = 0.0
        else:
            getattr(mgr, "_" + fourth).add(name)
    before = [j.name for j in mgr.queue]
    mgr._assign()
    assert [j.name for j in mgr.queue] == before
    assert _seated(mgr) == {}
    assert _admission(mgr) == {"ticks": 1, "examined": 0, "assigned": 0}


def test_admission_walk_stops_at_the_last_free_seat():
    """One seat free: a job backing off and a job that avoids that seat
    are deferred, the third takes the seat, and the walk stops there: the
    deferred pair goes back ahead of the untouched rest, in order."""
    mgr = _control_state(50, full=(0, 1, 2))
    free = mgr.slots[3].name
    mgr.queue[0].not_before = float("inf")      # backing off
    mgr._avoid["q1"] = free                     # only seat is its avoided one
    mgr._assign()
    assert _seated(mgr) == {free: ["q2"]}
    assert [j.name for j in mgr.queue] == (
        ["q0", "q1"] + [f"q{j}" for j in range(3, 50)])
    assert _admission(mgr) == {"ticks": 1, "examined": 3, "assigned": 1}


def test_admission_passes_over_a_job_whose_only_seat_is_its_avoided_one():
    """The avoid preference holds while something runs: the job whose only
    free seat is its old one stays queued with its preference, and the job
    behind it takes the seat."""
    mgr = _control_state(2, full=(0, 1, 2))
    free = mgr.slots[3].name
    mgr._avoid["q0"] = free
    mgr._assign()
    assert _seated(mgr) == {free: ["q1"]}
    assert [j.name for j in mgr.queue] == ["q0"]
    assert mgr._avoid == {"q0": free}


@pytest.mark.parametrize("backing_off", [False, True])
def test_admission_with_no_seat_and_nothing_running(backing_off):
    """No seat anywhere and nothing running: the farm is out of seats and
    says so, unless a job is backing off, when the tick yields and waits
    for the gate as it always did."""
    mgr = _control_state(20, full=(), running=False)
    mgr._lost = {s.name for s in mgr.slots}
    if backing_off:
        mgr.queue[7].not_before = float("inf")
        mgr._assign()
        assert len(mgr.queue) == 20 and _seated(mgr) == {}
    else:
        with pytest.raises(FarmError, match="no live slots"):
            mgr._assign()


def _full_walk(mgr):
    """Reference admission tick: take up every queued job, whether or not
    a seat is left (the walk before it learned to stop)."""
    assigned, deferred, backing_off = 0, [], False
    now = mgr.clock()
    while mgr.queue:
        job = mgr.queue.popleft()
        if job.not_before > now:
            deferred.append(job)
            backing_off = True
            continue
        slot = mgr._pick_slot(mgr._avoid.get(job.name))
        if slot is None:
            deferred.append(job)
            continue
        mgr._avoid.pop(job.name, None)
        mgr._dispatch_to_slot(job, slot)
        assigned += 1
    mgr.queue.extendleft(reversed(deferred))
    if not assigned and not mgr._running and mgr.queue and not backing_off:
        slot = mgr._pick_slot(None)
        if slot is not None:
            job = mgr.queue.popleft()
            mgr._avoid.pop(job.name, None)
            mgr._dispatch_to_slot(job, slot)
        elif not (set(mgr._benched) | mgr._probing):
            raise FarmError("no live slots left to place queued jobs")


def _random_control_state(seed):
    """A control-plane state drawn from ``seed``: slots lost, benched,
    probing or loaded up to a random depth; runs in flight or not; queued
    jobs backing off, avoiding a slot, or lane-coalescible."""
    rng = random.Random(seed)
    depth = rng.choice([1, 1, 2, 3])
    n_slots = rng.randint(1, 5)
    mgr = FarmManager(slots=n_slots, slot_queue_depth=depth,
                      clock=lambda: 100.0)
    mgr.slots = enumerate_slots(min_slots=n_slots,
                                lane_capacity=rng.choice([1, 1, 2, 3]))
    mgr._workers = {s.name: _SlotWorker(mgr, s, depth) for s in mgr.slots}
    mgr._slot_load = {s.name: 0 for s in mgr.slots}
    names = [s.name for s in mgr.slots]
    for name in names:
        r = rng.random()
        if r < 0.1:
            mgr._lost.add(name)
        elif r < 0.2:
            mgr._benched[name] = 0.0
        elif r < 0.25:
            mgr._probing.add(name)
        else:
            mgr._slot_load[name] = rng.randint(0, depth)
    if rng.random() < 0.6:
        mgr._running = {-1 - i: object() for i in range(rng.randint(1, 3))}
    for j in range(rng.choice([0, 1, 3, 10, 50])):
        job = mgr.submit(FarmJob(
            name=f"q{j}", engine=_engine, windows=_windows(j, n_items=2),
            state=jnp.float32(0), shell={}, stack_fn=_stack,
            lane_key="k" if rng.random() < 0.5 else None))
        if rng.random() < 0.15:
            job.not_before = 200.0
        if rng.random() < 0.2:
            mgr._avoid[job.name] = rng.choice(names)
    return mgr


def _decisions(mgr, tick):
    try:
        tick()
        err = None
    except FarmError:
        err = "no seats"
    placed = {}
    for name, w in mgr._workers.items():
        while not w.inbox.empty():
            run = w.inbox.get_nowait()
            placed.setdefault(name, []).append(
                [m.name for m in (run.lanes or [run.job])])
    return (placed, [j.name for j in mgr.queue], sorted(mgr._avoid.items()),
            mgr._slot_load, [j.attempts for j in mgr.jobs], err)


def test_admission_tick_decides_as_the_full_walk_does():
    """Over random control states (seat loads, lost / benched / probing
    slots, backoff, avoid marks, lane coalescing, runs in flight or
    none), one tick seats the same jobs on the same slots, leaves the
    same queue order and raises where the full walk raises."""
    seated = raised = 0
    for seed in range(300):
        got = _random_control_state(seed)
        want = _random_control_state(seed)
        d_got = _decisions(got, got._assign)
        d_want = _decisions(want, lambda: _full_walk(want))
        assert d_got == d_want, seed
        seated += bool(d_got[0])
        raised += d_got[-1] is not None
    assert seated > 100 and raised > 0     # both branches were exercised


def test_fault_free_farm_counts_admission_and_matches_run_many():
    """A fault-free 4-slot farm of 48 short boards seats every board once,
    in queue order, examining no job it does not seat, and delivers the
    run_many reference's outputs. Straggler eviction is off: on a loaded
    host it would requeue a board, and the farm would not be fault-free."""
    base, _ = _reference(n_jobs=48, n_items=4)
    mgr = FarmManager(slots=4, evict_stragglers=False)
    col = _submit(mgr, n_jobs=48, n_items=4)
    order = []
    dispatch = mgr._dispatch_to_slot

    def spy(job, slot):
        order.append(job.name)
        return dispatch(job, slot)

    mgr._dispatch_to_slot = spy
    rep = mgr.run()
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    assert order == [f"job{s}" for s in range(48)]
    adm = rep["telemetry"]["control"]["admission"]
    assert adm["examined"] == adm["assigned"] == 48
    assert adm["ticks"] >= 12        # 4 seats: at least 12 ticks seat jobs
    for name in base:
        assert len(col[name]) == len(base[name]) == 2
        for a, b in zip(base[name], col[name]):
            np.testing.assert_array_equal(a, b)
