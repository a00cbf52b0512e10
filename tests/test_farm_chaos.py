"""ZP-Chaos acceptance tests: the seeded fault-injection harness and the
farm's failure-policy layer (retry budgets, quarantine, slot circuit
breakers, snapshot integrity fallback, graceful shutdown)."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, MemorySnapshotStore
from repro.core import DrainBarrier
from repro.farm import (FailurePolicy, FarmError, FarmJob, FarmManager,
                        FarmTelemetry, enumerate_slots)
from repro.farm.chaos import (KINDS, ChaosInjector, ChaosSnapshotStore,
                              Injection, build_schedule)
from repro.launch.farm import run_chaos_smoke


def _submit(mgr, name, scale=2.0, n=6, barriers=True, max_requeues=6):
    """One toy board: window w yields [w * scale] (bit-exact expected
    stream), optional per-window checkpoint barriers."""
    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * scale

    def engine(state, shell, stack):
        s, ys = _body(state, stack)
        return s, shell, ys

    outs: list = []
    job = FarmJob(
        name=name, engine=engine,
        windows=[[np.float32(w)] for w in range(n)],
        state=jnp.float32(0), shell={},
        stack_fn=lambda it: jnp.asarray(np.stack(it)),
        on_drain=lambda p, r, y: outs.append(np.asarray(y)),
        barriers=((DrainBarrier(every=1, action=lambda s, b: None),)
                  if barriers else ()),
        max_requeues=max_requeues)
    mgr.submit(job)
    return job, outs


def _expected(scale, n):
    return [np.asarray([w * scale], np.float32) for w in range(n)]


def _assert_stream(outs, scale, n):
    assert len(outs) == n
    for got, want in zip(outs, _expected(scale, n)):
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------- the headline gate --
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("slots,n_jobs,seed", [
    pytest.param(4, 8, 7, id="async-7"),
    pytest.param(1, 5, 7, id="one-slot-7"),
    pytest.param(4, 8, 42, id="async-42")])
def test_chaos_smoke_recovers_every_fault(slots, n_jobs, seed):
    """The acceptance gate: a seeded schedule with >= 5 distinct fault
    kinds fires in full, every fault is recovered, non-quarantined boards
    deliver bit-identical-to-oracle outputs, and the genuinely poisoned
    board is dead-lettered instead of raising. A one-slot farm cannot
    outlive its only seat, so its five boards take the menu's first five
    kinds — none that writes the slot off — and every requeue returns to
    that seat."""
    out = run_chaos_smoke(seed, slots=slots, n_jobs=n_jobs)
    assert out["ok"], out["problems"]
    if slots == 1:
        assert {i["kind"] for i in out["schedule"]} == set(KINDS[:5])
    assert len({i["kind"] for i in out["schedule"]}) >= 5
    assert out["faults_injected"] == len(out["schedule"])
    assert out["quarantined"] == ["poison"]
    assert all(s == "done" for n, s in out["jobs"].items()
               if n != "poison")


def test_schedule_is_seed_deterministic_and_mode_scoped():
    mgr = FarmManager(slots=2)
    for i in range(8):
        _submit(mgr, f"b{i}")
    assert build_schedule(3, mgr.jobs) == build_schedule(3, mgr.jobs)
    assert build_schedule(3, mgr.jobs) != build_schedule(4, mgr.jobs)
    # one menu: eight boards with barriers give every kind a board
    assert {i.kind for i in build_schedule(3, mgr.jobs)} == set(KINDS)


# ------------------------------------------------- quarantine / dead-letter --
@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_exhausted_budget_quarantines_instead_of_raising(slots):
    mgr = FarmManager(slots=slots, evict_stragglers=False,
                      poll_s=0.01, policy=FailurePolicy(quarantine=True))

    def poison(state, shell, stack):
        raise RuntimeError("dead board")

    bad = FarmJob(name="bad", engine=poison, windows=[[np.float32(0)]],
                  state=jnp.float32(0), shell={},
                  stack_fn=lambda it: jnp.asarray(np.stack(it)),
                  max_requeues=2)
    mgr.submit(bad)
    _, outs = _submit(mgr, "good", scale=3.0, barriers=False)

    report = mgr.run(strict=True)       # must NOT raise
    assert report["jobs"]["bad"]["status"] == "quarantined"
    assert report["quarantined"] == ["bad"]
    assert bad.requeues == 2            # full budget consumed first
    assert report["jobs"]["good"]["status"] == "done"
    _assert_stream(outs, 3.0, 6)
    assert any(q["job"] == "bad"
               for q in report["telemetry"]["quarantined"])
    # every retry was logged with its attempt number
    attempts = [r["attempt"] for r in report["telemetry"]["retries"]
                if r["job"] == "bad"]
    assert attempts == [1, 2]


def test_legacy_no_policy_marks_failed_and_strict_raises():
    mgr = FarmManager(slots=2, evict_stragglers=False)

    def poison(state, shell, stack):
        raise RuntimeError("dead board")

    mgr.submit(FarmJob(name="bad", engine=poison,
                       windows=[[np.float32(0)]], state=jnp.float32(0),
                       shell={},
                       stack_fn=lambda it: jnp.asarray(np.stack(it)),
                       max_requeues=1))
    with pytest.raises(FarmError, match="bad"):
        mgr.run(strict=True)


def test_retry_backoff_gates_readmission():
    policy = FailurePolicy(backoff_base_s=0.05, backoff_factor=2.0,
                           backoff_max_s=0.2, quarantine=True)
    assert policy.backoff_for(1) == 0.05
    assert policy.backoff_for(2) == 0.10
    assert policy.backoff_for(10) == 0.2        # capped
    mgr = FarmManager(slots=2, evict_stragglers=False,
                      poll_s=0.005, policy=policy)
    flaky = {"left": 2}

    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * 2.0

    def engine(state, shell, stack):
        if flaky["left"] > 0:
            flaky["left"] -= 1
            raise RuntimeError("transient")
        s, ys = _body(state, stack)
        return s, shell, ys

    outs: list = []
    mgr.submit(FarmJob(name="flaky", engine=engine,
                       windows=[[np.float32(w)] for w in range(3)],
                       state=jnp.float32(0), shell={},
                       stack_fn=lambda it: jnp.asarray(np.stack(it)),
                       on_drain=lambda p, r, y: outs.append(np.asarray(y)),
                       max_requeues=4))
    report = mgr.run()
    assert report["jobs"]["flaky"]["status"] == "done"
    _assert_stream(outs, 2.0, 3)
    backoffs = [r["backoff_s"] for r in report["telemetry"]["retries"]]
    assert backoffs[:2] == [0.05, 0.10]         # exponential, logged


# --------------------------------------------------------- circuit breaker --
def test_flapping_slot_trips_breaker_and_readmits_after_canary():
    """A slot failing threshold runs inside its scoring window is benched;
    it only re-enters placement after a PASSING canary probe — the first
    (injected-to-fail) probe re-arms the bench."""
    policy = FailurePolicy(breaker_window=4, breaker_threshold=2,
                           breaker_cooldown_s=0.0)
    slots = enumerate_slots(min_slots=2)
    mgr = FarmManager(slots=slots, evict_stragglers=False,
                      poll_s=0.01, policy=policy)
    flappy = slots[0].name
    inj = ChaosInjector(telemetry=mgr.telemetry)
    inj.arm([
        Injection("slot_crash", "slot.dispatch", "slot", flappy, at=0),
        Injection("slot_crash", "slot.dispatch", "slot", flappy, at=1),
        Injection("canary_fail", "slot.canary", "slot", flappy, at=0),
    ])
    mgr.injector = inj
    outs = {}
    for i in range(4):
        _, outs[i] = _submit(mgr, f"j{i}", scale=float(i + 1), n=3,
                             barriers=False, max_requeues=3)

    report = mgr.run()
    assert not inj.pending                      # every injection fired
    for i in range(4):
        assert report["jobs"][f"j{i}"]["status"] == "done"
        _assert_stream(outs[i], float(i + 1), 3)
    assert report["telemetry"]["breaker_trips"] == {flappy: 1}
    events = [e["event"] for e in report["telemetry"]["breaker_events"]
              if e["slot"] == flappy]
    t = events.index("trip")
    after = events[t + 1:]
    # probe -> injected canary failure -> probe -> pass -> readmit, in
    # that order: re-admission strictly after a passing canary
    assert after.index("canary_fail") < after.index("canary_pass")
    assert after.index("canary_pass") < after.index("readmit")


# ------------------------------------------------------ snapshot integrity --
@pytest.mark.parametrize("kind", ["snapshot_truncate", "snapshot_corrupt"])
def test_torn_disk_snapshot_falls_back_to_previous_step(tmp_path, kind):
    """A truncated/corrupted ON-DISK snapshot: the requeue restores the
    newest older verifiable step, rewinds its cursor, logs the fallback,
    and still delivers a bit-identical stream."""
    mgr = FarmManager(slots=2, evict_stragglers=False,
                      policy=FailurePolicy(quarantine=True))
    inj = ChaosInjector(telemetry=mgr.telemetry)
    job, outs = _submit(mgr, "ckpt", scale=2.0, n=6)
    job.snapshot_store = ChaosSnapshotStore(
        CheckpointManager(str(tmp_path / kind), keep=3), inj, "ckpt")
    # corrupt the snapshot published at the 3rd commit (step 3), then
    # crash at the very next dispatch so that snapshot is the newest one
    # the requeue tries to restore
    inj.arm([Injection(kind, "snapshot.store", "job", "ckpt", at=2),
             Injection("dispatch_exc", "slot.dispatch", "job", "ckpt",
                       at=3)])
    mgr.injector = inj

    report = mgr.run()
    assert not inj.pending
    assert report["jobs"]["ckpt"]["status"] == "done"
    falls = [f for f in report["telemetry"]["fallbacks"]
             if f["job"] == "ckpt"]
    assert falls and falls[0]["want_step"] == 3 \
        and falls[0]["got_step"] == 2
    _assert_stream(outs, 2.0, 6)                # exactly-once, in order
    assert report["jobs"]["ckpt"]["windows_replayed"] >= 1


def test_no_verifiable_snapshot_replays_from_window_zero():
    """Corrupting the job's ONLY published snapshot leaves nothing
    verifiable: the requeue rewinds to a window-0 replay (verifier
    included) and the fallback is logged with got_step=None."""
    mgr = FarmManager(slots=2, evict_stragglers=False,
                      policy=FailurePolicy(quarantine=True))
    inj = ChaosInjector(telemetry=mgr.telemetry)
    job, outs = _submit(mgr, "solo", scale=2.0, n=4)
    job.snapshot_store = ChaosSnapshotStore(
        MemorySnapshotStore(keep=2), inj, "solo")
    inj.arm([Injection("snapshot_corrupt", "snapshot.store", "job",
                       "solo", at=0),
             Injection("dispatch_exc", "slot.dispatch", "job", "solo",
                       at=1)])
    mgr.injector = inj

    report = mgr.run()
    assert report["jobs"]["solo"]["status"] == "done"
    falls = [f for f in report["telemetry"]["fallbacks"]
             if f["job"] == "solo"]
    assert falls and falls[0]["got_step"] is None
    _assert_stream(outs, 2.0, 4)
    assert report["jobs"]["solo"]["windows_replayed"] >= 1


# -------------------------------------------- async checkpoint write errors --
def test_async_save_failure_surfaces_on_wait_and_next_save(
        tmp_path, monkeypatch):
    """A background checkpoint write failing (full disk) is never silent:
    the recorded error re-raises at the next wait() OR save(), exactly
    once, and the store still restores the last good step."""
    mgr = CheckpointManager(str(tmp_path), keep=3)
    state = {"w": jnp.arange(4, dtype=jnp.float32)}
    mgr.save(state, step=1, blocking=True)

    real_save = np.save

    def full_disk(*a, **k):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(np, "save", full_disk)
    mgr.save(state, step=2)                     # async write fails
    with pytest.raises(OSError, match="No space"):
        mgr.save(state, step=3)                 # surfaces HERE, pre-write
    monkeypatch.setattr(np, "save", real_save)

    mgr.wait()                                  # error already consumed
    mgr.save(state, step=4)
    mgr.wait()
    tree, got = mgr.restore(state, fallback=True)
    assert got == 4
    np.testing.assert_array_equal(np.asarray(tree["w"]),
                                  np.arange(4, dtype=np.float32))
    # the torn step-2 attempt never became a restorable step
    assert 2 not in mgr.steps()


# ----------------------------------------------------------- graceful stop --
@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_request_shutdown_drains_at_barrier_and_keeps_snapshots(slots):
    mgr = FarmManager(slots=slots, evict_stragglers=False,
                      poll_s=0.01)
    job, outs = _submit(mgr, "long", scale=2.0, n=40)
    _submit(mgr, "short", scale=3.0, n=2, barriers=False)
    fired = {"done": False}

    def verify(plan, records, ys):
        if plan.index >= 5 and not fired["done"]:
            fired["done"] = True
            mgr.request_shutdown()

    job.verify = verify
    report = mgr.run(strict=True)       # interrupted is not a failure
    assert report["interrupted"] and mgr.interrupted
    assert report["jobs"]["long"]["status"] == "interrupted"
    assert report["jobs"]["short"]["status"] in ("done", "interrupted")
    # cut at a drain boundary WITH its committed snapshots intact: a
    # restarted farm could resume from the cursor
    assert report["jobs"]["long"]["windows_committed"] >= 1
    assert job.snapshot is not None
    assert job.snapshot_store.verify(job.snapshot.step)


# ------------------------------------------------------- bounded telemetry --
def test_telemetry_event_logs_are_bounded_with_dropped_counts():
    tele = FarmTelemetry(max_events=8)
    for i in range(20):
        tele.eviction("s0", f"j{i}", "why")
        tele.fault("slot.dispatch", "dispatch_exc", job=f"j{i}")
    r = tele.report()
    assert len(r["evictions"]) == 8
    assert len(r["faults"]) == 8
    assert r["events_dropped"] == {"evictions": 12, "faults": 12}
    # the newest events are the ones retained
    assert r["evictions"][-1]["job"] == "j19"
    assert "dropped:" in tele.summary()


# ----------------------------------------------------- injector determinism --
def test_injector_counts_per_scope_and_fires_exactly_once():
    inj = ChaosInjector()
    inj.arm([Injection("dispatch_exc", "slot.dispatch", "job", "a", at=2)])
    # occurrences 0 and 1 pass; other jobs/slots never match
    inj.fire("slot.dispatch", job="a", slot="s0")
    inj.fire("slot.dispatch", job="b", slot="s0")
    inj.fire("slot.dispatch", job="a", slot="s1")
    with pytest.raises(Exception, match="dispatch_exc"):
        inj.fire("slot.dispatch", job="a", slot="s0")
    assert not inj.pending
    assert len(inj.fired) == 1
    inj.fire("slot.dispatch", job="a")          # consumed: never re-fires


def test_injector_fire_is_thread_safe_single_winner():
    inj = ChaosInjector()
    inj.arm([Injection("boom", "p", "job", "j", at=50)])
    hits, lock = [], threading.Lock()

    def hammer():
        for _ in range(50):
            try:
                inj.fire("p", job="j")
            except Exception:
                with lock:
                    hits.append(1)

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(hits) == 1                       # exactly one thread won
    assert not inj.pending
