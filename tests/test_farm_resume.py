"""Checkpointed farm requeue tests: an evicted job resumes from its last
ACCEPTED drain-barrier snapshot instead of replaying the window stream
from window 0 — delivered outputs stay bit-identical to an uninterrupted
run, committed windows never re-run and never re-deliver, a veto keeps
the resume point BEFORE the rejected window, donating engines survive
both the no-snapshot replay and the snapshot-resume path, and the
snapshot travels the checkpoint store's atomic publish path (in-memory
and on-disk)."""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, MemorySnapshotStore,
                              step_to_window)
from repro.core import DrainBarrier, iter_windows
from repro.farm import FarmJob, FarmManager

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------- toy workload --
@jax.jit
def _body(state, stack):
    return state + jnp.sum(stack), stack * 2.0


def _engine(state, shell, stack):
    s, ys = _body(state, stack)
    return s, shell, ys


def _windows(n_items=16, group=2):
    return list(iter_windows([np.float32(i) for i in range(n_items)],
                             group))


def _stack(items):
    return jnp.asarray(np.stack(items))


def _submit_board(mgr, *, windows=None, engine=_engine, verify=None,
                  barrier_every=2, commits=None, name="j", state=None,
                  **extra):
    got = []
    barriers = ()
    if barrier_every:
        action = (lambda s, b: commits.append((b, float(s)))
                  ) if commits is not None else (lambda s, b: None)
        barriers = (DrainBarrier(every=barrier_every, action=action),)
    mgr.submit(FarmJob(
        name=name, engine=engine,
        windows=_windows() if windows is None else windows,
        state=jnp.float32(0) if state is None else state, shell={},
        stack_fn=_stack, verify=verify,
        on_drain=lambda p, r, y: got.append((p.index, p.start,
                                             np.asarray(y))),
        barriers=barriers, **extra))
    return got


def _baseline(windows=None):
    mgr = FarmManager(slots=3, evict_stragglers=False)
    got = _submit_board(mgr, windows=windows)
    mgr.run()
    return got, np.asarray(mgr.results["j"][0])


def _evict_trigger(mgr, at_index, name="j"):
    """verify hook that force-marks the job once it has delivered window
    ``at_index`` (first attempt only): the job's slot thread honors the
    mark at its next drain boundary."""
    fired = {"done": False}

    def verify(plan, records, ys):
        if plan.index >= at_index and not fired["done"]:
            fired["done"] = True
            mgr.force_evict(name)

    return verify


# ----------------------------------------------------- resume bit-identity --
def test_one_slot_resume_zero_replay_and_bit_identical():
    """The acceptance contract on a one-slot farm: a job evicted right
    after N committed barriers requeues onto its only seat, replays ZERO
    windows before its resume cursor, and its delivered outputs + final
    state are bit-identical to the uninterrupted run."""
    base, base_state = _baseline()
    mgr = FarmManager(slots=1, evict_stragglers=False)
    got = _submit_board(mgr, verify=_evict_trigger(mgr, 4))
    rep = mgr.run()
    j = rep["jobs"]["j"]
    assert j["status"] == "done" and j["requeues"] == 1
    ev = rep["telemetry"]["evictions"]
    assert len(ev) == 1 and j["slot"] == ev[0]["slot"]  # its only seat
    assert j["windows_committed"] > 0
    assert j["windows_replayed"] == 0           # resumed AT the commit
    resumes = rep["telemetry"]["resumes"]
    assert len(resumes) == 1 and resumes[0]["job"] == "j"
    assert resumes[0]["window"] == j["windows_committed"]
    assert len(got) == len(base) == 8
    for (ia, sa, ya), (ib, sb, yb) in zip(base, got):
        assert ia == ib and sa == sb
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


def test_async_resume_bit_identical_and_replays_less_than_committed():
    """Same contract on a three-slot farm: the evict lands at the drain
    boundary after window 3, the resumed job never re-runs more than the
    uncommitted tail (replayed < committed) and delivery is
    bit-identical."""
    base, base_state = _baseline()
    mgr = FarmManager(slots=3, evict_stragglers=False)
    got = _submit_board(mgr, verify=_evict_trigger(mgr, 3))
    rep = mgr.run()
    j = rep["jobs"]["j"]
    assert j["status"] == "done" and j["requeues"] == 1
    assert j["windows_committed"] > 0
    assert j["windows_replayed"] < j["windows_committed"]
    assert any(r["job"] == "j" and r["window"] > 0
               for r in rep["telemetry"]["resumes"])
    assert len(got) == len(base)
    for (ia, sa, ya), (ib, sb, yb) in zip(base, got):
        assert ia == ib and sa == sb
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


@pytest.mark.parametrize("slots", [pytest.param(3, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_resumed_on_drain_never_redelivers_a_committed_window(slots):
    """Exactly-once across the eviction: every window index reaches
    on_drain once, in window order — the committed prefix is retained,
    never re-delivered by the resumed attempt."""
    mgr = FarmManager(slots=slots, evict_stragglers=False)
    got = _submit_board(mgr, verify=_evict_trigger(mgr, 4))
    rep = mgr.run()
    assert rep["jobs"]["j"]["requeues"] == 1
    counts = Counter(i for i, _, _ in got)
    assert all(c == 1 for c in counts.values()), counts
    assert [i for i, _, _ in got] == list(range(8))     # in order


@pytest.mark.parametrize("slots", [pytest.param(3, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_veto_then_evict_resumes_from_barrier_before_the_veto(slots):
    """A drain veto blocks BOTH the barrier action and the snapshot: the
    faulted attempt requeues with its resume point at the last barrier
    before the rejected window, the rejected window re-runs (and passes),
    and every boundary commits exactly once across the two attempts."""
    base, _ = _baseline()
    commits: list = []
    failed = {"n": 0}

    def verify(plan, records, ys):
        if plan.index == 3 and failed["n"] == 0:
            failed["n"] += 1
            raise AssertionError("synthetic commit divergence")

    mgr = FarmManager(slots=slots, evict_stragglers=False)
    got = _submit_board(mgr, verify=verify, commits=commits)
    rep = mgr.run()
    j = rep["jobs"]["j"]
    assert j["status"] == "done" and j["requeues"] == 1
    assert rep["telemetry"]["drain_vetoes"] == 1
    # resumed from the barrier BEFORE the vetoed window (index 3): only
    # the rejected window itself was re-run
    resumes = rep["telemetry"]["resumes"]
    assert len(resumes) == 1 and resumes[0]["window"] == 3
    assert j["windows_replayed"] == 1
    # each boundary committed exactly once, in order, across both attempts
    assert [b for b, _ in commits] == [2, 4, 6, 8, 10, 12, 14, 16]
    for (ia, sa, ya), (ib, sb, yb) in zip(base, got):
        assert ia == ib and sa == sb
        np.testing.assert_array_equal(ya, yb)


# ------------------------------------------------------- donating engines --
def _donating_engine():
    return jax.jit(lambda state, shell, stack:
                   (state + jnp.sum(stack), shell, stack * 2.0),
                   donate_argnums=(0,))


def test_donating_engine_full_replay_after_eviction():
    """Regression: requeue replay used to crash with "Array has been
    deleted" when the engine donates its state — admission now dispatches
    from fresh copies, so the job's state stays a valid replay source
    with no snapshot involved (evicted before any barrier)."""
    base, base_state = _baseline()
    mgr = FarmManager(slots=3, evict_stragglers=False)
    got = _submit_board(mgr, engine=_donating_engine(), barrier_every=0)
    mgr.force_evict("j")            # at the first drain boundary
    rep = mgr.run()
    assert rep["jobs"]["j"]["requeues"] == 1
    assert rep["telemetry"]["resumes"] == []    # no snapshot: full replay
    assert len(got) == len(base)
    for (ia, sa, ya), (ib, sb, yb) in zip(base, got):
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


@pytest.mark.parametrize("slots", [pytest.param(3, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_donating_engine_snapshot_resume_bit_identical(slots):
    """The acceptance criterion's donating case: snapshots are host
    copies, so a donated-and-deleted device buffer is never a restore
    source — the resumed attempt restores fresh buffers and finishes
    bit-identical."""
    base, base_state = _baseline()
    mgr = FarmManager(slots=slots, evict_stragglers=False)
    got = _submit_board(mgr, engine=_donating_engine(),
                        verify=_evict_trigger(mgr, 4))
    rep = mgr.run()
    j = rep["jobs"]["j"]
    assert j["status"] == "done" and j["requeues"] == 1
    assert any(r["window"] > 0 for r in rep["telemetry"]["resumes"])
    assert len(got) == len(base)
    for (ia, sa, ya), (ib, sb, yb) in zip(base, got):
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


# -------------------------------------------------- tail windows + stores --
def test_resume_keeps_tail_window_math_for_non_divisible_streams():
    """A 7-step stream in windows of 2 (sizes 2,2,2,1): resuming past the
    cut keeps global step ids and the short tail window intact."""
    windows = _windows(n_items=7, group=2)
    base, base_state = _baseline(windows=windows)
    assert [s for _, s, _ in base] == [0, 2, 4, 6]
    mgr = FarmManager(slots=3, evict_stragglers=False)
    got = _submit_board(mgr, windows=windows, verify=_evict_trigger(mgr, 2))
    rep = mgr.run()
    assert rep["jobs"]["j"]["requeues"] == 1
    assert rep["telemetry"]["resumes"][0]["window"] > 0
    assert [(i, s) for i, s, _ in got] == [(0, 0), (1, 2), (2, 4), (3, 6)]
    for (_, _, ya), (_, _, yb) in zip(base, got):
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


def test_on_disk_snapshot_store_resumes_through_atomic_publish(tmp_path):
    """``FarmJob.snapshot_store`` accepts a real CheckpointManager: the
    barrier snapshot rides the step-directory atomic publish and the
    requeued attempt restores from disk."""
    base, base_state = _baseline()
    store = CheckpointManager(str(tmp_path / "snaps"), keep=2)
    mgr = FarmManager(slots=3, evict_stragglers=False)
    got = _submit_board(mgr, verify=_evict_trigger(mgr, 4),
                        snapshot_store=store)
    rep = mgr.run()
    j = rep["jobs"]["j"]
    assert j["status"] == "done" and j["requeues"] == 1
    assert store.steps()                        # snapshots hit disk
    assert max(store.steps()) >= rep["telemetry"]["resumes"][0]["step"]
    for (_, _, ya), (_, _, yb) in zip(base, got):
        np.testing.assert_array_equal(ya, yb)
    np.testing.assert_array_equal(np.asarray(mgr.results["j"][0]),
                                  base_state)


def test_memory_snapshot_store_contract():
    """MemorySnapshotStore honors the CheckpointManager surface: host-copy
    isolation at save, retention, latest/explicit-step restore, and the
    step→window cursor mapping used by resume."""
    store = MemorySnapshotStore(keep=2)
    with pytest.raises(FileNotFoundError):
        store.restore()
    src = {"a": np.zeros(3, np.float32)}
    store.save(src, step=2)
    src["a"][:] = 7.0                   # mutate AFTER publish
    tree, step = store.restore()
    assert step == 2
    np.testing.assert_array_equal(tree["a"], np.zeros(3))   # isolated copy
    store.save(src, step=4)
    store.save(src, step=6)
    assert store.steps() == [4, 6]      # retention: keep=2
    tree, step = store.restore(step=4)
    assert step == 4
    # step→window mapping (non-divisible tail counts once complete)
    assert step_to_window(0, 4) == 0
    assert step_to_window(8, 4) == 2
    assert step_to_window(10, 4) == 3
    assert step_to_window(7, 2) == 4


# ------------------------------------------------ commit-stream verifier --
def _toy_oracle(scale=2.0):
    def oracle_step(state, batch):
        b = jnp.float32(batch)
        aux = {"scanned": (),
               "tail": ({"checksum": jnp.stack([b, b * scale])},)}
        return state + b, {}, aux
    return oracle_step


def _commit_records(batches, scale=2.0):
    rows = np.asarray([[0.0, b, b * scale] for b in batches], np.float64)
    return {"fifos": {"commits": {"data": rows, "count": len(rows),
                                  "dropped": 0}}}


def test_commit_stream_verifier_resumes_mid_stream():
    """snapshot()/restore() rewind the oracle to a barrier: the windows
    after the snapshot re-verify against the restored oracle state and
    stream position, and a post-resume divergence reports the true global
    step."""
    from repro.core.coemu import CommitDivergence, CommitStreamVerifier

    batches = [float(i) for i in range(8)]
    v = CommitStreamVerifier(_toy_oracle(), jnp.float32(0), batches,
                             layers=1)
    v(1, _commit_records(batches[0:2]))
    v(3, _commit_records(batches[2:4]))
    snap = v.snapshot()
    assert int(snap["step"]) == 4 and int(snap["consumed"]) == 4
    v(5, _commit_records(batches[4:6]))         # beyond the barrier...
    v.restore(snap)                             # ...evicted: rewind
    v(5, _commit_records(batches[4:6]))         # re-verify, same stream
    assert v.step == 6
    assert float(np.asarray(v.state)) == sum(batches[:6])
    # a divergence after resume localizes the true global step
    bad = _commit_records(batches[6:8])
    bad["fifos"]["commits"]["data"][1, 1] += 100.0
    with pytest.raises(CommitDivergence) as e:
        v(7, bad)
    assert e.value.step == 7


@pytest.mark.parametrize("slots", [pytest.param(3, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_stateful_verifier_rewinds_on_no_snapshot_requeue(slots):
    """A snapshot/restore verifier must rewind to its STARTING position
    when the job requeues without any accepted barrier (full window-0
    replay) — otherwise the replay is compared against an oracle already
    advanced mid-stream and a healthy board fails verification."""
    class PositionVerifier:
        def __init__(self):
            self.pos = 0

        def __call__(self, plan, records, ys):
            assert plan.index == self.pos, (plan.index, self.pos)
            self.pos += 1

        def snapshot(self):
            return {"pos": self.pos}

        def restore(self, snap):
            self.pos = snap["pos"]

    mgr = FarmManager(slots=slots, evict_stragglers=False)
    v = PositionVerifier()
    # barrier never fires (every=1000): eviction happens with NO snapshot
    got = _submit_board(mgr, verify=v, barrier_every=1000)
    mgr.force_evict("j")
    rep = mgr.run()
    assert rep["jobs"]["j"]["status"] == "done"
    assert rep["jobs"]["j"]["requeues"] == 1
    assert rep["telemetry"]["resumes"] == []        # full replay path
    assert rep["telemetry"]["drain_vetoes"] == 0    # verifier never misfired
    assert [i for i, _, _ in got] == list(range(8))


def test_commit_stream_verifier_restore_needs_reiterable_source():
    """A one-shot iterator source can be consumed but never rewound —
    restore() must say so instead of silently resuming mid-wrong."""
    from repro.core.coemu import CommitStreamVerifier

    v = CommitStreamVerifier(_toy_oracle(), jnp.float32(0),
                             iter([0.0, 1.0]), layers=1)
    snap = v.snapshot()
    with pytest.raises(ValueError, match="re-iterable"):
        v.restore(snap)


# ----------------------------------------------------- extract_block args --
def test_extract_block_validates_layer_idx_for_every_smoke_arch():
    """Out-of-range layer_idx raises a ValueError naming the arch and its
    layer count (the 2-layer smoke archs made the bare IndexError a
    recurring trap); in-range extraction still works."""
    from repro.configs import ARCH_IDS, get_smoke_config
    from repro.core.decompose import extract_block
    from repro.models import build_model
    from repro.models.runtime import Runtime

    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        for bad in (cfg.num_layers, cfg.num_layers + 3, -1):
            with pytest.raises(ValueError) as e:
                # params untouched on the validation path
                extract_block(None, cfg, bad, Runtime(), 2, 16)
            assert cfg.name in str(e.value)
            assert str(cfg.num_layers) in str(e.value)

    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    sub = extract_block(params, cfg, cfg.num_layers - 1, Runtime(), 2, 16)
    assert sub.layer_idx == cfg.num_layers - 1


# -------------------------------------------------------- capture resume --
def test_capture_keeps_committed_rows_across_resume():
    """A FarmJob capture under checkpointed requeue: rows for committed
    windows survive the eviction, only the discarded tail is re-recorded
    — one row per window overall."""
    from repro.roofline import WindowCapture

    cap = WindowCapture()
    mgr = FarmManager(slots=3, evict_stragglers=False)
    _submit_board(mgr, verify=_evict_trigger(mgr, 4), capture=cap)
    rep = mgr.run()
    assert rep["jobs"]["j"]["requeues"] == 1
    assert rep["telemetry"]["resumes"][0]["window"] > 0
    assert [r["window"] for r in cap.rows] == list(range(8))
