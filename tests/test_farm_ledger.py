"""ZP-Ledger tests: the durable farm journal and whole-process crash
recovery. Covers the WAL format itself (crc-framed records, torn-tail
truncation, byte-boundary and bit-flip fuzz over the last record,
compaction), the serializable JobSpec registry (round-trip over every
smoke arch), and the recovery contract end-to-end in-process: a farm cut
mid-stream is rebuilt from its journal by a second FarmManager and every
window reaches the sink exactly once ACROSS the two manager lifetimes —
including the one documented re-delivery edge when the final ``deliver``
record itself was torn by the crash."""
import json
import os
import signal
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS
from repro.core import DrainBarrier
from repro.farm import (FarmJob, FarmLedger, FarmManager, JobSpec,
                        choose_resume, register)
from repro.launch.farm import _SignalDrain, train_board_spec

jax.config.update("jax_platform_name", "cpu")


# ----------------------------------------------------------- toy factory --
#: tag -> [(board, window, value)] — module-global so the sink survives a
#: job's reconstruction from its JobSpec (phase-2 recovery builds a NEW
#: closure, but it appends to the same list)
DELIVERED: dict = {}


def _stack(items):
    return jnp.asarray(np.stack(items))


def _nb(state, boundary):
    pass


@register("test.board")
def _test_board(board="b", tag="t", scale=2.0, n_windows=8, delay=0.0):
    import time

    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * float(scale)

    def engine(state, shell, stack):
        if delay:
            time.sleep(delay)
        s, ys = _body(state, stack)
        return s, shell, ys

    def sink(plan, records, ys):
        DELIVERED.setdefault(tag, []).append(
            (board, plan.index, float(np.asarray(ys)[0])))

    return dict(engine=engine,
                windows=[[np.float32(w)] for w in range(int(n_windows))],
                state=jnp.float32(0), shell={},
                stack_fn=_stack, on_drain=sink,
                barriers=(DrainBarrier(every=1, action=_nb),))


def _spec(name, tag, tmp_path, n_windows=8, delay=0.004, scale=2.0):
    return JobSpec(
        name=name, factory="test.board",
        kwargs={"board": name, "tag": tag, "scale": scale,
                "n_windows": n_windows, "delay": delay},
        snapshot_dir=str(tmp_path / "snaps" / name),
        snapshot_keep=4, max_requeues=3)


# =========================================================== WAL format ==
def test_append_replay_round_trip(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="a", spec=None)
    led.append("admit", job="a", slot="cpu:0", attempt=1)
    led.append("commit", job="a", slot="cpu:0", step=2, window=2)
    led.append("deliver", job="a", upto=2)
    led.append("done", job="a", windows=4)
    led.close()

    led2 = FarmLedger(str(tmp_path))
    assert led2.dropped_records == 0 and led2.dropped_bytes == 0
    assert [r["seq"] for r in led2.records()] == [0, 1, 2, 3, 4]
    st = led2.replay()
    j = st.jobs["a"]
    assert j.status == "done" and j.windows == 4
    assert j.commits == [[2, 2]] and j.delivered == 2 and j.attempts == 1
    # appends continue the seq after reopen
    assert led2.append("interrupted", job="a")["seq"] == 5
    led2.close()


def test_numpy_scalars_journal_as_plain_json(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("commit", job="a", slot="s", step=np.int64(3),
               window=np.int32(3))
    led.close()
    with open(os.path.join(str(tmp_path), "journal.jsonl"), "rb") as f:
        payload = f.read().split(b" ", 1)[1]
    rec = json.loads(payload)
    assert rec["step"] == 3 and rec["window"] == 3


def test_torn_tail_truncated_in_place(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="a", spec=None)
    led.append("deliver", job="a", upto=3)
    led.close()
    path = os.path.join(str(tmp_path), "journal.jsonl")
    good = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b"00000000 {\"kind\":\"deliver\",\"job\":\"a\",\"upto")

    led2 = FarmLedger(str(tmp_path))
    assert led2.dropped_records == 1
    assert led2.dropped_bytes > 0
    assert led2.replay().jobs["a"].delivered == 3
    led2.close()
    assert os.path.getsize(path) == good     # tail physically truncated


def test_fuzz_every_byte_boundary_of_last_record(tmp_path):
    """Cutting the journal at EVERY byte offset inside the last record
    must never raise, never advance the delivered cursor past the full
    journal's, and report exactly what was dropped."""
    src = tmp_path / "src"
    led = FarmLedger(str(src))
    led.append("submit", job="a", spec=None)
    led.append("commit", job="a", slot="s", step=1, window=1)
    led.append("deliver", job="a", upto=1)
    led.append("deliver", job="a", upto=4)
    led.close()
    raw = open(os.path.join(str(src), "journal.jsonl"), "rb").read()
    last_start = raw.rstrip(b"\n").rfind(b"\n") + 1

    for cut in range(last_start, len(raw) + 1):
        d = tmp_path / f"cut{cut}"
        os.makedirs(str(d))
        with open(os.path.join(str(d), "journal.jsonl"), "wb") as f:
            f.write(raw[:cut])
        led2 = FarmLedger(str(d))
        st = led2.replay()
        led2.close()
        if cut == len(raw):                 # intact journal
            assert led2.dropped_records == 0 and led2.dropped_bytes == 0
            assert st.jobs["a"].delivered == 4
        else:
            whole_tail = cut == last_start
            assert led2.dropped_records == (0 if whole_tail else 1)
            assert led2.dropped_bytes == cut - last_start
            assert st.jobs["a"].delivered == 1      # never past the drop
            assert st.jobs["a"].commits == [[1, 1]]


def test_fuzz_bit_flip_in_last_record_drops_only_it(tmp_path):
    src = tmp_path / "src"
    led = FarmLedger(str(src))
    led.append("submit", job="a", spec=None)
    led.append("deliver", job="a", upto=2)
    led.append("deliver", job="a", upto=5)
    led.close()
    raw = open(os.path.join(str(src), "journal.jsonl"), "rb").read()
    last_start = raw.rstrip(b"\n").rfind(b"\n") + 1

    for i in range(last_start, len(raw)):
        flipped = bytearray(raw)
        flipped[i] ^= 0x40
        d = tmp_path / f"flip{i}"
        os.makedirs(str(d))
        with open(os.path.join(str(d), "journal.jsonl"), "wb") as f:
            f.write(bytes(flipped))
        led2 = FarmLedger(str(d))
        st = led2.replay()
        led2.close()
        # crc32 catches every single-bit/short-burst corruption: the
        # flipped record is dropped, the cursor stays at the prior record
        assert st.jobs["a"].delivered == 2, f"flip at byte {i}"
        assert st.records == 2


def test_compaction_preserves_replay_state(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="a", spec={"name": "a", "factory": "f"})
    for w in range(1, 13):
        led.append("commit", job="a", slot="s", step=w, window=w)
    led.append("deliver", job="a", upto=10)
    led.append("requeue", job="a", attempt=1, backoff_s=2.5, why="x")
    before = led.replay().jobs["a"]
    led.compact(keep_commits=8)
    after = led.replay().jobs["a"]
    assert len(led.records()) == 1
    assert after.spec == before.spec
    assert after.delivered == 10 and after.requeues == 1
    assert after.backoff_s == 2.5 and after.status == "queued"
    assert after.commits == before.commits[-8:]
    # the compacted journal is itself a valid crc-framed journal
    assert led.append("admit", job="a", slot="s", attempt=2)["seq"] == 1
    led.close()
    led2 = FarmLedger(str(tmp_path))
    assert led2.replay().jobs["a"].status == "running"
    led2.close()


def test_choose_resume_never_passes_delivered_and_skips_torn():
    commits = [[1, 1], [2, 2], [3, 3], [4, 4]]
    assert choose_resume(commits, delivered=3) == (3, 3)
    assert choose_resume(commits, delivered=99) == (4, 4)
    assert choose_resume(commits, delivered=0) == (0, None)
    # step 3 is torn: fall back to the older verifiable commit
    assert choose_resume(commits, 3, verify=lambda s: s != 3) == (2, 2)
    # a verifier that raises means unverifiable, not an error
    def boom(step):
        raise IOError("disk gone")
    assert choose_resume(commits, 3, verify=boom) == (0, None)


# ============================================================= registry ==
def test_jobspec_round_trips_for_every_smoke_arch():
    for arch in ARCH_IDS:
        spec = train_board_spec(arch, steps=4, interval=2)
        d = json.loads(json.dumps(spec.to_json()))
        assert JobSpec.from_json(d) == spec


def test_registered_train_board_builds_a_runnable_job():
    spec = train_board_spec(ARCH_IDS[0], steps=2, interval=2)
    job = spec.build()
    assert job.name == "train" and job.spec == spec
    assert callable(job.engine) and len(job.windows) >= 1


def test_unknown_factory_and_bad_parts_fail_loud():
    with pytest.raises(KeyError, match="unknown job factory"):
        JobSpec(name="x", factory="no.such.factory").build()
    register("test.badparts", lambda: {"engine": lambda *a: a,
                                       "bogus_field": 1})
    with pytest.raises(TypeError, match="bogus_field"):
        JobSpec(name="x", factory="test.badparts").build()


def test_submit_without_spec_dead_letters_on_recovery(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="ghost", spec=None)
    led.close()
    mgr = FarmManager.recover(FarmLedger(str(tmp_path)), slots=1)
    ghost = next(j for j in mgr.jobs if j.name == "ghost")
    assert ghost.status == "quarantined"
    assert "closures" in ghost.error
    mgr.ledger.close()


def test_unbuildable_spec_dead_letters_with_reason(tmp_path):
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="bad",
               spec={"name": "bad", "factory": "no.such.factory"})
    led.close()
    mgr = FarmManager.recover(FarmLedger(str(tmp_path)), slots=1)
    bad = next(j for j in mgr.jobs if j.name == "bad")
    assert bad.status == "quarantined"
    assert "rebuild failed" in bad.error
    mgr.ledger.close()


def test_recover_rebases_relative_backoff_onto_fresh_clock(tmp_path):
    spec = _spec("slow", "unused-backoff", tmp_path)
    led = FarmLedger(str(tmp_path))
    led.append("submit", job="slow", spec=spec.to_json())
    led.append("requeue", job="slow", attempt=1, backoff_s=7.5, why="x")
    led.close()
    mgr = FarmManager.recover(FarmLedger(str(tmp_path)), slots=1,
                              clock=lambda: 1000.0)
    job = next(j for j in mgr.jobs if j.name == "slow")
    # the dead process's absolute deadline is meaningless here: the
    # RELATIVE journal value lands on the recovering clock's origin
    assert job.not_before == pytest.approx(1007.5)
    assert job.requeues == 1
    mgr.ledger.close()


# ===================================================== crash recovery ==
def _cut_mid_stream(mgr, at_window=3):
    """Make every job request a graceful farm stop once its stream passes
    ``at_window`` — the in-process stand-in for process death that still
    exercises journal-seeded resume + delivered-window suppression."""
    for job in mgr.jobs:
        def cut(plan, records, ys, _m=mgr):
            if plan.index >= at_window:
                _m.request_shutdown()
        job.verify = cut


@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_recover_finishes_campaign_exactly_once_across_lifetimes(
        tmp_path, slots):
    tag = f"xonce-{slots}"
    DELIVERED[tag] = []
    n = 8
    mgr = FarmManager(slots=slots, evict_stragglers=False,
                      poll_s=0.01, ledger=FarmLedger(str(tmp_path)))
    for i in range(2):
        mgr.submit_spec(_spec(f"b{i}", tag, tmp_path, n_windows=n,
                              scale=float(i + 1)))
    _cut_mid_stream(mgr)
    rep1 = mgr.run(strict=False)
    mgr.ledger.close()
    assert rep1["interrupted"]
    phase1 = {b: [w for bb, w, _ in DELIVERED[tag] if bb == b]
              for b in ("b0", "b1")}
    assert any(phase1.values())          # delivery was already in flight

    mgr2 = FarmManager.recover(FarmLedger(str(tmp_path)), slots=slots,
                               evict_stragglers=False, poll_s=0.01)
    rep2 = mgr2.run(strict=False)
    mgr2.ledger.close()
    assert all(j["status"] == "done" for j in rep2["jobs"].values())
    rec = rep2["telemetry"]["recoveries"]
    assert {r["job"] for r in rec} == {"b0", "b1"}
    assert any(r["window"] > 0 for r in rec)    # genuine mid-stream resume
    for b in ("b0", "b1"):
        got = [w for bb, w, _ in DELIVERED[tag] if bb == b]
        # every window exactly once ACROSS both manager lifetimes, and
        # each lifetime's deliveries stay in window order
        assert sorted(got) == list(range(n))
        assert len(got) == len(set(got))
        assert got[:len(phase1[b])] == phase1[b]
        assert rep2["jobs"][b]["windows_delivered"] == n
    # the journal agrees, and the recovered run replayed less than the
    # campaign committed
    led = FarmLedger(str(tmp_path))
    fin = led.replay()
    led.close()
    assert all(fin.jobs[b].delivered == n for b in ("b0", "b1"))
    total_replayed = sum(j["windows_replayed"]
                         for j in rep2["jobs"].values())
    total_committed = sum(max((c[1] for c in fin.jobs[b].commits),
                              default=0) for b in ("b0", "b1"))
    assert 0 <= total_replayed < total_committed


def test_torn_deliver_record_redelivers_only_its_own_windows(tmp_path):
    """The WAL's one honest edge: a crash BETWEEN the sink call and its
    ``deliver`` record re-delivers exactly that batch's windows once —
    nothing before the surviving cursor, nothing else twice."""
    tag = "torn-deliver"
    DELIVERED[tag] = []
    n = 8
    mgr = FarmManager(slots=1, evict_stragglers=False,
                      ledger=FarmLedger(str(tmp_path)))
    mgr.submit_spec(_spec("b0", tag, tmp_path, n_windows=n))
    _cut_mid_stream(mgr, at_window=4)
    mgr.run(strict=False)
    mgr.ledger.close()
    phase1 = [w for _, w, _ in DELIVERED[tag]]

    # tear the LAST deliver record out of the journal: the sink already
    # ran for its windows, but the cursor on disk never advanced
    path = os.path.join(str(tmp_path), "journal.jsonl")
    lines = open(path, "rb").read().splitlines(keepends=True)
    delivers = [(i, json.loads(ln.split(b" ", 1)[1]))
                for i, ln in enumerate(lines)
                if json.loads(ln.split(b" ", 1)[1])["kind"] == "deliver"]
    assert len(delivers) >= 2, "pacing produced too few deliver batches"
    torn_i, torn = delivers[-1]
    prev_upto = delivers[-2][1]["upto"]
    assert phase1 == list(range(torn["upto"]))
    with open(path, "wb") as f:
        f.writelines(ln for i, ln in enumerate(lines) if i != torn_i)

    mgr2 = FarmManager.recover(FarmLedger(str(tmp_path)), slots=1,
                               evict_stragglers=False)
    rep2 = mgr2.run(strict=False)
    mgr2.ledger.close()
    assert rep2["jobs"]["b0"]["status"] == "done"
    from collections import Counter
    counts = Counter(w for _, w, _ in DELIVERED[tag])
    dup = set(range(prev_upto, torn["upto"]))
    assert {w for w, c in counts.items() if c == 2} == dup
    assert all(c <= 2 for c in counts.values())
    assert set(counts) == set(range(n))
    # and the re-delivered values are bit-identical to the originals
    by_window = {}
    for _, w, v in DELIVERED[tag]:
        by_window.setdefault(w, []).append(v)
    assert all(len(set(vs)) == 1 for vs in by_window.values())


@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_ledger_on_delivery_bit_identical_to_ledger_off(tmp_path, slots):
    """Attaching a ledger switches delivery to incremental-at-commit; the
    delivered stream (order AND values) must not change."""
    n = 6
    tag_off, tag_on = f"id-off-{slots}", f"id-on-{slots}"
    for tag, ledger in ((tag_off, None),
                        (tag_on, FarmLedger(str(tmp_path)))):
        DELIVERED[tag] = []
        mgr = FarmManager(slots=slots, evict_stragglers=False,
                          poll_s=0.01, ledger=ledger)
        for i in range(2):
            mgr.submit_spec(_spec(f"b{i}", tag, tmp_path / tag,
                                  n_windows=n, delay=0.0,
                                  scale=float(i + 1)))
        mgr.run()
        if ledger is not None:
            ledger.close()
    for b in ("b0", "b1"):
        off = [(w, v) for bb, w, v in DELIVERED[tag_off] if bb == b]
        on = [(w, v) for bb, w, v in DELIVERED[tag_on] if bb == b]
        assert off == on


# ========================================================== satellites ==
def test_checkpoint_save_is_immune_to_caller_mutation(tmp_path):
    """Regression: ``save`` must force host COPIES. With ``np.asarray``
    the host 'copy' of a numpy-backed leaf is an alias, and a caller
    mutating its state right after save() tears the bytes the background
    thread is still writing."""
    cm = CheckpointManager(str(tmp_path), keep=2)
    state = {"w": np.arange(16, dtype=np.float32),
             "b": np.ones(4, dtype=np.float32)}
    want = {k: v.copy() for k, v in state.items()}
    cm.save(state, step=1, blocking=False)      # async write in flight
    state["w"] += 100.0                         # caller mutates in place
    state["b"][:] = -1.0
    cm.wait()
    tree, landed = cm.restore({"w": want["w"], "b": want["b"]}, step=1)
    assert landed == 1
    np.testing.assert_array_equal(tree["w"], want["w"])
    np.testing.assert_array_equal(tree["b"], want["b"])
    assert cm.verify(1)


def test_signal_drain_sigterm_drains_and_reports_143():
    calls = []

    class Mgr:
        def request_shutdown(self):
            calls.append("shutdown")

    drainer = _SignalDrain(Mgr()).install()
    try:
        signal.raise_signal(signal.SIGTERM)
        assert calls == ["shutdown"]
        assert drainer.exit_code == 128 + int(signal.SIGTERM)  # 143
    finally:
        drainer.restore()
    # handlers restored: SIGTERM is back to its previous disposition
    assert signal.getsignal(signal.SIGTERM) != drainer._handle


def test_signal_drain_second_sigint_raises_keyboard_interrupt():
    calls = []

    class Mgr:
        def request_shutdown(self):
            calls.append("shutdown")

    drainer = _SignalDrain(Mgr()).install()
    try:
        signal.raise_signal(signal.SIGINT)
        assert drainer.exit_code == 130 and calls == ["shutdown"]
        with pytest.raises(KeyboardInterrupt):
            signal.raise_signal(signal.SIGINT)
    finally:
        drainer.restore()


def test_killrestart_gate_keeps_jax_out_of_the_parent():
    """One process per device: the kill/restart gate runs its oracle,
    victim and recovery as child processes one after another, so the
    parent never initialises a JAX backend (on a chip it would hold the
    device its children need)."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    probe = ("import json\n"
             "from repro.launch.farm import run_killrestart_smoke\n"
             "out = run_killrestart_smoke()\n"
             "from jax._src import xla_bridge\n"
             "print(json.dumps({'backend': "
             "xla_bridge.backends_are_initialized(),\n"
             "                  'problems': out['problems']}))\n")
    proc = subprocess.run([sys.executable, "-c", probe],
                          env={**os.environ, "JAX_PLATFORMS": "cpu",
                               "PYTHONPATH": src},
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["backend"] is False
    assert not [p for p in out["problems"] if "oracle" in p], out
