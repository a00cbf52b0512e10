"""ZP-Farm CLI: a mixed co-emulation workload through one FarmManager.

The paper's end state — a farm of scaled-down DUTs behind one host — as an
executable: a TRAIN engine (fused clock-gated windows, P-Shell commit
stream), a DECODE engine (scan-fused autoregressive windows, telemetry
FIFO), and N VERIFY boards (extracted subsystems replaying captured
boundary traffic) all share one farm pass: device placement (round-robin
virtual slots on a single-device host), dynamic admission, per-slot
watchdogs, straggler eviction + requeue, and one aggregated telemetry
report.

Host-loop mode: ``--async`` (default) drives each slot from its own
dispatcher thread — a slow board delays only itself; ``--lockstep`` is the
single-thread round-robin oracle the async mode is bit-identity-tested
against.

  PYTHONPATH=src python -m repro.launch.farm --steps 8
  PYTHONPATH=src python -m repro.launch.farm --steps 8 --synthetic-straggler
  PYTHONPATH=src python -m repro.launch.farm --steps 8 --lockstep \\
      --synthetic-straggler

``--synthetic-straggler`` slows one verify board down. In lockstep mode it
is force-marked for eviction (the deterministic path — dispatch-cost
observations there come from too few windows to flag it); in async mode
NOTHING is marked: the board must be caught by the watchdog from its
measured per-window WALL time alone — the wall-time-divergence gate the CI
``farm-async-smoke`` leg enforces. The run exits non-zero unless every job
completes verified — and, when a straggler was injected, unless it was
actually evicted (in async mode: evicted specifically as a ``straggler``),
requeued, and still delivered correct outputs.

``--restart-smoke`` is the checkpointed-requeue gate (CI
``farm-restart-smoke``): a long board with per-window checkpoint barriers
is evicted mid-stream and must RESUME from its last accepted snapshot —
the run exits non-zero unless the job re-ran fewer windows than it had
committed (``windows_replayed < windows_committed``), resumed through the
telemetry resume log, and still delivered bit-identical outputs:

  PYTHONPATH=src python -m repro.launch.farm --restart-smoke
  PYTHONPATH=src python -m repro.launch.farm --restart-smoke --lockstep

``--chaos SEED`` is the fault-recovery gate (CI ``farm-chaos-smoke``): a
toy multi-board workload is run twice — once fault-free (the bit-identity
oracle), once under a seeded ``ChaosHarness`` schedule injecting board
crashes, hung drains, commit divergence, snapshot corruption/truncation,
thread death, and results stalls — plus one genuinely poisoned board that
must land in quarantine. The run exits non-zero unless every injected
fault fired AND was recovered (eviction/fallback/veto evidence in
telemetry), every non-quarantined board's outputs are bit-identical to
the oracle, and the poisoned board was dead-lettered, not raised:

  PYTHONPATH=src python -m repro.launch.farm --chaos 7
  PYTHONPATH=src python -m repro.launch.farm --chaos 7 --lockstep

``--lanes N`` is the lane-batched-boards gate (CI ``farm-lanes-smoke``):
N identical-arch boards sharing one weight tree must coalesce into ONE
vmap-ed dispatch stream (one ClientDriver drives all N) and deliver
outputs bit-identical to the same boards run solo. ``--chaos-lane``
additionally fails one board's verify mid-stream: the farm must evict
exactly that lane (requeued solo, resuming from its per-lane barrier
snapshot) while the surviving lanes keep running:

  PYTHONPATH=src python -m repro.launch.farm --lanes 8 --chaos-lane
  PYTHONPATH=src python -m repro.launch.farm --lanes 8 --lockstep

``--scope-smoke`` is the ZP-Scope non-interference gate (CI
``farm-scope-smoke``): the same boards run scope-off (the oracle) and
scope-on must deliver bit-identical outputs and final states while the
scoped run produces a non-empty fleet scope report; ``--lanes N`` runs
the lane-coalesced variant (per-lane counter slices). ``--scope N``
enables the plane on the full mixed workload at a read rate of every N
window drains, and ``--telemetry-out PATH`` dumps the merged telemetry +
scope report as mergeable JSON:

  PYTHONPATH=src python -m repro.launch.farm --scope-smoke
  PYTHONPATH=src python -m repro.launch.farm --scope-smoke --lanes 8 \\
      --lockstep
  PYTHONPATH=src python -m repro.launch.farm --steps 8 --scope 2 \\
      --telemetry-out telemetry.json

``--ledger DIR`` attaches a ZP-Ledger write-ahead journal to the run: a
toy multi-board workload journals every control-plane decision to
``DIR/journal.jsonl``, publishes durable per-window snapshots under
``DIR/snaps/``, and delivers each window as an atomic per-window output
file under ``DIR/outputs/``. ``--kill-after-commits N`` arms a
``process_kill`` chaos injection that SIGKILLs the whole process at the
N-th journaled commit (no cleanup, no flushes — real process death);
``--recover`` rebuilds the farm from the journal and finishes the
campaign. ``--killrestart-smoke`` is the whole-process crash-recovery
gate (CI ``farm-killrestart-smoke``): it runs the fault-free oracle
in-process, launches a subprocess that kills itself mid-stream, then a
``--recover`` subprocess that must finish with bit-identical per-window
outputs, every window delivered exactly once across both process
lifetimes, and ``windows_replayed < windows_committed``:

  PYTHONPATH=src python -m repro.launch.farm --killrestart-smoke
  PYTHONPATH=src python -m repro.launch.farm --killrestart-smoke \\
      --lockstep

SIGINT (^C) and SIGTERM during a farm run are a GRACEFUL stop: every
board is cut at its next drain boundary, committed prefixes and
published snapshots are kept, the partial report + telemetry summary
are printed, and the process exits ``128 + signum`` (130 for SIGINT,
143 for SIGTERM — what a supervisor's kill/timeout expects from a clean
drain). A second signal kills immediately.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import hashlib
import json
import os
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config
from repro.core import DrainBarrier, plan_windows
from repro.core.commit import default_shell_config, make_ingest
from repro.core.pshell import PShell, drain, shell_init, stack_batches
from repro.core.coemu import submit_subsystem_jobs
from repro.core.scope import ScopeSpec
from repro.core.watchdog import Watchdog
from repro.data import SyntheticPipeline
from repro.farm import (FailurePolicy, FarmJob, FarmLedger, FarmManager,
                        JobSpec, register)
from repro.farm.chaos import ChaosHarness, ChaosInjector, Injection
from repro.farm.placement import enumerate_slots
from repro.launch.serve import decode_shell_config, make_decode_engine
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.roofline import WindowCapture
from repro.serve import make_prefill_step
from repro.train.optim import OptConfig
from repro.train.step import init_state, make_group_step
from repro.utils import dtype_of, enable_compile_cache


class _SignalDrain:
    """Graceful-stop signal plumbing for a farm run. First SIGINT *or*
    SIGTERM: the farm drains at the next barrier, keeps its committed
    prefixes and published snapshots, ``run()`` returns the partial
    report, and the process should exit ``exit_code`` (``128 + signum``:
    130 for ^C, 143 for SIGTERM — SIGTERM is what supervisors, container
    runtimes, and CI timeouts send, and it must get the same clean drain
    a ^C does). A second SIGINT raises KeyboardInterrupt; a second
    SIGTERM restores the default disposition and re-delivers it — an
    immediate hard kill either way."""

    def __init__(self, mgr):
        self.mgr = mgr
        self.exit_code = 130
        self._hits = 0
        self._prev = {}

    def install(self) -> "_SignalDrain":
        for s in (signal.SIGINT, signal.SIGTERM):
            self._prev[s] = signal.signal(s, self._handle)
        return self

    def restore(self):
        for s, h in self._prev.items():
            signal.signal(s, h)
        self._prev = {}

    def _handle(self, signum, frame):
        self._hits += 1
        if self._hits == 1:
            self.exit_code = 128 + int(signum)
            print(f"{signal.Signals(signum).name}: draining farm at the "
                  f"next barrier (signal again to kill)", file=sys.stderr)
            self.mgr.request_shutdown()
        elif signum == signal.SIGTERM:
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)
        else:
            signal.signal(signal.SIGINT,
                          self._prev.get(signal.SIGINT, signal.SIG_DFL))
            raise KeyboardInterrupt


def _train_board_parts(cfg, steps, interval, batch=2, seq=16, seed=0):
    """Fused train engine's job parts: P-Shell drain + stack_batches per
    window. The engine donates the train state: a published-width train
    state cannot sit on a chip twice, as input and as output of one
    window. So the initial state is a factory, initialised on the device
    per attempt, and requeue replays never read a donated buffer. Shared
    by the CLI submit path and the ``zp.train_board`` registered factory
    — everything here is rebuilt from plain kwargs, which is what lets
    crash recovery re-instantiate the board from its journaled JobSpec
    instead of a dead process's closures."""
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    ingest = make_ingest(cfg)
    shell = PShell(default_shell_config(cfg, sample_interval=interval),
                   ingest)
    engine = shell.compile_group(
        make_group_step(model, OptConfig(), ingest=ingest))
    pipe = SyntheticPipeline(cfg, batch, seq, seed=seed)
    windows = [[next(pipe) for _ in range(p.size)]
               for p in plan_windows(steps, interval)]
    pipe.close()
    init = jax.jit(functools.partial(init_state, model))
    return dict(engine=engine, windows=windows,
                state=lambda: init(jax.random.key(seed)),
                shell=shell.init(), drain_fn=drain,
                stack_fn=stack_batches)


@register("zp.train_board")
def _train_board_factory(arch="granite-8b", steps=8, interval=2, batch=2,
                         seq=16, seed=0):
    return _train_board_parts(get_smoke_config(arch), steps, interval,
                              batch=batch, seq=seq, seed=seed)


def train_board_spec(arch: str, steps: int, interval: int,
                     **kw) -> JobSpec:
    """Serializable JobSpec for the fused TRAIN board (the durable-intake
    analog of :func:`submit_train_job`, minus the loss sink — a recovered
    board delivers through the ledger's exactly-once cursor instead)."""
    return JobSpec(name="train", factory="zp.train_board",
                   kwargs={"arch": arch, "steps": int(steps),
                           "interval": int(interval), **kw})


def submit_train_job(mgr, cfg, steps, interval, batch=2, seq=16, seed=0,
                     capture=None):
    """Fused train engine as a farm job (see ``_train_board_parts``)."""
    parts = _train_board_parts(cfg, steps, interval, batch=batch, seq=seq,
                               seed=seed)
    losses: list = []

    def sink(plan, records, metrics):
        losses.extend(np.asarray(metrics["loss"], np.float32).tolist())

    if capture is not None:
        # the board's own first compile is the HLO cost source — no
        # dry-run second lowering (attach_cost is the offline path)
        parts["engine"] = capture.attach_engine(parts["engine"])
    mgr.submit(FarmJob(name="train", on_drain=sink, capture=capture,
                       **parts))
    return losses


def submit_decode_job(mgr, cfg, params, gen, interval, batch=2,
                      prompt_len=16, seed=0):
    """Scan-fused decode engine over ``params`` as a farm job (prefill
    runs up front; the farm schedules the windowed decode with its
    telemetry shell). The weights ride in the board's state, so they live
    on whichever slot's device the board is placed on. The engine donates
    that state, so each attempt starts from its own copy (a factory)."""
    from repro.data.pipeline import make_batch_fn

    model = build_model(cfg, Runtime())
    bf = make_batch_fn(cfg, batch, prompt_len, seed)
    b = {k: jnp.asarray(v) for k, v in bf(0).items() if k != "labels"}
    max_len = prompt_len + (cfg.num_patches if cfg.family == "vlm" else 0) \
        + gen + 8
    cache, logits = jax.jit(make_prefill_step(model, max_len))(params, b)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    engine = make_decode_engine(model)
    windows = [list(range(p.start, p.boundary))
               for p in plan_windows(gen - 1, interval)]
    toks: list = [np.asarray(tok)]

    def sink(plan, records, ys):
        toks.append(np.asarray(ys)[:, :, 0].T)

    mgr.submit(FarmJob(
        name="decode", engine=engine, windows=windows,
        state=lambda: jax.tree.map(jnp.copy, (params, cache, tok)),
        shell=shell_init(decode_shell_config(interval)),
        drain_fn=drain, stack_fn=stack_batches, on_drain=sink))
    return toks


def prewarm(mgr, devices) -> float:
    """Build every board's bitstream before the farm runs: call each
    submitted job's engine once on its first window, on every device in
    ``devices`` (the distinct devices of the farm's slots — a board may
    land on any of them), so jit compilation happens up front, not on the
    boards. Each call runs on a fresh initial state placed as the farm
    places it (committed to the device), and its results are discarded.
    The paper's farm synthesizes bitstreams before deployment; the host
    analog matters doubly on a virtual-slot (single-device) host, where
    one board's in-run compile contends with every other board's windows
    and pollutes the wall-time samples the straggler detector compares.
    Returns the total prewarm seconds."""
    t0 = time.perf_counter()
    for device in devices:
        with jax.default_device(device):
            for job in mgr.jobs:
                items = next(job._window_iter(), None)
                if not items:
                    continue
                stack = job.stack_fn(items) if job.stack_fn else items
                args = jax.device_put((job._initial("state"),
                                       job._initial("shell"), stack),
                                      device)
                jax.block_until_ready(job.engine(*args))
    return time.perf_counter() - t0


@dataclasses.dataclass
class SoakBoard:
    """Handle for the synthetic async straggler (see
    ``submit_soak_straggler``): the job, its delivered outputs, and the
    bitwise-expected outputs an uninterrupted run would produce."""
    job: FarmJob
    outputs: list
    expected: list

    def preserved(self) -> bool:
        return (len(self.outputs) == len(self.expected)
                and all(np.array_equal(a, b)
                        for a, b in zip(self.outputs, self.expected)))


def submit_soak_straggler(mgr, n_windows: int = 150,
                          delay: float = 0.5) -> SoakBoard:
    """A long-workload board gone slow, for the wall-time eviction gate.

    The board sleeps per window on its FIRST attempt only — modeling a slow
    SEAT rather than a slow job, so the requeued attempt replays fast on
    its new slot. The stream is long (ceiling ``n_windows * delay``)
    because on a virtual-slot host the watchdog's fleet reference is only
    clean once the farm-wide jit-compile phase has passed — the straggler
    must still be running then to be caught, and eviction is what cuts the
    stream short. Its ``verify`` asserts every window bit-exactly, so
    preserved-outputs checks are meaningful."""
    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * 2.0

    def engine(state, shell, stack):
        if board.job.attempts == 1:
            time.sleep(delay)           # the slow seat
        s, ys = _body(state, stack)
        return s, shell, ys

    items = [np.float32(i) for i in range(n_windows)]
    expected = [np.asarray([x * 2.0], np.float32) for x in items]
    outs: list = []

    def verify(plan, records, ys):
        np.testing.assert_array_equal(np.asarray(ys), expected[plan.start])

    board = SoakBoard(
        job=FarmJob(
            name="soak", engine=engine, windows=[[x] for x in items],
            state=jnp.float32(0), shell={},
            stack_fn=lambda it: jnp.asarray(np.stack(it)), verify=verify,
            on_drain=lambda p, r, y: outs.append(np.asarray(y))),
        outputs=outs, expected=expected)
    mgr.submit(board.job)
    return board


def submit_restart_board(mgr, n_windows: int = 40, evict_at: int = 8,
                         delay: float = 0.02) -> SoakBoard:
    """A long board with a checkpoint barrier at EVERY window boundary,
    for the checkpointed-requeue gate: its verify force-marks the job
    mid-stream (first attempt only), so the eviction lands with committed
    snapshots behind it and the requeued attempt must resume from the
    last accepted barrier instead of window 0. The per-window ``delay``
    keeps attempt 1 slow enough that the async control plane's sweep can
    signal the mark at a drain boundary; the replay runs full speed."""
    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * 2.0

    def engine(state, shell, stack):
        if board.job.attempts == 1:
            time.sleep(delay)
        s, ys = _body(state, stack)
        return s, shell, ys

    items = [np.float32(i) for i in range(n_windows)]
    expected = [np.asarray([x * 2.0], np.float32) for x in items]
    outs: list = []
    marked = {"done": False}

    def verify(plan, records, ys):
        np.testing.assert_array_equal(np.asarray(ys), expected[plan.start])
        if plan.index >= evict_at and not marked["done"]:
            marked["done"] = True
            mgr.force_evict("restart")

    board = SoakBoard(
        job=FarmJob(
            name="restart", engine=engine, windows=[[x] for x in items],
            state=jnp.float32(0), shell={},
            stack_fn=lambda it: jnp.asarray(np.stack(it)), verify=verify,
            on_drain=lambda p, r, y: outs.append(np.asarray(y)),
            barriers=(DrainBarrier(every=1, action=lambda s, b: None),)),
        outputs=outs, expected=expected)
    mgr.submit(board.job)
    return board


def run_restart_smoke(mode: str = "async", slots: int = 3) -> dict:
    """The ``farm-restart-smoke`` gate: a mid-stream eviction must resume
    from the job's last accepted drain-barrier snapshot. Exits non-zero
    (via ``ok``) unless the evicted board requeued, replayed FEWER windows
    than it had committed, logged a snapshot resume, and still delivered
    outputs bit-identical to an uninterrupted run."""
    mgr = FarmManager(slots=slots, mode=mode, evict_stragglers=False)
    board = submit_restart_board(mgr)
    report = mgr.run(strict=False)
    j = report["jobs"]["restart"]
    resumes = report["telemetry"]["resumes"]
    ok = (j["status"] == "done"
          and j["requeues"] >= 1
          and j["windows_committed"] > 0
          and j["windows_replayed"] < j["windows_committed"]
          and any(r["job"] == "restart" and r["window"] > 0
                  for r in resumes)
          and board.preserved())
    return {
        "mode": mode,
        "jobs": report["jobs"],
        "resumes": resumes,
        "evictions": report["telemetry"]["evictions"],
        "preserved": board.preserved(),
        "windows_delivered": len(board.outputs),
        "ok": ok,
    }


def _chaos_board(mgr, name: str, scale: float, n_windows: int,
                 max_requeues: int = 6) -> list:
    """One toy chaos board: window *w* yields ``[w * scale]`` (analytic,
    so divergence is detectable bit-exactly), a checkpoint barrier at
    every window boundary (the snapshot-fault target), and a generous
    requeue budget (chaos schedules at most one fault pair per board).
    Returns the board's delivered-output list."""
    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * scale

    def engine(state, shell, stack):
        s, ys = _body(state, stack)
        return s, shell, ys

    outs: list = []
    mgr.submit(FarmJob(
        name=name, engine=engine,
        windows=[[np.float32(w)] for w in range(n_windows)],
        state=jnp.float32(0), shell={},
        stack_fn=lambda it: jnp.asarray(np.stack(it)),
        on_drain=lambda p, r, y: outs.append(np.asarray(y)),
        barriers=(DrainBarrier(every=1, action=lambda s, b: None),),
        max_requeues=max_requeues))
    return outs


def run_chaos_smoke(seed: int, mode: str = "async", slots: int = 4,
                    n_jobs: int = 8, n_windows: int = 6) -> dict:
    """The ``farm-chaos-smoke`` gate: run the toy workload fault-free
    (the oracle), then again under the seed's injection schedule plus one
    permanently-poisoned board. ``ok`` requires every injected fault
    fired and recovered, non-quarantined outputs bit-identical to the
    oracle, and the poisoned board quarantined (never raised)."""
    def build(policy=None, timeout_s=600.0):
        # straggler eviction OFF: wall-time heuristics are the one
        # nondeterministic eviction source, and chaos needs the injected
        # faults to be the ONLY faults
        m = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                        watchdog=Watchdog(timeout_s=timeout_s),
                        poll_s=0.01, policy=policy)
        o = {f"board{i}": _chaos_board(m, f"board{i}", float(i + 1),
                                       n_windows) for i in range(n_jobs)}
        return m, o

    mgr0, oracle = build()
    mgr0.run()

    mgr, outs = build(policy=FailurePolicy(quarantine=True),
                      timeout_s=1.5)
    harness = ChaosHarness(mgr, seed)
    schedule = harness.arm()

    # the poison board: submitted AFTER arm() so no injection targets it
    # — its engine genuinely always fails, and the farm must dead-letter
    # it and still complete everything else
    def poison_engine(state, shell, stack):
        raise RuntimeError("poisoned board output bus")

    mgr.submit(FarmJob(
        name="poison", engine=poison_engine,
        windows=[[np.float32(0)]], state=jnp.float32(0), shell={},
        stack_fn=lambda it: jnp.asarray(np.stack(it)), max_requeues=2))

    report = mgr.run(strict=False)
    problems = harness.gate(report, expect_quarantined={"poison"})
    for name in oracle:
        same = (len(outs[name]) == len(oracle[name])
                and all(np.array_equal(a, b)
                        for a, b in zip(outs[name], oracle[name])))
        if not same:
            problems.append(f"{name}: outputs diverged from the "
                            f"fault-free oracle")
    return {
        "mode": mode,
        "seed": seed,
        "schedule": [dataclasses.asdict(i) for i in schedule],
        "faults_injected": len(harness.injector.fired),
        "jobs": {n: j["status"] for n, j in report["jobs"].items()},
        "quarantined": report["quarantined"],
        "retries": len(report["telemetry"]["retries"]),
        "fallbacks": report["telemetry"]["fallbacks"],
        "breaker_trips": report["telemetry"]["breaker_trips"],
        "problems": problems,
        "ok": not problems,
    }


@jax.jit
def _lane_body(state, stack):
    def step(s, x):
        y = jnp.tanh(x @ s["w"]) + s["bias"]
        return ({"bias": s["bias"] + 0.01 * jnp.sum(y), "w": s["w"]},
                jnp.sum(y, axis=-1))
    return jax.lax.scan(step, state, stack)


def _lane_engine(state, shell, stack):
    s, ys = _lane_body(state, stack)
    return s, shell, ys


def _lane_stack(items):
    # ONE shared function: lane coalescing requires the same stack_fn
    # OBJECT across members (per-board lambdas would defeat it)
    return jnp.asarray(np.stack(items))


def _submit_lane_boards(mgr, w, n_boards: int, n_steps: int, group: int,
                        chaos_lane: bool, lane_key, scope=None):
    """``n_boards`` identical-arch boards over ONE shared weight ``w``
    (per-board state differs only in seed-derived inputs and bias — the
    lane packer must broadcast ``w`` as a single device copy). With
    ``chaos_lane`` the last board's verify raises ONCE mid-stream: in a
    lane-batched run that is a lane veto — only that lane may be detached
    and requeued solo; every other lane keeps running."""
    outs = {}
    marked = {"done": False}
    for i in range(n_boards):
        name = f"lane-board{i}"
        outs[name] = []
        rng = np.random.RandomState(100 + i)
        items = [rng.randn(4, 8).astype(np.float32)
                 for _ in range(n_steps)]
        verify = None
        if chaos_lane and i == n_boards - 1:
            def verify(plan, records, ys):
                if plan.index == 3 and not marked["done"]:
                    marked["done"] = True
                    raise RuntimeError("chaos lane: injected veto")
        mgr.submit(FarmJob(
            name=name, engine=_lane_engine,
            windows=[items[k:k + group]
                     for k in range(0, n_steps, group)],
            state={"bias": jnp.float32(i) * 0.5, "w": w}, shell={},
            stack_fn=_lane_stack,
            on_drain=lambda p, r, y, n=name: outs[n].append(
                np.asarray(y)),
            barriers=(DrainBarrier(every=1, action=lambda s, b: None),),
            verify=verify, lane_key=lane_key, max_requeues=2,
            scope=scope))
    return outs


def run_lanes_smoke(lanes: int = 8, chaos_lane: bool = False,
                    mode: str = "async", slots: int = 2,
                    n_steps: int = 12, group: int = 2) -> dict:
    """The ``farm-lanes-smoke`` gate: ``lanes`` identical-arch boards must
    coalesce into one vmap-ed dispatch stream and stay bit-identical to
    the same boards run solo (the oracle). With ``--chaos-lane`` one
    board's verify raises mid-stream: the farm must evict EXACTLY that
    lane (one lane veto, one requeue, snapshot resume), keep the other
    lanes running, and still deliver every board bit-identical."""
    w = jnp.asarray(np.random.RandomState(0).randn(8, 8)
                    .astype(np.float32))
    n_windows = (n_steps + group - 1) // group

    # solo oracle: same boards, no lane coalescing, no chaos
    mgr0 = FarmManager(slots=slots, mode=mode, evict_stragglers=False)
    oracle = _submit_lane_boards(mgr0, w, lanes, n_steps, group,
                                 chaos_lane=False, lane_key=None)
    mgr0.run()

    mgr = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                      lanes=lanes)
    outs = _submit_lane_boards(mgr, w, lanes, n_steps, group,
                               chaos_lane=chaos_lane,
                               lane_key="lanes-smoke")
    report = mgr.run(strict=False)
    tel = report["telemetry"]

    problems = []
    for name in oracle:
        same = (len(outs[name]) == len(oracle[name])
                and all(np.array_equal(a, b)
                        for a, b in zip(outs[name], oracle[name])))
        if not same:
            problems.append(f"{name}: outputs diverged from solo oracle")
    if any(j["status"] != "done" for j in report["jobs"].values()):
        problems.append("not every board finished done")
    if tel.get("lanes_per_dispatch_max", 1) < lanes:
        problems.append(
            f"boards did not coalesce: lanes_per_dispatch_max="
            f"{tel.get('lanes_per_dispatch_max')} < {lanes}")
    chaos_name = f"lane-board{lanes - 1}"
    if chaos_lane:
        vetoes = tel.get("lane_vetoes", [])
        if len(vetoes) != 1 or vetoes[0]["job"] != chaos_name:
            problems.append(f"expected exactly one lane veto on "
                            f"{chaos_name}, got {vetoes}")
        j = report["jobs"][chaos_name]
        if j["requeues"] != 1:
            problems.append(f"chaos lane requeues={j['requeues']}, "
                            f"expected 1")
        others = [report["jobs"][n]["requeues"] for n in outs
                  if n != chaos_name]
        if any(others):
            problems.append(f"surviving lanes were requeued: {others}")
        if not (0 < j["windows_committed"]
                and j["windows_replayed"] < n_windows):
            problems.append(
                f"chaos lane replayed the full stream "
                f"(committed={j['windows_committed']}, "
                f"replayed={j['windows_replayed']}) — snapshot resume "
                f"did not carry over")
    elif tel.get("lane_vetoes"):
        problems.append(f"unexpected lane vetoes: {tel['lane_vetoes']}")

    return {
        "mode": mode,
        "lanes": lanes,
        "chaos_lane": chaos_lane,
        "jobs": report["jobs"],
        "lanes_per_dispatch_max": tel.get("lanes_per_dispatch_max"),
        "lane_vetoes": tel.get("lane_vetoes", []),
        "problems": problems,
        "ok": not problems,
    }


def run_scope_smoke(mode: str = "async", lanes: int = 1,
                    every_n: int = 2, slots: int = 2,
                    n_steps: int = 12, group: int = 2) -> dict:
    """The ``farm-scope-smoke`` gate: the SAME boards run scope-off (the
    oracle) and scope-on must deliver bit-identical outputs and final
    states — the ZP-Scope non-interference invariant — and the scoped run
    must produce a non-empty fleet scope report (on-device counters
    actually drained at the read rate). ``lanes > 1`` additionally runs
    the boards lane-coalesced, exercising the per-lane counter slices."""
    w = jnp.asarray(np.random.RandomState(0).randn(8, 8)
                    .astype(np.float32))
    n = max(1, lanes)
    lane_key = "scope-smoke" if n > 1 else None

    mgr0 = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                       lanes=n)
    oracle = _submit_lane_boards(mgr0, w, n, n_steps, group,
                                 chaos_lane=False, lane_key=lane_key)
    mgr0.run()

    spec = ScopeSpec(every_n_windows=every_n)
    mgr = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                      lanes=n)
    outs = _submit_lane_boards(mgr, w, n, n_steps, group,
                               chaos_lane=False, lane_key=lane_key,
                               scope=spec)
    report = mgr.run(strict=False)
    sc = report["telemetry"]["scope"]

    problems = []
    for name in oracle:
        same = (len(outs[name]) == len(oracle[name])
                and all(np.array_equal(a, b)
                        for a, b in zip(outs[name], oracle[name])))
        if not same:
            problems.append(f"{name}: outputs diverged with scope on")
        s0, _ = mgr0.results[name]
        s1, sh1 = mgr.results[name]
        if not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(s0),
                                   jax.tree.leaves(s1))):
            problems.append(f"{name}: final state diverged with scope on")
        if isinstance(sh1, dict) and "zp_scope" in sh1:
            problems.append(f"{name}: scope counters leaked into results")
    if any(j["status"] != "done" for j in report["jobs"].values()):
        problems.append("not every board finished done")
    if not sc["samples"]:
        problems.append("scope report is empty: no samples drained")
    for job, row in sc["jobs"].items():
        if not row.get("windows") or not row.get("steps"):
            problems.append(f"{job}: scope counters never advanced "
                            f"({row})")

    return {
        "mode": mode,
        "lanes": n,
        "every_n_windows": every_n,
        "jobs": report["jobs"],
        "scope": sc,
        "problems": problems,
        "ok": not problems,
    }


# ------------------------------------------------------------ ZP-Ledger --

def _toy_stack(items):
    return jnp.asarray(np.stack(items))


def _noop_barrier(state, boundary):
    pass


def _write_window_file(out_dir: str, board: str, index: int, ys) -> str:
    """Atomic, idempotent per-window delivery: tmp + fsync + rename keyed
    on the GLOBAL window index. This is the documented sink contract for
    the WAL's one honest edge — a window whose ``deliver`` record was
    torn by a crash is re-delivered once after recovery, and rewriting
    the same window file with the same bytes is a no-op."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{board}_w{index:05d}.json")
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump({"window": int(index), "y": np.asarray(ys).tolist()},
                  f, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


@register("zp.ledger_board")
def _ledger_board_factory(board="board", scale=1.0, n_windows=24,
                          out_dir=".", delay=0.005):
    """Registered toy board for the durable-farm gates: window *w* yields
    ``[w * scale]`` (analytic — divergence after recovery is detectable
    bit-exactly), a checkpoint barrier at every window boundary, and an
    idempotent per-window file sink. The per-window ``delay`` paces
    commits so the control plane's incremental delivery cursor tracks
    them — at a mid-stream SIGKILL the journal then holds BOTH a commit
    frontier and a delivered cursor behind it, the state recovery must
    reconcile."""
    scale = float(scale)

    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * scale

    def engine(state, shell, stack):
        if delay:
            time.sleep(delay)
        s, ys = _body(state, stack)
        return s, shell, ys

    def sink(plan, records, ys):
        _write_window_file(out_dir, board, plan.index, ys)

    return dict(
        engine=engine,
        windows=[[np.float32(w)] for w in range(int(n_windows))],
        state=jnp.float32(0), shell={},
        stack_fn=_toy_stack, on_drain=sink,
        barriers=(DrainBarrier(every=1, action=_noop_barrier),))


def ledger_board_spec(name: str, scale: float, n_windows: int,
                     ledger_dir: str) -> JobSpec:
    """One durable toy board: outputs, snapshots, and journal all live
    under ``ledger_dir`` so a recovering process finds everything by the
    journal alone. ``snapshot_keep=4`` leaves enough on-disk history for
    ``choose_resume`` to rewind past a torn newest snapshot."""
    return JobSpec(
        name=name, factory="zp.ledger_board",
        kwargs={"board": name, "scale": float(scale),
                "n_windows": int(n_windows),
                "out_dir": os.path.join(ledger_dir, "outputs")},
        snapshot_dir=os.path.join(ledger_dir, "snaps", name),
        snapshot_keep=4, max_requeues=4)


def run_ledger_farm(ledger_dir: str, mode: str = "async",
                    recover: bool = False, kill_after=None,
                    n_boards: int = 3, n_windows: int = 24,
                    slots: int = 2) -> dict:
    """One durable-farm process lifetime: fresh (``recover=False``)
    submits ``n_boards`` toy boards through the journaled JobSpec intake;
    ``recover=True`` rebuilds the whole farm from ``ledger_dir``'s
    journal and finishes the campaign. ``kill_after=N`` arms a
    ``process_kill`` injection at the N-th journaled commit — the caller
    sees this process die by SIGKILL, mid-write-order, exactly like an
    OOM kill."""
    ledger = FarmLedger(ledger_dir)
    if recover:
        mgr = FarmManager.recover(ledger, slots=slots, mode=mode,
                                  evict_stragglers=False, poll_s=0.01)
    else:
        mgr = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                          poll_s=0.01, ledger=ledger)
        for i in range(n_boards):
            mgr.submit_spec(ledger_board_spec(
                f"board{i}", float(i + 1), n_windows, ledger_dir))
    if kill_after is not None:
        injector = ChaosInjector(telemetry=mgr.telemetry)
        # scope "farm" counts every journaled commit across all boards:
        # die at the Nth, whoever commits it
        injector.arm([Injection(kind="process_kill", point="ledger.commit",
                                scope="farm", name="*",
                                at=max(0, int(kill_after) - 1))])
        mgr.injector = injector
    report = mgr.run(strict=False)
    jobs = report["jobs"]       # empty-journal recover: a minimal report
    out = {
        "mode": mode,
        "recover": recover,
        "jobs": jobs,
        "recoveries": report["telemetry"].get("recoveries", []),
        "interrupted": report.get("interrupted", False),
        "windows_committed": sum(j["windows_committed"]
                                 for j in jobs.values()),
        "windows_replayed": sum(j["windows_replayed"]
                                for j in jobs.values()),
        "windows_delivered": sum(j["windows_delivered"]
                                 for j in jobs.values()),
        "ok": (not report.get("interrupted", False)
               and all(j["status"] == "done" for j in jobs.values())),
    }
    if not report.get("interrupted", False):
        # bound journal growth once the campaign settled — NOT inside
        # FarmManager.run(), which must leave the full audit trail for
        # a supervisor (and the kill-restart gate) to inspect
        ledger.compact()
    ledger.close()
    return out


def _read_window_files(out_dir: str) -> dict:
    files = {}
    if os.path.isdir(out_dir):
        for fn in sorted(os.listdir(out_dir)):
            if fn.endswith(".json"):
                with open(os.path.join(out_dir, fn), "rb") as f:
                    files[fn] = f.read()
    return files


def run_killrestart_smoke(mode: str = "async", n_boards: int = 3,
                          n_windows: int = 24, kill_after: int = 8,
                          slots: int = 2) -> dict:
    """The ``farm-killrestart-smoke`` gate: whole-process crash recovery.
    Three subprocess phases, run one after another so that one process
    at a time holds the device (this process never initialises a JAX
    backend): (1) a fault-free oracle run; (2) a victim armed with
    ``process_kill`` at the ``kill_after``-th journaled commit — it must
    die by SIGKILL with delivery already in flight; (3) a ``--recover``
    subprocess over the victim's ledger that must finish the campaign.
    ``ok`` requires the recovery resumed at least one board mid-stream
    (window > 0), replayed fewer windows than the campaign committed,
    delivered every window exactly once across both lifetimes (per-board
    cursors reach exactly
    ``n_windows``), and produced per-window output files bit-identical to
    the oracle's."""
    import shutil
    import subprocess
    import tempfile

    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    base = tempfile.mkdtemp(prefix="zp-killrestart-")
    problems: list = []
    out: dict = {"mode": mode, "kill_after": kill_after}
    try:
        env = dict(os.environ)
        env["PYTHONPATH"] = src_root + os.pathsep \
            + env.get("PYTHONPATH", "")

        def ledger_cmd(ledger_dir):
            return [sys.executable, "-m", "repro.launch.farm",
                    "--ledger", ledger_dir, f"--{mode}",
                    "--slots", str(slots),
                    "--ledger-boards", str(n_boards),
                    "--ledger-windows", str(n_windows)]

        oracle_dir = os.path.join(base, "oracle")
        oracle = subprocess.run(ledger_cmd(oracle_dir), env=env,
                                capture_output=True, text=True, timeout=600)
        if oracle.returncode != 0:
            problems.append(f"fault-free oracle run exited "
                            f"{oracle.returncode}: {oracle.stderr[-500:]}")

        victim_dir = os.path.join(base, "victim")
        common = ledger_cmd(victim_dir)
        victim = subprocess.run(
            common + ["--kill-after-commits", str(kill_after)],
            env=env, capture_output=True, text=True, timeout=600)
        if victim.returncode != -signal.SIGKILL:
            problems.append(f"victim exited {victim.returncode}, expected "
                            f"{-signal.SIGKILL} (SIGKILL'd mid-commit)")

        # the victim's journal as the recovery will see it: the delivered
        # cursors must already be moving, or the exactly-once suppression
        # across lifetimes would be exercised vacuously
        led = FarmLedger(victim_dir)
        pre = led.replay()
        led.close()
        pre_delivered = {n: js.delivered for n, js in pre.jobs.items()}
        out["pre_delivered"] = pre_delivered
        if sum(pre_delivered.values()) <= 0:
            problems.append("victim died before delivering any window — "
                            "the kill landed too early to gate recovery")

        rec = subprocess.run(common + ["--recover"], env=env,
                             capture_output=True, text=True, timeout=600)
        if rec.returncode != 0:
            problems.append(f"recovery run exited {rec.returncode}: "
                            f"{rec.stderr[-500:]}")
        try:
            recovered = json.loads(rec.stdout)
        except ValueError:
            recovered = {}
            problems.append("recovery run printed no parseable report")
        out["recovered"] = recovered

        if recovered:
            if not recovered.get("ok"):
                problems.append("recovered run did not finish every "
                                "board done")
            if not any(r["window"] > 0
                       for r in recovered.get("recoveries", [])):
                problems.append("no board resumed mid-stream "
                                "(every recovery fell back to window 0)")
            replayed = recovered.get("windows_replayed", -1)
            committed = recovered.get("windows_committed", 0)
            if not 0 <= replayed < committed:
                problems.append(
                    f"windows_replayed={replayed} not below "
                    f"windows_committed={committed} — recovery replayed "
                    f"the full stream")

        # exactly-once across both lifetimes: the final journal's deliver
        # cursor per board is exactly the stream length — never short
        # (lost windows) and never past it (double delivery)
        led = FarmLedger(victim_dir)
        final = led.replay()
        led.close()
        for i in range(n_boards):
            js = final.jobs.get(f"board{i}")
            if js is None or js.status != "done":
                problems.append(f"board{i}: not done in the final journal")
            elif js.delivered != n_windows:
                problems.append(
                    f"board{i}: delivered cursor {js.delivered} != "
                    f"{n_windows} windows across both lifetimes")

        want = _read_window_files(os.path.join(oracle_dir, "outputs"))
        got = _read_window_files(os.path.join(victim_dir, "outputs"))
        if len(want) != n_boards * n_windows:
            problems.append(f"oracle produced {len(want)} window files, "
                            f"expected {n_boards * n_windows}")
        if got != want:
            missing = sorted(set(want) - set(got))
            diff = sorted(k for k in set(want) & set(got)
                          if want[k] != got[k])
            problems.append(f"outputs diverged from the oracle: "
                            f"missing={missing[:5]} differing={diff[:5]}")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    out["problems"] = problems
    out["ok"] = not problems
    return out


def _poison_board(n_windows: int = 4) -> FarmJob:
    """A board ZP-Cert must reject at admission: the engine smuggles a
    host round-trip (``pure_callback``) into the window body — the
    silent per-window host-sync class (rule ZC101)."""
    def engine(state, shell, stack):
        host = jax.pure_callback(
            lambda x: np.asarray(x),
            jax.ShapeDtypeStruct((), jnp.float32), state)
        return state + host, shell, stack * 2.0

    return FarmJob(
        name="poison", engine=engine,
        windows=[[np.float32(i)] for i in range(n_windows)],
        state=jnp.float32(0), shell={}, stack_fn=_toy_stack)


def run_certify_smoke(work_dir: str | None = None, mode: str = "async",
                      slots: int = 2, n_boards: int = 2,
                      n_windows: int = 8) -> dict:
    """The ``farm-certify-smoke`` gate: a ``certify=True`` farm given
    ``n_boards`` healthy boards plus one statically-broken board must
    dead-letter the broken one AT ADMISSION — an unrun quarantine with a
    durable ``certify_fail`` journal record — while the co-submitted
    healthy boards finish bit-identical to a ``certify=False`` oracle
    run of the same boards."""
    import shutil
    import tempfile
    base = work_dir or tempfile.mkdtemp(prefix="zp_certify_")
    own = work_dir is None
    problems = []
    out = {"mode": mode}
    try:
        cert_dir = os.path.join(base, "certified")
        ledger = FarmLedger(cert_dir)
        mgr = FarmManager(slots=slots, mode=mode, evict_stragglers=False,
                          poll_s=0.01, ledger=ledger, certify=True)
        for i in range(n_boards):
            mgr.submit_spec(ledger_board_spec(
                f"board{i}", float(i + 1), n_windows, cert_dir))
        poison = mgr.submit(_poison_board())
        if poison.status != "quarantined":
            problems.append("poison board was not quarantined at submit")
        report = mgr.run(strict=False)
        fails = [r for r in ledger.records()
                 if r.get("kind") == "certify_fail"]
        ledger.close()
        if not any(r.get("job") == "poison" for r in fails):
            problems.append("no certify_fail journal record for poison")
        if not any(not c["ok"] for c in
                   report["telemetry"].get("certifications", [])):
            problems.append("no failed-certification telemetry event")
        healthy = {k: v for k, v in report["jobs"].items()
                   if k != "poison"}
        if not all(j["status"] == "done" for j in healthy.values()):
            problems.append(f"healthy boards did not finish: "
                            f"{ {k: j['status'] for k, j in healthy.items()} }")

        oracle_dir = os.path.join(base, "oracle")
        oracle = FarmManager(slots=slots, mode=mode,
                             evict_stragglers=False, poll_s=0.01)
        for i in range(n_boards):
            oracle.submit_spec(ledger_board_spec(
                f"board{i}", float(i + 1), n_windows, oracle_dir))
        oracle_report = oracle.run(strict=False)
        if not all(j["status"] == "done"
                   for j in oracle_report["jobs"].values()):
            problems.append("oracle run did not finish")
        got = _read_window_files(os.path.join(cert_dir, "outputs"))
        want = _read_window_files(os.path.join(oracle_dir, "outputs"))
        if len(want) != n_boards * n_windows:
            problems.append(f"oracle produced {len(want)} window files, "
                            f"expected {n_boards * n_windows}")
        if got != want:
            problems.append("certified run's outputs diverged from the "
                            "uncertified oracle")
        out.update(
            jobs=report["jobs"],
            certify_fail_records=fails,
            certifications=report["telemetry"].get("certifications", []),
            windows_delivered=sum(j["windows_delivered"]
                                  for j in healthy.values()))
    finally:
        if own:
            shutil.rmtree(base, ignore_errors=True)
    out["problems"] = problems
    out["ok"] = not problems
    return out


def write_telemetry(path: str, out: dict, run_key: str) -> str:
    """Dump a farm run's merged telemetry + scope report as JSON, keyed
    by run so repeated invocations MERGE into one file (the
    BENCH_results.json convention — one mergeable record per run)."""
    data = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
    key, i = run_key, 1
    while key in data:
        i += 1
        key = f"{run_key}#{i}"
    data[key] = {
        "ts": time.time(),
        "telemetry": out.get("telemetry", {}),
        "scope": out.get("telemetry", {}).get("scope", {}),
        "summary": out.get("summary"),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    return key


def _delivered_digest(outputs) -> str:
    """SHA-256 over every delivered window's drained records and outputs,
    in window order: equal digests mean bit-identical delivered streams."""
    h = hashlib.sha256()
    for _, records, ys in outputs:
        for leaf in jax.tree.leaves((records, ys)):
            a = np.asarray(leaf)
            h.update(str((a.dtype, a.shape)).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _tree_devices(tree) -> list:
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   if isinstance(leaf, jax.Array) for d in leaf.devices()})


def run_farm(cfg, slots, *, steps: int, gen: int, batch: int, seq: int,
             prompt_len: int, verify_seq: int, interval: int = 2,
             synthetic_straggler: bool = False, straggler_factor: float = 6.0,
             roofline: bool = False, seed: int = 0,
             mode: str = "async", handle_sigint: bool = False,
             scope: ScopeSpec = None, certify: bool = False) -> dict:
    """The mixed farm over ``cfg``: one fused train board (``steps``
    steps of ``batch`` x ``seq``), one decode board (``gen`` tokens per
    sequence after a ``prompt_len`` prompt) and two subsystem verify
    boards (layers 0 and 1, ``batch`` x ``verify_seq`` activations),
    windows of ``interval`` steps. ``slots`` is a slot count (one slot
    per device; virtual slots fill in on a smaller host) or a
    :class:`~repro.farm.placement.DeviceSlot` list."""
    t_setup = time.perf_counter()
    if isinstance(slots, int):
        slots = enumerate_slots(min_slots=slots)
    # min_s floors the straggler RATIO check: the mixed workload's boards
    # legitimately differ in window cost (a decode window costs more than
    # a one-layer verify window), so sub-200ms medians are never flagged
    # however large the ratio — only genuinely slow boards are evictable
    mgr = FarmManager(slots=slots, straggler_factor=straggler_factor,
                      straggler_min_s=0.2, mode=mode, certify=certify)

    capture = WindowCapture() if roofline else None
    losses = submit_train_job(mgr, cfg, steps, interval, batch=batch,
                              seq=seq, seed=seed, capture=capture)
    # one weight tree, initialised on the device, serves the decode board
    # and the verify boards' in-situ oracle capture
    params = jax.jit(build_model(cfg, Runtime()).init)(jax.random.key(seed))
    toks = submit_decode_job(mgr, cfg, params, gen=gen, interval=interval,
                             batch=batch, prompt_len=prompt_len, seed=seed)

    n_verify = max(2, steps // 4)
    xs = [jax.random.normal(jax.random.key(i), (batch, verify_seq,
                                                 cfg.d_model))
          .astype(dtype_of(cfg.dtype)) for i in range(n_verify)]
    pos = jnp.tile(jnp.arange(verify_seq, dtype=jnp.int32)[None],
                   (batch, 1))
    finalize = submit_subsystem_jobs(mgr, params, cfg, Runtime(), xs, pos,
                                     layer_idxs=[0, 1],
                                     group_size=interval)

    if scope is not None:
        # every board opts into the instrumentation plane: on-device
        # counters drained at the read rate, feeding the scope telemetry
        # channel and the watchdog's work-rate straggler signal
        for j in mgr.jobs:
            j.scope = scope

    straggler = None
    soak = None
    if synthetic_straggler:
        if mode == "async":
            # wall-time path: a long-workload board gone slow, caught by
            # the watchdog from measured window wall alone
            soak = submit_soak_straggler(mgr)
            straggler = soak.job
        else:
            # lockstep path: dispatch-cost observations on the short
            # verify streams are too few to flag (window 0 is compile), so
            # the board is force-marked — the deterministic oracle path
            straggler = mgr.jobs[-1]        # last verify board
            inner = straggler.engine

            def slow_engine(state, shell, stack):
                time.sleep(0.15)            # a board gone slow
                return inner(state, shell, stack)

            straggler.engine = slow_engine
            mgr.force_evict(straggler.name)

    setup_s = time.perf_counter() - t_setup
    prewarm_s = prewarm(mgr, list(dict.fromkeys(s.device for s in slots)))
    drainer = _SignalDrain(mgr).install() if handle_sigint else None
    t_run = time.perf_counter()
    try:
        report = mgr.run(strict=False)
    finally:
        if drainer is not None:
            drainer.restore()
    run_s = time.perf_counter() - t_run
    if report["interrupted"]:
        # graceful stop: partial report + telemetry, no pass/fail gating —
        # committed prefixes and published snapshots were kept
        return {
            "mode": mode,
            "interrupted": True,
            "exit_code": drainer.exit_code if drainer else 130,
            "prewarm_s": round(prewarm_s, 3),
            "jobs": report["jobs"],
            "telemetry": report["telemetry"],
            "summary": mgr.telemetry.summary(),
            "ok": False,
        }
    reps = finalize()

    out = {
        "mode": mode,
        "setup_s": setup_s,
        "prewarm_s": round(prewarm_s, 3),
        "run_s": run_s,
        "jobs": report["jobs"],
        "telemetry": report["telemetry"],
        "summary": mgr.telemetry.summary(),
        "train": {"steps": len(losses),
                  "loss_first": losses[0] if losses else None,
                  "loss_last": losses[-1] if losses else None,
                  "all_finite": bool(np.all(np.isfinite(losses)))},
        "decode": {"tokens": int(np.concatenate(toks, axis=1).size),
                   "expected": batch * gen},
        "verify": {k: {"summary": r.summary(),
                       "max_rel_err": r.max_rel_err}
                   for k, r in reps.items()},
        # where each board's final carry lives, and a bit-level digest of
        # what it delivered: the multi-device placement checks read these
        "placement": {n: {"slot": report["jobs"][n]["slot"],
                          "devices": _tree_devices(mgr.results[n])}
                      for n in mgr.results},
        "delivered_sha256": {n: _delivered_digest(o)
                             for n, o in mgr.outputs.items()},
    }
    if capture is not None:
        out["roofline"] = capture.report()

    ok = all(j["status"] == "done" for j in report["jobs"].values())
    ok = ok and not any(r.diverged for r in reps.values())
    if synthetic_straggler:
        evs = report["telemetry"]["evictions"]
        evicted = {e["job"] for e in evs}
        ok = ok and straggler.name in evicted \
            and report["jobs"][straggler.name]["requeues"] >= 1
        if soak is not None:
            # the CI wall-time-divergence gate: the board must have been
            # caught by the watchdog (not a forced mark), and its delivered
            # outputs must be bit-identical to an uninterrupted run
            ok = ok and any(e["job"] == straggler.name
                            and e["why"] == "straggler" for e in evs)
            ok = ok and soak.preserved()
            out["soak"] = {"windows": len(soak.outputs),
                           "preserved": soak.preserved()}
    out["ok"] = ok
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="granite-8b")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--sample-interval", type=int, default=2)
    ap.add_argument("--synthetic-straggler", action="store_true")
    ap.add_argument("--straggler-factor", type=float, default=6.0)
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--restart-smoke", action="store_true",
                    help="checkpointed-requeue gate: a mid-stream "
                         "eviction must resume from the last accepted "
                         "barrier snapshot (replayed < committed) with "
                         "bit-identical outputs")
    ap.add_argument("--lanes", type=int, metavar="N", default=None,
                    help="lane-batched boards gate: N identical-arch "
                         "boards must coalesce into one vmap-ed dispatch "
                         "stream bit-identical to solo runs")
    ap.add_argument("--chaos-lane", action="store_true",
                    help="with --lanes: one board's verify raises "
                         "mid-stream; exactly that lane must be evicted "
                         "and requeued solo while the others keep "
                         "running bit-identically")
    ap.add_argument("--scope", type=int, metavar="N", default=None,
                    help="enable the ZP-Scope instrumentation plane on "
                         "every board with a read rate of every N window "
                         "drains")
    ap.add_argument("--scope-smoke", action="store_true",
                    help="non-interference gate: the same boards run "
                         "scope-off and scope-on must be bit-identical "
                         "and the scoped run must produce a non-empty "
                         "scope report (combine with --lanes for the "
                         "lane-coalesced variant)")
    ap.add_argument("--telemetry-out", metavar="PATH", default=None,
                    help="dump the run's merged telemetry + scope report "
                         "as JSON at PATH (repeated runs merge by key, "
                         "like BENCH_results.json)")
    ap.add_argument("--ledger", metavar="DIR", default=None,
                    help="attach a ZP-Ledger write-ahead journal at DIR "
                         "and run the durable toy workload (outputs, "
                         "snapshots, and journal all under DIR)")
    ap.add_argument("--recover", action="store_true",
                    help="with --ledger: rebuild the farm from DIR's "
                         "journal after a process death and finish the "
                         "campaign")
    ap.add_argument("--kill-after-commits", type=int, metavar="N",
                    default=None,
                    help="with --ledger: SIGKILL this process at the "
                         "N-th journaled commit (chaos process_kill — "
                         "models an OOM kill mid-write-order)")
    ap.add_argument("--ledger-boards", type=int, default=3,
                    help="with --ledger: number of toy boards")
    ap.add_argument("--ledger-windows", type=int, default=24,
                    help="with --ledger: windows per toy board")
    ap.add_argument("--killrestart-smoke", action="store_true",
                    help="whole-process crash-recovery gate: oracle run, "
                         "SIGKILL'd victim subprocess, --recover "
                         "subprocess; exit non-zero unless recovery "
                         "resumed mid-stream with bit-identical outputs "
                         "and exactly-once delivery across lifetimes")
    ap.add_argument("--certify-smoke", action="store_true",
                    help="ZP-Cert admission gate: a certify=True farm "
                         "must dead-letter a statically-broken board at "
                         "submit (durable certify_fail record) while "
                         "co-submitted healthy boards finish "
                         "bit-identical to an uncertified oracle")
    ap.add_argument("--certify", action="store_true",
                    help="statically certify every submitted board "
                         "(ZP-Cert boardcheck) before it can reach a "
                         "slot; error findings dead-letter the job")
    ap.add_argument("--chaos", type=int, metavar="SEED", default=None,
                    help="fault-recovery gate: inject a seeded fault "
                         "schedule; exit non-zero unless every fault was "
                         "recovered with oracle-identical outputs and "
                         "the poisoned board quarantined")
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--async", dest="mode", action="store_const",
                   const="async", default="async",
                   help="per-slot dispatcher threads (default)")
    g.add_argument("--lockstep", dest="mode", action="store_const",
                   const="lockstep",
                   help="single-thread round-robin host loop (the "
                        "bit-identity oracle)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.certify_smoke:
        out = run_certify_smoke(mode=args.mode, slots=args.slots)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.killrestart_smoke:
        out = run_killrestart_smoke(mode=args.mode)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.ledger:
        out = run_ledger_farm(args.ledger, mode=args.mode,
                              recover=args.recover,
                              kill_after=args.kill_after_commits,
                              n_boards=args.ledger_boards,
                              n_windows=args.ledger_windows,
                              slots=args.slots)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.scope_smoke:
        out = run_scope_smoke(mode=args.mode, lanes=args.lanes or 1,
                              every_n=args.scope or 2, slots=args.slots)
        if args.telemetry_out:
            write_telemetry(args.telemetry_out,
                            {"telemetry": {"scope": out["scope"]}},
                            f"scope-smoke-{args.mode}-l{args.lanes or 1}")
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.restart_smoke:
        out = run_restart_smoke(mode=args.mode, slots=args.slots)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.lanes is not None:
        out = run_lanes_smoke(lanes=args.lanes,
                              chaos_lane=args.chaos_lane,
                              mode=args.mode)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    if args.chaos is not None:
        out = run_chaos_smoke(args.chaos, mode=args.mode,
                              slots=args.slots)
        print(json.dumps(out, indent=1, default=float))
        if not out["ok"]:
            sys.exit(1)
        return

    scope = (ScopeSpec(every_n_windows=args.scope)
             if args.scope is not None else None)
    try:
        out = run_farm(get_smoke_config(args.arch), args.slots,
                       steps=args.steps, gen=args.steps, batch=2, seq=16,
                       prompt_len=16, verify_seq=16,
                       interval=args.sample_interval,
                       synthetic_straggler=args.synthetic_straggler,
                       straggler_factor=args.straggler_factor,
                       roofline=args.roofline, mode=args.mode,
                       handle_sigint=True, scope=scope,
                       certify=args.certify)
    except KeyboardInterrupt:
        # ^C before the farm was running (job setup / compile) or a
        # second ^C during the graceful drain: nothing to keep, exit the
        # conventional SIGINT code without a traceback
        print("farm: interrupted before completion", file=sys.stderr)
        sys.exit(130)
    if args.telemetry_out:
        write_telemetry(args.telemetry_out, out,
                        f"farm-{args.mode}-{args.arch}-s{args.steps}")
    if out.get("interrupted"):
        print(json.dumps(out, indent=1, default=float))
        print(out["summary"], file=sys.stderr)
        sys.exit(out.get("exit_code", 130))
    print(json.dumps(out, indent=1, default=float))
    if not out["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
