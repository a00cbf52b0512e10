"""The integrated training driver: the ZP-Farm host loop (DESIGN C8).

Wires together every substrate: data pipeline (prefetch), P-Shell
instrumentation (drain at the gating granularity -> coverage + commit
verification hooks), profiler phases (device/host/data attribution),
watchdog heartbeats, async checkpointing, and restart-from-latest.

Both execution engines run through the core ``WindowScheduler``
(``repro.core.schedule``) — engine selection is the ONLY difference, the
window/drain/barrier machinery is shared and bit-identical by construction
(tests assert it):

  fused (default) — the whole clock-gated window (``sample_interval``
      steps) is ONE jit dispatch (lax.scan over a stacked batch group, see
      train.step.make_group_step). Losses/metrics accumulate on device and
      cross to the host once per group; the scheduler overlaps the drain of
      window *i* with the in-flight compute of window *i+1* (double-buffered
      shell, ``overlap=True``).

  per-step — one dispatch per batch inside the window (``overlap=False``),
      kept as the equivalence baseline. Even here nothing blocks inside the
      "device" phase: loss arrays are held on device and materialized only
      at drain boundaries, so the profiler's device phase measures
      dispatch/compute, not a forced host<->device sync per step.

Profiler, watchdog, coverage, and checkpointing hook in via scheduler
callbacks: the profiler IS the scheduler's phase timer, the watchdog
heartbeats from ``on_dispatch``, coverage folds drained CSRs in
``on_drain``, and checkpoints are ``DrainBarrier`` actions — a checkpoint
at a boundary may only hit disk after every window up to it was drained
and ACCEPTED by the host (an on_drain verifier that raises vetoes it).
Both engines share the barrier semantics: saves commit at the first window
boundary at/after each ``checkpoint_every`` mark.

Profiler attribution under async dispatch: "device" is dispatch time (the
enqueue), and the wait for a window's results lands in the "host" phase at
its drain — by design, since that wait runs concurrently with the NEXT
window's in-flight compute. A host-dominated live stack therefore means
"host is waiting on the device", not "host work dominates".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (PShell, default_shell_config, make_ingest,
                        CoverageMap, Profiler, Watchdog, DrainBarrier,
                        plan_windows)
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticPipeline
from repro.roofline.capture import WindowCapture
from repro.train.optim import OptConfig
from repro.train.step import make_train_step, make_group_step, init_state


@dataclasses.dataclass
class LoopConfig:
    steps: int = 20
    batch: int = 4
    seq: int = 32
    seed: int = 0
    sample_interval: int = 1
    checkpoint_every: int = 10
    checkpoint_dir: Optional[str] = None
    watchdog_timeout_s: float = 600.0
    grad_compress: bool = False
    accum_steps: int = 1
    fused: bool = True          # fused step groups vs per-step dispatch
    scope: Any = None           # ScopeSpec: ZP-Scope instrumentation
    # plane (on-device counters drained at the read rate; bit-identical
    # DUT stream with the plane on or off)


def train_loop(model, loop_cfg: LoopConfig,
               opt_cfg: OptConfig = OptConfig(),
               on_drain: Optional[Callable[[int, dict], None]] = None,
               resume: bool = True,
               oracle_step: Optional[Callable] = None,
               oracle_state: Any = None,
               oracle_rtol: float = 1e-3) -> Dict[str, Any]:
    """``oracle_step`` arms the verified-snapshot workflow: a
    ``CommitStreamVerifier`` replays the same deterministic batch stream
    through the oracle and checks the drained commit FIFO rows at every
    window — a diverging commit stream raises at the drain, vetoing the
    checkpoint ``DrainBarrier`` before the save can publish.
    ``oracle_state`` defaults to the DUT's own starting state — the fresh
    seed init, or the restored checkpoint on resume — so the oracle
    replays from the same weights the engine continues from; pass a
    different state to model a faulted engine.

    ``oracle_rtol`` bounds the relative error of each commit checksum. The
    fused engine and a separately compiled oracle step fuse differently,
    so on a TPU their bf16 weight updates round differently: on a v5e at
    internvl2-1b widths (6 layers, batch 1 x 512, 8 steps at interval 2)
    the largest error read 9.2e-5, and the default leaves ten times that.
    On the CPU the two agree exactly. ``out["oracle_max_rel_err"]`` reports
    the largest error of the run."""
    cfg = model.cfg

    state = init_state(model, jax.random.key(loop_cfg.seed), opt_cfg,
                       grad_compress=loop_cfg.grad_compress)
    start_step = 0
    ckpt = None
    if loop_cfg.checkpoint_dir:
        ckpt = CheckpointManager(loop_cfg.checkpoint_dir)
        if resume and ckpt.steps():
            state, start_step = ckpt.restore(state)

    shell_cfg = default_shell_config(
        cfg, sample_interval=loop_cfg.sample_interval)
    ingest = make_ingest(cfg)
    shell = PShell(shell_cfg, ingest)
    sh = shell.init()

    prof = Profiler(sample_interval=loop_cfg.sample_interval)
    wd = Watchdog(timeout_s=loop_cfg.watchdog_timeout_s)
    cov = CoverageMap()
    # measured-window roofline capture rides every run by default; the
    # fused engine routes dispatch through capture.attach_engine, so HLO
    # cost comes off the run's own first compile — flops/bytes with no
    # second lowering
    capture = WindowCapture()
    scope_plane = None
    if loop_cfg.scope is not None:
        from repro.core.scope import as_plane
        scope_plane = as_plane(loop_cfg.scope)
        capture.attach_scope(scope_plane)
    pipe = SyntheticPipeline(cfg, loop_cfg.batch, loop_cfg.seq,
                             seed=loop_cfg.seed, start_step=start_step)
    losses: list = []

    verifier = None
    orc_pipe = None
    if oracle_step is not None:
        from repro.core.coemu import CommitStreamVerifier
        if oracle_state is None:
            # the DUT's own starting state — fresh init, or the restored
            # checkpoint on resume, so the oracle replays from the same
            # weights and step the engine continues from. A copy: the
            # fused engine donates its state on the device, which deletes
            # the buffers the oracle would otherwise read
            oracle_state = jax.tree.map(jnp.copy, state)
        orc_pipe = SyntheticPipeline(cfg, loop_cfg.batch, loop_cfg.seq,
                                     seed=loop_cfg.seed,
                                     start_step=start_step)
        verifier = CommitStreamVerifier(
            oracle_step, oracle_state, orc_pipe,
            layers=cfg.num_layers + cfg.encoder_layers, rtol=oracle_rtol,
            start_step=start_step)

    try:
        runner = _run_fused if loop_cfg.fused else _run_per_step
        state = runner(model, loop_cfg, opt_cfg, state, shell, sh, ingest,
                       pipe, prof, wd, cov, ckpt, losses, start_step,
                       on_drain, verifier, capture, scope_plane)
    finally:
        pipe.close()
        if orc_pipe is not None:
            orc_pipe.close()
        if ckpt:
            ckpt.wait()

    if scope_plane is not None and scope_plane.samples:
        # fold the plane's on-device gate bits into the coverage map —
        # the same OR-accumulated CSR semantics, one more bitmap
        last = scope_plane.samples[-1]
        if last.get("gates") is not None:
            cov.update_gates(last["gates"])
    out = {
        "state": state,
        "losses": losses,
        "coverage": cov.summary(),
        "profile": prof.live_stack().seconds,
        "stragglers": wd.stragglers(),
        "final_step": loop_cfg.steps,
        "roofline": capture.report(),
    }
    if scope_plane is not None:
        out["scope"] = scope_plane.report()
    if verifier is not None:
        out["oracle_max_rel_err"] = verifier.max_rel_err
    return out


def _pipe_windows(pipe, loop_cfg, start_step):
    """Window source: pull each planned window's batches from the pipeline
    (consumed inside the scheduler's "data" phase)."""
    for plan in plan_windows(loop_cfg.steps, loop_cfg.sample_interval,
                             start=start_step):
        yield [next(pipe) for _ in range(plan.size)]


def _barriers(ckpt, loop_cfg):
    if not ckpt:
        return ()
    return (DrainBarrier(every=loop_cfg.checkpoint_every,
                         action=lambda state, step: ckpt.save(state, step)),)


def _step_counter(prof):
    """on_window hook: one profiler step per step of the drained window."""
    def step_done(plan, state):
        for _ in range(plan.size):
            prof.step_done()
    return step_done


def _run_fused(model, loop_cfg, opt_cfg, state, shell, sh, ingest, pipe,
               prof, wd, cov, ckpt, losses, start_step, on_drain,
               verifier=None, capture=None, scope_plane=None):
    """Group-granular engine: one fused dispatch per clock-gated window,
    host drain of window i overlapped with window i+1's device compute."""
    group_fn = shell.compile_group(
        make_group_step(model, opt_cfg, ingest=ingest,
                        grad_compress=loop_cfg.grad_compress,
                        accum_steps=loop_cfg.accum_steps))
    if capture is not None:
        # the run's first compile doubles as the roofline cost source
        group_fn = capture.attach_engine(group_fn)
    sched = shell.scheduler(overlap=True, timer=prof)

    def emit(plan, records, metrics):
        if verifier is not None:        # raising here vetoes the barrier
            verifier(plan.last, records)
        losses.extend(np.asarray(metrics["loss"], np.float32).tolist())
        cov.update(records["csrs"])
        if on_drain:
            on_drain(plan.last, records)

    od, odr = _chain_capture(capture, lambda plan, state: wd.heartbeat(),
                             emit)
    state, _, _ = sched.run(
        group_fn, _pipe_windows(pipe, loop_cfg, start_step), state, sh,
        start_step=start_step, on_drain=odr, on_dispatch=od,
        on_window=_step_counter(prof), barriers=_barriers(ckpt, loop_cfg),
        scope=scope_plane)
    return state


def _chain_capture(capture, on_dispatch, on_drain):
    """Chain the default WindowCapture in front of the loop's own
    callbacks (no-op pass-through when capture is None)."""
    if capture is None:
        return on_dispatch, on_drain
    return capture.callbacks(on_dispatch=on_dispatch, on_drain=on_drain)


def _run_per_step(model, loop_cfg, opt_cfg, state, shell, sh, ingest, pipe,
                  prof, wd, cov, ckpt, losses, start_step, on_drain,
                  verifier=None, capture=None, scope_plane=None):
    """Per-step dispatch baseline (``overlap=False``: serial in-place
    drains at window boundaries). Loss materialization is deferred to drain
    boundaries — no blocking sync inside the device phase."""
    step_fn = jax.jit(make_train_step(
        model, opt_cfg, with_aux=True,
        grad_compress=loop_cfg.grad_compress,
        accum_steps=loop_cfg.accum_steps))

    def wrapped(state, batch, shell_state):
        state, metrics, aux = step_fn(state, batch)
        return state, metrics, ingest(shell_state, aux, metrics)

    wrapped = jax.jit(wrapped)
    sched = shell.scheduler(overlap=False, timer=prof, stacked=False)

    def engine(state, sh, batches):
        window_losses = []          # device arrays, materialized at drain
        for batch in batches:
            state, metrics, sh = wrapped(state, batch, sh)
            window_losses.append(metrics["loss"])
            wd.heartbeat()
        return state, sh, window_losses

    def emit(plan, records, window_losses):
        if verifier is not None:        # raising here vetoes the barrier
            verifier(plan.last, records)
        losses.extend(float(x) for x in window_losses)
        cov.update(records["csrs"])
        if on_drain:
            on_drain(plan.last, records)

    od, odr = _chain_capture(capture, None, emit)
    state, _, _ = sched.run(
        engine, _pipe_windows(pipe, loop_cfg, start_step), state, sh,
        start_step=start_step, on_drain=odr, on_dispatch=od,
        on_window=_step_counter(prof), barriers=_barriers(ckpt, loop_cfg),
        scope=scope_plane)
    return state
