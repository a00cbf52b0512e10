"""Step-locked co-emulation against a golden model (DESIGN C3).

The DUT is the optimized, jit-compiled step; the oracle is a slower
reference implementation (pure-jnp paths / f32 / interpret-mode kernels).
Both run step-locked on identical inputs; their commit streams (per-layer
checksums through the P-Shell) are cross-verified each step — the Dromajo
pattern. The report localizes the FIRST divergent (step, layer), which is
what makes injected faults debuggable (the mutation tests assert the fault
layer is identified exactly).

Group-locked mode (``group_size > 1``): DUT and oracle each dispatch ONCE
per clock-gated window — a lax.scan over the window's batch stack whose ys
carry every step's checksums — so host crossings amortize over the window
while localization stays exact: the per-step commit streams are recovered
from the scanned aux and compared step by step, bit-for-bit equivalent to
step-locked verification.

Both modes now run through the core ``WindowScheduler``: DUT and oracle
windows are dispatched back-to-back (async) before EITHER side's checksums
are fetched, and with ``overlap=True`` (default) window *i*'s blocking
fetch + comparison runs while window *i+1*'s compute is already in flight —
the oracle no longer serializes behind the DUT drain, and grouped verify
stops paying two serial syncs per window (``overlap=False`` reproduces the
serial baseline for benchmarking).

``verify_subsystems`` is the multi-DUT (ZP-Farm) mode: several
``decompose.extract_block`` subsystems verify as independent boards. It
routes through the ``repro.farm`` ``FarmManager`` — one farm job per
subsystem, placed one-per-device (round-robin on a single device), with
per-device watchdogs and straggler eviction riding along for free.

``CommitStreamVerifier`` closes the verified-snapshot loop: attached to
the train loop's checkpoint ``DrainBarrier`` path, it replays the same
deterministic batch stream through the oracle and compares the drained
commit FIFO rows window by window — a diverging commit stream raises at
the drain, which vetoes the checkpoint before it can publish.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.commit import layer_checksums
from repro.core.schedule import WindowScheduler, iter_windows
from repro.utils import checksum


@dataclasses.dataclass
class Divergence:
    step: int
    layer: int
    rel_err: float
    lane: Optional[int] = None      # lane-batched runs: which board


@dataclasses.dataclass
class CoEmuReport:
    steps: int
    diverged: bool
    first: Optional[Divergence]
    max_rel_err: float
    loss_max_abs_diff: float

    def summary(self) -> str:
        if not self.diverged:
            return (f"PASS: {self.steps} steps verified, "
                    f"max commit rel-err {self.max_rel_err:.2e}")
        return (f"FAIL: first divergence at step {self.first.step} "
                f"layer {self.first.layer} (rel-err {self.first.rel_err:.2e})")


def _rel_err(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.abs(a - b) / (np.abs(b) + 1e-6)


def _stack_on_device(items):
    """Device-side window stacking (the DUT/oracle dispatch consumes jnp
    stacks; no host round-trip for already-resident batches)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *items)


class _CompareAccumulator:
    """Folds one window's (dut, oracle) checksum/loss ys at a time into the
    running CoEmuReport fields. The np.asarray calls here are the blocking
    device->host fetches — the scheduler runs them overlapped with the next
    window's in-flight compute."""

    def __init__(self, rtol: float):
        self.rtol = rtol
        self.first: Optional[Divergence] = None
        self.max_err = 0.0
        self.loss_diff = 0.0
        self.steps = 0

    def ingest(self, step0: int, ys):
        # ONE device fetch for the window's whole (dut, oracle) ys tuple —
        # four separate np.asarray calls would each sync the stream
        (cks_d, loss_d), (cks_o, loss_o) = jax.device_get(ys)
        cks_d = np.asarray(cks_d, np.float64)             # (g, L, 2)
        cks_o = np.asarray(cks_o, np.float64)
        self._compare(cks_d, cks_o, step0)
        self.loss_diff = max(self.loss_diff, float(np.max(np.abs(
            np.asarray(loss_d, np.float64)
            - np.asarray(loss_o, np.float64)))))
        self.steps += cks_d.shape[0]

    def _compare(self, cks_d, cks_o, step0):
        """Per-step (g, L, 2) checksum comparison; records the first
        divergent (step, layer) in window order."""
        err = _rel_err(cks_d, cks_o).max(axis=2)          # (g, L)
        self.max_err = max(self.max_err, float(err.max()))
        if self.first is None:
            bad_steps, bad_layers = np.nonzero(err > self.rtol)
            if bad_steps.size:
                s, l = int(bad_steps[0]), int(bad_layers[0])
                self.first = Divergence(step=step0 + s, layer=l,
                                        rel_err=float(err[s, l]))

    def report(self) -> CoEmuReport:
        return CoEmuReport(steps=self.steps,
                           diverged=self.first is not None,
                           first=self.first, max_rel_err=self.max_err,
                           loss_max_abs_diff=self.loss_diff)


class CoEmulator:
    """verify(): DUT-vs-oracle commit comparison. determinism(): DUT-vs-DUT
    bitwise reproducibility (run-to-run, the emulation-debug contract)."""

    def __init__(self, dut_step: Callable, oracle_step: Callable,
                 rtol: float = 5e-2):
        self.dut_step = dut_step
        self.oracle_step = oracle_step
        self.rtol = rtol
        # keyed on the step function OBJECT (kept alive by the key), never
        # id(): id keys are only sound while every cached fn happens to
        # stay alive; object keys make no-aliasing unconditional
        self._group_fns: Dict[Any, Callable] = {}

    def verify(self, state_dut, state_orc, batches, group_size: int = 1,
               overlap: bool = True) -> CoEmuReport:
        """Cross-verify commit streams. ``group_size=1`` is the step-locked
        Dromajo loop; ``group_size=N`` dispatches each side once per
        N-step window (scan-fused) and recovers per-step checksums from the
        scanned ys — same localization, 2 dispatches per window instead of
        2N. ``overlap=False`` forces the serial baseline: each window's
        checksums are fetched before the next window dispatches, and in
        grouped mode the DUT window is additionally synced to completion
        before the oracle window dispatches (the 2-serial-syncs Dromajo
        loop). Step-locked mode always dispatches DUT and oracle
        back-to-back within a step."""
        grouped = group_size > 1
        engine = (self._grouped_engine(serial=not overlap) if grouped
                  else self._step_engine())
        sched = WindowScheduler(
            interval=max(1, group_size), overlap=overlap, drain_fn=None,
            stack_fn=_stack_on_device if grouped else None)
        acc = _CompareAccumulator(self.rtol)
        sched.run(engine, sched.windows(batches),
                  (state_dut, state_orc), {},
                  on_drain=lambda plan, records, ys: acc.ingest(plan.start,
                                                                ys))
        return acc.report()

    # ------------------------------------------------------------ engines --
    def _step_engine(self):
        """Step-locked two-sided engine: per-step dispatches exactly as the
        legacy Dromajo loop, but checksum materialization is deferred to
        the scheduler's drain (ys stay on device)."""
        def engine(states, shell, batches):
            state_dut, state_orc = states
            cks_d, cks_o, loss_d, loss_o = [], [], [], []
            for batch in batches:
                state_dut, m_dut, aux_dut = self.dut_step(state_dut, batch)
                state_orc, m_orc, aux_orc = self.oracle_step(state_orc, batch)
                cks_d.append(layer_checksums(aux_dut))
                cks_o.append(layer_checksums(aux_orc))
                loss_d.append(m_dut["loss"])
                loss_o.append(m_orc["loss"])
            ys = ((jnp.stack(cks_d), jnp.stack(loss_d)),
                  (jnp.stack(cks_o), jnp.stack(loss_o)))
            return (state_dut, state_orc), shell, ys

        return engine

    def _grouped_engine(self, serial: bool = False):
        """Group-locked two-sided engine: DUT and oracle windows dispatch
        back-to-back (async); nothing is fetched here. ``serial=True`` is
        the benchmark's no-dispatch-overlap baseline: the DUT window is
        synced to completion before the oracle window dispatches."""
        dut_group = self._cached_group(self.dut_step)
        orc_group = self._cached_group(self.oracle_step)

        def engine(states, shell, stack):
            state_dut, state_orc = states
            state_dut, ys_d = dut_group(state_dut, stack)
            if serial:
                jax.block_until_ready(ys_d)
            state_orc, ys_o = orc_group(state_orc, stack)
            return (state_dut, state_orc), shell, (ys_d, ys_o)

        return engine

    def _group_fn(self, step: Callable):
        """One fused dispatch per window: scan ``step`` over the batch
        stack, ys = (per-step checksums, per-step loss). The scan is
        unrolled (capped at 8 steps per rolled iteration) — a rolled
        XLA while-loop around a remat'd train step costs ~2x the
        unrolled body on CPU, which is exactly what made grouped verify
        lose to step-locked before; unrolling is semantics-preserving,
        so per-step checksums stay bit-identical."""
        def body(state, batch):
            state, metrics, aux = step(state, batch)
            return state, (layer_checksums(aux).astype(jnp.float32),
                           metrics["loss"].astype(jnp.float32))

        def group(state, stack):
            g = jax.tree.leaves(stack)[0].shape[0]
            return jax.lax.scan(body, state, stack, unroll=min(g, 8))

        return jax.jit(group)

    def _cached_group(self, step: Callable):
        if step not in self._group_fns:
            self._group_fns[step] = self._group_fn(step)
        return self._group_fns[step]

    @staticmethod
    def determinism(step: Callable, state, batch) -> bool:
        """Two identical dispatches must be BITWISE identical (functional
        purity is the TPU analogue of deterministic clock-gated emulation)."""
        out1 = step(state, batch)
        out2 = step(state, batch)
        leaves1 = jax.tree.leaves(out1)
        leaves2 = jax.tree.leaves(out2)
        return all(np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True)
                   for a, b in zip(leaves1, leaves2))


# --------------------------------------------------- checkpoint verifier ---
class CommitDivergence(RuntimeError):
    """Raised by CommitStreamVerifier at the drain whose commit rows
    diverge from the oracle — inside the scheduler's ``on_drain``, this
    vetoes any DrainBarrier commit (checkpoint save) behind the window."""

    def __init__(self, step: int, layer: int, rel_err: float,
                 lane: Optional[int] = None):
        at_lane = "" if lane is None else f" lane {lane}"
        super().__init__(
            f"commit stream diverged at step {step} layer {layer}"
            f"{at_lane} (rel-err {rel_err:.2e}); checkpoint vetoed")
        self.step = step
        self.layer = layer
        self.rel_err = rel_err
        self.lane = lane


class CommitStreamVerifier:
    """The paper's verified-snapshot workflow, wired into the train loop:
    a checkpoint may only publish if the host has ACCEPTED every commit up
    to the boundary.

    Called as the train loop's drain verifier with ``(last_step,
    records)``: replays its OWN copy of the deterministic batch stream
    through ``oracle_step`` (eager, step-locked) and compares the drained
    commit FIFO rows — per-step ``[layer, abs_mean, rms]`` checksums
    pushed by the P-Shell ingest — against the oracle's
    ``layer_checksums``. A divergence raises :class:`CommitDivergence`,
    which the ``WindowScheduler`` barrier semantics turn into a checkpoint
    veto (the barrier action never runs). Requires a losslessly sized
    commit FIFO (the ``default_shell_config`` contract); rows beyond what
    the FIFO kept are not checkable and are skipped.

    Digest first pass (ZP-Scope): ``expected_digests`` maps a window index
    to the oracle's commit digest for that window's outputs
    (:func:`repro.core.scope.digest_tree` over the oracle ys — the exact
    host twin of the on-device fold). When the caller passes the drained
    window's on-device ``digest`` and it MATCHES, the per-step/per-layer
    host row comparison is skipped — the oracle still replays to advance
    its state, but verification cost collapses to one uint32 compare,
    scaling total verify cost with the scope's read rate (the paper's
    arbitrary-granularity knob). A mismatch falls through to the full
    compare, which localizes the divergence (step/layer) and raises.
    ``digest_hits`` counts fast-path windows; ``max_rel_err`` is the
    largest relative error of any compared row so far.

    Mid-stream resume (the farm's checkpointed-requeue protocol):
    :meth:`snapshot` captures the oracle's position — host-copied state,
    global step, and the number of batches consumed — and
    :meth:`restore` rewinds to it, so a job evicted after N accepted
    windows re-verifies from the barrier's oracle state instead of
    replaying the oracle from step 0. Rewinding re-reads the batch
    stream, so resume requires ``batches`` to be a sequence or a zero-arg
    factory (a one-shot iterator can be consumed but never rewound).
    """

    def __init__(self, oracle_step: Callable, state, batches,
                 layers: int, rtol: float = 1e-5, start_step: int = 0,
                 lane: Optional[int] = None,
                 expected_digests: Optional[dict] = None):
        self.oracle_step = oracle_step
        self.state = state
        self._batches_src = batches
        self.batches = self._iter_batches()
        self.L = layers
        self.rtol = rtol
        self.step = start_step      # resume: report true global step ids
        self._consumed = 0          # batches taken from the stream so far
        self.lane = lane            # lane-batched boards: divergences name
        # the lane, so a fused farm run localizes the veto to ONE board
        self.expected_digests = expected_digests or {}
        self.digest_hits = 0        # windows verified by digest alone
        self.max_rel_err = 0.0

    def _iter_batches(self):
        b = self._batches_src
        return iter(b() if callable(b) else b)

    def _next_batch(self):
        batch = next(self.batches)
        self._consumed += 1
        return batch

    def __call__(self, last_step: int, records, digest: Optional[int] = None,
                 window: Optional[int] = None):
        rows = np.asarray(records["fifos"]["commits"]["data"], np.float64)
        steps = rows.shape[0] // self.L
        # Digest first pass: the on-device fold matched the precomputed
        # oracle digest for this window — skip the host row compare, but
        # still replay the oracle to keep its state step-locked.
        skip_rows = (digest is not None and window is not None
                     and window in self.expected_digests
                     and int(digest) == int(self.expected_digests[window]))
        for s in range(steps):
            batch = self._next_batch()
            self.state, _, aux = self.oracle_step(self.state, batch)
            if skip_rows:
                continue
            exp = np.asarray(layer_checksums(aux), np.float64)   # (L, 2)
            got = rows[s * self.L:(s + 1) * self.L, 1:]
            err = _rel_err(got, exp).max(axis=1)                 # (L,)
            self.max_rel_err = max(self.max_rel_err, float(err.max()))
            bad = np.nonzero(err > self.rtol)[0]
            if bad.size:
                l = int(bad[0])
                raise CommitDivergence(step=self.step + s, layer=l,
                                       rel_err=float(err[l]),
                                       lane=self.lane)
        if skip_rows:
            self.digest_hits += 1
        self.step += steps

    # ------------------------------------------------------------- resume --
    def snapshot(self):
        """Host-copied resume point (oracle state + stream position); the
        farm publishes this with the job snapshot at every accepted
        barrier commit."""
        return {"state": jax.tree.map(np.asarray, self.state),
                "step": np.int64(self.step),
                "consumed": np.int64(self._consumed)}

    def restore(self, snap):
        """Rewind to a :meth:`snapshot`: subsequent drains re-verify from
        that barrier's oracle state against a re-seeked batch stream."""
        src = self._batches_src
        if not callable(src) and iter(src) is src:
            raise ValueError(
                "CommitStreamVerifier resume needs a re-iterable batch "
                "source (sequence or zero-arg factory); a one-shot "
                "iterator cannot be rewound to the snapshot position")
        self.state = snap["state"]
        self.step = int(snap["step"])
        self._consumed = int(snap["consumed"])
        self.batches = itertools.islice(self._iter_batches(),
                                        self._consumed, None)


# ------------------------------------------------------------- multi-DUT ---
def subsystem_boards(params, cfg, rt, xs: Sequence, positions,
                     layer_idxs: Sequence[int], dut_params=None):
    """Build the multi-DUT farm boards: for each activation batch in ``xs``
    (the "steps"), an in-situ unrolled run over ``params`` captures every
    block's boundary traffic (the oracle); each layer in ``layer_idxs``
    becomes one DUT board — its extracted subsystem (from ``dut_params``,
    defaulting to the oracle's params) replayed standalone over its
    captured inputs, scan-fused per window.

    Returns one ``(engine, state, x_ins, oracle_cks, lane_key)`` tuple per
    layer. Boards sharing a block spec share ONE jitted engine whose
    block params ride as the board's STATE (not a per-engine closure):
    same-spec boards are lane-batchable under ``lane_key``, the farm's
    identity-aware lane packing broadcasts any params shared across
    boards instead of replicating them per board, and extraction is a
    single :func:`~repro.core.decompose.extract_blocks` walk instead of
    one full-stack re-walk per board."""
    from repro.core.decompose import extract_blocks, unrolled_capture
    from repro.models import transformer as tfm

    captures = [unrolled_capture(params, cfg, x, positions, rt)[1]
                for x in xs]                       # [step][layer] records
    batch, seq = xs[0].shape[0], xs[0].shape[1]
    subs = extract_blocks(dut_params if dut_params is not None else params,
                          cfg, layer_idxs, rt, batch, seq)

    engines = {}                    # spec -> ONE engine for all its boards

    def shared_engine(spec):
        if spec not in engines:
            def window_fn(tree, stack):
                def step(x):
                    y, _ = tfm.block_apply(tree, cfg, spec, x,
                                           positions, rt)
                    return checksum(y)
                return jax.lax.map(step, stack)
            jitted = jax.jit(window_fn)

            def engine(state, shell, stack):
                return state, shell, jitted(state, stack)

            engines[spec] = engine
        return engines[spec]

    boards = []
    for li in layer_idxs:
        sub = subs[li]
        x_ins = [captures[s][li]["x_in"] for s in range(len(xs))]
        oracle_cks = np.stack([
            np.asarray(checksum(captures[s][li]["x_out"]),
                       np.float64)
            for s in range(len(xs))])              # (steps, 2)
        boards.append((shared_engine(sub.spec), sub.params, x_ins,
                       oracle_cks, f"subsys:{sub.spec[0]}+{sub.spec[1]}"))
    return boards


def submit_subsystem_jobs(farm, params, cfg, rt, xs: Sequence, positions,
                          layer_idxs: Sequence[int], group_size: int = 2,
                          rtol: float = 5e-2, dut_params=None,
                          lanes: bool = False):
    """Submit one verification FarmJob per extracted subsystem to ``farm``
    (a ``repro.farm.FarmManager``) and return a zero-arg ``finalize``
    producing the per-subsystem ``CoEmuReport``\\ s once the farm ran.

    Checksum ingestion rides the job's exactly-once ``on_drain`` sink, so
    an evicted + requeued board's replayed windows are never
    double-counted. A divergence localizes a fault to the exact (step,
    subsystem) — it is RECORDED in the report, not raised, so a diverging
    board never takes down the farm pass.

    ``lanes=True`` tags each job with its block-spec ``lane_key`` so a
    lane-capable farm coalesces same-spec subsystem boards into one
    vmap-ed dispatch stream (they already share one engine, and the lane
    packer broadcasts any param leaves shared across boards)."""
    from repro.farm.manager import FarmJob

    boards = subsystem_boards(params, cfg, rt, xs, positions, layer_idxs,
                              dut_params=dut_params)
    accs = []
    for li, (engine, state, x_ins, oracle_cks, lane_key) in zip(layer_idxs,
                                                                boards):
        acc = _CompareAccumulator(rtol)
        accs.append(acc)

        def sink(plan, records, ys, acc=acc, oracle_cks=oracle_cks):
            cks_d = np.asarray(ys, np.float64)[:, None, :]    # (g, 1, 2)
            cks_o = oracle_cks[plan.start:plan.start
                               + plan.size][:, None, :]
            acc._compare(cks_d, cks_o, plan.start)
            acc.steps += cks_d.shape[0]

        farm.submit(FarmJob(
            name=f"layer{li}", engine=engine, state=state,
            windows=list(iter_windows(x_ins, group_size)), shell={},
            stack_fn=_stack_on_device, on_drain=sink,
            lane_key=lane_key if lanes else None))

    def finalize() -> Dict[str, CoEmuReport]:
        out = {}
        for k, li in enumerate(layer_idxs):
            rep = accs[k].report()
            if rep.first is not None:
                # the board sees a single "layer" (itself); report true id
                rep.first = Divergence(step=rep.first.step, layer=li,
                                       rel_err=rep.first.rel_err)
            out[f"layer{li}"] = rep
        return out

    return finalize


def verify_subsystems(params, cfg, rt, xs: Sequence, positions,
                      layer_idxs: Sequence[int], group_size: int = 2,
                      rtol: float = 5e-2, dut_params=None,
                      farm=None, lanes: bool = False) -> Dict[str, CoEmuReport]:
    """Multi-DUT (ZP-Farm) mode: verify several extracted subsystems as
    independent boards of one farm pass (see ``submit_subsystem_jobs``).
    ``farm=None`` builds a dedicated ``FarmManager`` with one slot per
    subsystem — every board dispatches before any board's previous window
    is fetched, exactly the paper's board-farm shape.

    Note on tolerance: the scan-compiled replay may differ from the eager
    in-situ capture in low mantissa bits (XLA fusion/reassociation,
    especially bf16), so comparison is at ``rtol`` — the BITWISE
    non-interference contract is the eager ``decompose.verify_extraction``
    path."""
    from repro.farm.manager import FarmManager

    # the internal farm disables wall-clock straggler eviction: a library
    # verification call must be timing-independent (heterogeneous blocks
    # legitimately differ in window cost); callers who want eviction pass
    # their own farm
    mgr = farm if farm is not None else FarmManager(
        slots=len(layer_idxs), evict_stragglers=False,
        lanes=len(layer_idxs) if lanes else 1)
    finalize = submit_subsystem_jobs(
        mgr, params, cfg, rt, xs, positions, layer_idxs,
        group_size=group_size, rtol=rtol, dut_params=dut_params,
        lanes=lanes)
    mgr.run()
    return finalize()


def inject_fault(params, cfg, layer: int, scale: float = 100.0):
    """Perturb one weight tensor of block ``layer`` (mutation testing: the
    co-emulator must localize the divergence to this layer)."""
    P_len = len(cfg.layer_pattern)
    period, pos = divmod(layer, P_len)

    def bump(stack):
        blocks = list(stack["blocks"])
        blk = blocks[pos]

        # perturb the first (n_periods, ...) weight leaf of this position
        leaves, treedef = jax.tree.flatten(blk)
        for i, leaf in enumerate(leaves):
            if leaf.ndim >= 3:
                leaves[i] = leaf.at[period].mul(scale)
                break
        else:
            raise ValueError(
                f"inject_fault: block position {pos} (layer {layer}) has no "
                f"stacked weight leaf with ndim >= 3 to perturb; leaf shapes"
                f" = {[tuple(l.shape) for l in leaves]}")
        blocks[pos] = treedef.unflatten(leaves)
        return {**stack, "blocks": tuple(blocks)}

    return {**params, "stack": bump(params["stack"])}
