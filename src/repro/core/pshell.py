"""P-Shell: the ZynqParrot host<->DUT interface, adapted to JAX (DESIGN C2).

The shell carries two kinds of state through the jit-compiled step function:

  CSRs      — named control/status registers. Host writes land at step
              boundaries (clock edges); reads never block the DUT.
  SB-FIFOs  — bounded ring buffers with the semi-blocking contract: the
              device side NEVER blocks (a push into a full FIFO increments a
              ``dropped`` credit counter instead — credit/valid semantics),
              and the host drains between step groups.

Clock-gating analogue: the device runs ``sample_interval`` steps between
host drains. interval=1 == cycle-accurate co-emulation (nothing can drop if
FIFO depth >= events/step); larger intervals trade completeness for speed —
exactly the paper's gating-granularity knob (Fig. 11).

Fused step groups (the FireSim lesson: keep the FPGA busy while the host
lags): ``PShell.run_grouped`` compiles the whole clock-gated window into ONE
jit dispatch — a ``lax.scan`` over a stacked batch group whose body is
step + ingest — instead of ``sample_interval`` separate dispatches with a
Python re-thread between each. Per-step metrics accumulate on device and are
materialized once per group; the host drain of group *i* is overlapped with
the (async-dispatched) device compute of group *i+1* by double-buffering the
shell: the group's output shell is kept aside as the drain snapshot while
``group_reset`` derives a fresh (count=0, new buffer) shell that the next
group consumes. Model/optimizer state is donated into the group dispatch so
large buffers are reused in place.

Non-interference invariants (tests assert all of these):
  1. Shell state is threaded functionally BESIDE the model state and never
     feeds back into it: model state is bit-identical with the shell
     enabled, disabled, and at any interval.
  2. Grouped execution is bit-identical to per-step execution: for any
     interval, final model/opt state AND the drained commit records (FIFO
     payload order, counts, cumulative dropped credits, CSR values) match
     the per-step loop exactly.
  3. Drain resets FIFO occupancy but never the cumulative ``dropped``
     credit counter — overflow accounting is exact across group boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FifoSpec:
    depth: int
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32


@dataclasses.dataclass(frozen=True)
class ShellConfig:
    csrs: Dict[str, jax.ShapeDtypeStruct] = dataclasses.field(
        default_factory=dict)
    fifos: Dict[str, FifoSpec] = dataclasses.field(default_factory=dict)
    sample_interval: int = 1


def shell_init(cfg: ShellConfig):
    state = {"csr": {}, "fifo": {}}
    for name, spec in cfg.csrs.items():
        state["csr"][name] = jnp.zeros(spec.shape, spec.dtype)
    for name, f in cfg.fifos.items():
        state["fifo"][name] = {
            "buf": jnp.zeros((f.depth,) + tuple(f.shape), f.dtype),
            "count": jnp.zeros((), jnp.int32),
            "dropped": jnp.zeros((), jnp.int32),
        }
    return state


# ------------------------------------------------------------ device side ---
def csr_write(state, name: str, value):
    csr = dict(state["csr"])
    csr[name] = jnp.asarray(value, state["csr"][name].dtype) \
        .reshape(state["csr"][name].shape)
    return {**state, "csr": csr}


def csr_accum(state, name: str, value, op: str = "or"):
    """Accumulating CSR write (toggle bitmaps OR in, counters add)."""
    cur = state["csr"][name]
    v = jnp.asarray(value).astype(cur.dtype).reshape(cur.shape)
    new = jnp.bitwise_or(cur, v) if op == "or" else cur + v
    return csr_write(state, name, new)


def csr_read(state, name: str):
    return state["csr"][name]


def fifo_push(state, name: str, payload):
    """Non-blocking single push (credit/valid: full => dropped += 1)."""
    f = state["fifo"][name]
    depth = f["buf"].shape[0]
    ok = f["count"] < depth
    idx = jnp.minimum(f["count"], depth - 1)
    payload = jnp.asarray(payload, f["buf"].dtype) \
        .reshape(f["buf"].shape[1:])
    cur = jax.lax.dynamic_index_in_dim(f["buf"], idx, 0, keepdims=False)
    buf = jax.lax.dynamic_update_index_in_dim(
        f["buf"], jnp.where(ok, payload, cur), idx, 0)
    new = {"buf": buf,
           "count": f["count"] + ok.astype(jnp.int32),
           "dropped": f["dropped"] + (~ok).astype(jnp.int32)}
    return {**state, "fifo": {**state["fifo"], name: new}}


def fifo_push_many(state, name: str, payloads):
    """Vectorized push of ``payloads`` (n, *shape) — e.g. all per-layer
    commits of one step. Entries beyond the free space are dropped and
    counted (never blocks)."""
    f = state["fifo"][name]
    depth = f["buf"].shape[0]
    n = payloads.shape[0]
    start = f["count"]
    slots = start + jnp.arange(n)
    ok = slots < depth
    # overflow entries scatter into a trash row (index `depth`) so duplicate
    # indices never race with a valid write
    idxs = jnp.where(ok, slots, depth)
    payloads = payloads.astype(f["buf"].dtype)
    padded = jnp.concatenate(
        [f["buf"], jnp.zeros((1,) + f["buf"].shape[1:], f["buf"].dtype)])
    buf = padded.at[idxs].set(payloads)[:depth]
    pushed = jnp.sum(ok.astype(jnp.int32))
    new = {"buf": buf, "count": start + pushed,
           "dropped": f["dropped"] + (n - pushed)}
    return {**state, "fifo": {**state["fifo"], name: new}}


def group_reset(shell):
    """Device-side inter-group reset (jit-safe): FIFO occupancy returns to
    zero with a FRESH buffer (so the previous group's output shell stays
    valid as a host-drain snapshot while the next group overwrites this
    one), the cumulative ``dropped`` credit counter and all CSR accumulators
    carry forward. The host-side ``drain`` of the snapshot is thereby free
    to overlap the next group's device compute."""
    new_fifo = {}
    for name, f in shell["fifo"].items():
        new_fifo[name] = {"buf": jnp.zeros_like(f["buf"]),
                          "count": jnp.zeros((), jnp.int32),
                          "dropped": f["dropped"]}
    return {**shell, "fifo": new_fifo}


_RESET_JIT = None


def _reset_jitted():
    global _RESET_JIT
    if _RESET_JIT is None:
        _RESET_JIT = jax.jit(group_reset)
    return _RESET_JIT


def stack_batches(group):
    """Stack a list of per-step batches into one (g, ...) batch stack for a
    fused group dispatch. Host-side numpy stacking so the device transfer is
    a single contiguous upload per leaf."""
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *group)


# -------------------------------------------------------------- host side ---
def drain(state):
    """Host-side drain: returns (records, reset_state). Must be called on
    concrete (non-traced) state — i.e. between jit step dispatches, which is
    exactly the clock-gated window."""
    records = {}
    new_fifo = {}
    for name, f in state["fifo"].items():
        n = int(f["count"])
        records[name] = {
            "data": np.asarray(f["buf"][:n]),
            "count": n,
            "dropped": int(f["dropped"]),
        }
        new_fifo[name] = {"buf": f["buf"],
                          "count": jnp.zeros((), jnp.int32),
                          "dropped": f["dropped"]}
    csrs = {k: np.asarray(v) for k, v in state["csr"].items()}
    return {"fifos": records, "csrs": csrs}, {**state, "fifo": new_fifo}


# ------------------------------------------------------------------ shell ---
class PShell:
    """Wraps a step function with shell-state threading and runs the
    host-side drain loop at the configured gating granularity."""

    def __init__(self, cfg: ShellConfig,
                 ingest: Callable[[Any, Any, Any], Any]):
        self.cfg = cfg
        self.ingest = ingest
        self._jit_cache: Dict[Any, Callable] = {}

    def init(self):
        return shell_init(self.cfg)

    def wrap(self, step_fn):
        """step_fn(state, batch) -> (state, metrics, aux)  ==>
        wrapped(state, batch, shell) -> (state, metrics, shell)."""
        ingest = self.ingest

        def wrapped(state, batch, shell):
            state, metrics, aux = step_fn(state, batch)
            shell = ingest(shell, aux, metrics)
            return state, metrics, shell

        return wrapped

    def scheduler(self, overlap: bool = True, timer=None,
                  stacked: bool = True):
        """The core WindowScheduler configured for this shell: P-Shell
        drain, device-side ``group_reset`` double-buffering when
        overlapping, windows of ``sample_interval`` steps.
        ``stacked=False`` hands engines the raw per-step batch list
        (per-step engines — no window-stacking copy)."""
        from repro.core.schedule import WindowScheduler
        return WindowScheduler(
            interval=max(1, self.cfg.sample_interval), overlap=overlap,
            reset=_reset_jitted() if overlap else None, drain_fn=drain,
            stack_fn=stack_batches if stacked else None, timer=timer)

    def run(self, wrapped_step, state, batches, shell=None,
            on_drain: Optional[Callable[[int, dict], None]] = None):
        """Per-step host (VPS) baseline: one dispatch per step, serial
        drain every ``sample_interval`` steps (tail window included), all
        through the core WindowScheduler. ``batches`` is an iterable;
        returns (state, last_metrics, shell)."""
        shell = self.init() if shell is None else shell
        sched = self.scheduler(overlap=False, stacked=False)

        def engine(state, sh, batches):
            metrics = None
            for batch in batches:
                state, metrics, sh = wrapped_step(state, batch, sh)
            return state, sh, metrics

        def emit(plan, records, ys):
            if on_drain is not None:
                on_drain(plan.last, records)

        return sched.run(engine, sched.windows(batches), state, shell,
                         on_drain=emit)

    def compile_group(self, group_step, donate: bool = True):
        """Jit a group_step for fused dispatch, caching per (fn, donation).
        Returns the jitted group fn (the scheduler owns the device-side
        ``group_reset`` double-buffering). By default it donates the
        model/opt state (argnum 0) — on every backend, the CPU included,
        so CPU runs exercise the same buffer lifetimes as the chip.
        Callers that redispatch from the SAME state object (benchmark
        timing loops) must pass donate=False so the input buffers survive.

        The cache is keyed on the function OBJECT (kept alive by the key),
        never on ``id()``: id keys are only sound while every cached fn
        happens to stay alive, and a recycled id would silently hand a
        different step fn a stale compiled group."""
        key = (group_step, donate)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                group_step, donate_argnums=(0,) if donate else ())
        return self._jit_cache[key]

    def run_grouped(self, group_step, state, batches, shell=None,
                    on_drain: Optional[Callable[[int, dict], None]] = None,
                    donate: bool = True):
        """Fused host loop: ONE jit dispatch per clock-gated window,
        scheduled by the core WindowScheduler in overlap mode.

        ``group_step(state, shell, batch_stack) -> (state, shell,
        metrics_stack)`` runs ``sample_interval`` steps as a lax.scan (see
        train.step.make_group_step). Per window the scheduler:

          1. stacks the window's batches and dispatches the fused group
             (async) — model/opt state donated so buffers reuse in place;
          2. derives the next group's shell via ``group_reset`` (device
             side, async) — the double buffer;
          3. only THEN drains the PREVIOUS window's snapshot on the host —
             the blocking device->host fetch overlaps the current window's
             in-flight compute.

        Returns (state, last_metrics_stack, shell). ``on_drain(i, records)``
        fires with i = the last step index of the drained window, matching
        ``run``'s cadence; records additionally carry the window's stacked
        per-step metrics under "metrics".
        """
        shell = self.init() if shell is None else shell
        jitted = self.compile_group(group_step, donate=donate)
        sched = self.scheduler(overlap=True)

        def emit(plan, records, metrics):
            if on_drain is not None:
                records["metrics"] = {k: np.asarray(v)
                                      for k, v in metrics.items()}
                on_drain(plan.last, records)

        return sched.run(jitted, sched.windows(batches), state, shell,
                         on_drain=emit)
