"""The core window scheduler: ONE host loop for every P-Shell client.

The paper's P-Shell is a single host<->DUT interface that serves every use
— functional verification, performance validation, long-workload execution.
Before this module the repo had four divergent copies of the windowing /
double-buffer / drain-overlap machinery (``PShell.run``/``run_grouped``,
the two ``train.loop`` engines, ``CoEmulator.verify``). The scheduler — not
each caller — now owns window pipelining (the FireSim lesson: keep the
device busy while the host lags; the FASE lesson: overlap host work with
in-flight target execution):

  * batch stacking — each window's per-step items are stacked into one
    contiguous (g, ...) payload per leaf, one upload per window;
  * one dispatch per clock-gated window — the *engine* is any
    ``(state, shell, batch_stack) -> (state, shell_snapshot, ys)``
    callable, typically a jit-compiled lax.scan over the stack with the
    model/opt state donated;
  * double-buffered shell + overlapped drain — in ``overlap`` mode the
    window's output shell is kept aside as a drain snapshot while ``reset``
    (device-side, e.g. ``pshell.group_reset``) hands the next window a
    fresh shell; the blocking host drain of window *i* then runs while
    window *i+1*'s compute is already in flight;
  * tail windows — a step count not divisible by the interval yields a
    final smaller window, executed and drained exactly once;
  * barrier points — a ``DrainBarrier`` forces the in-flight window to be
    drained and ACCEPTED by the host (an ``on_drain`` verifier that raises
    vetoes the commit) before its action (e.g. a checkpoint save) runs.

Engines must donate at most the model/opt state (argnum 0), never the
shell: the snapshot must survive on the host until its deferred drain.

``run_many`` schedules several engines through one pass on the calling
thread — many DUT boards, one host; window *w* of every engine is
dispatched back-to-back before any engine's window *w-1* results are
fetched, so every board's compute overlaps every board's drain. It is the
plain round-robin reference the farm (``repro.farm.manager``, one
:class:`ClientDriver` per slot thread) is tested against. Its hooks (all
optional, the bare 4-tuple form is unchanged):

  * per-client plumbing — a :class:`Client` carries its OWN drain_fn /
    stack_fn / reset, so one pass can mix shell-ful (train, decode) and
    shell-less (verify) boards;
  * device-aware dispatch — ``place_fn(k, stack)`` runs right before
    client *k*'s engine call (device placement of the window payload),
    and ``on_dispatch(k, plan, state)`` fires right after the dispatch is
    enqueued.
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis.annotations import thread_confined
from repro.core.profiler import Profiler, phase
from repro.core.pshell import _reset_jitted
from repro.core.pshell import drain as shell_drain
from repro.core.pshell import stack_batches
from repro.core.scope import ScopePlane, as_plane


@dataclasses.dataclass(frozen=True)
class WindowPlan:
    """One clock-gated window: ``size`` consecutive steps from ``start``."""
    index: int          # window ordinal within the run
    start: int          # global index of the window's first step
    size: int           # steps in this window (the tail window may be short)

    @property
    def last(self) -> int:
        """Global index of the window's last step (the drain cadence id —
        ``on_drain`` fires with this, matching the per-step loops)."""
        return self.start + self.size - 1

    @property
    def boundary(self) -> int:
        """Step count after this window completes (checkpoint step ids)."""
        return self.start + self.size


@dataclasses.dataclass(frozen=True)
class DrainBarrier:
    """A host commit point: when a window crosses a multiple of ``every``,
    the scheduler drains that window (in overlap mode this forfeits ONE
    window's drain/compute overlap, no more) so the host has accepted every
    step up to the boundary, then calls ``action(state, boundary_step)``."""
    every: int
    action: Callable[[Any, int], None]

    def fires(self, plan: WindowPlan) -> bool:
        return plan.boundary // self.every > plan.start // self.every


_INHERIT = object()         # Client field sentinel: use the scheduler's own


@dataclasses.dataclass
class Client:
    """One ``run_many`` board with per-client plumbing. Fields left at
    ``_INHERIT`` fall back to the scheduler's drain_fn/stack_fn/reset, so a
    bare ``(engine, windows, state, shell)`` tuple and
    ``Client(engine, windows, state, shell)`` behave identically.
    ``barriers`` are per-client :class:`DrainBarrier`\\ s — each client
    commits at its OWN window boundaries (the farm's per-job checkpoint
    path), independent of its neighbors' progress.

    ``start_step`` / ``start_index`` are the RESUME cursor: a client whose
    window stream was cut at a committed barrier re-enters the pass with
    the remaining windows only, and its plans carry the true global step /
    window ids — so barrier ``fires`` math, ``on_drain`` cadence, and
    tail-window sizing stay correct for non-divisible streams."""
    engine: Callable
    windows: Iterable
    state: Any = None
    shell: Any = None
    drain_fn: Any = _INHERIT
    stack_fn: Any = _INHERIT
    reset: Any = _INHERIT
    barriers: Sequence = ()
    start_step: int = 0
    start_index: int = 0
    lanes: int = 1      # >1: a LaneBatch-fused client driving N boards
    scope: Any = None   # ScopeSpec/ScopePlane: opt into the ZP-Scope
    # instrumentation plane — normalization binds the client's engine /
    # shell / drain / reset so on-device counters ride the window carry
    # (per-lane counter slices under a fused client)


def plan_windows(steps: int, interval: int, start: int = 0) -> List[WindowPlan]:
    """Partition steps [start, steps) into interval-sized windows plus a
    tail. Windows are aligned to ``start`` (the resume point), matching the
    fused train engine's legacy cadence."""
    interval = max(1, interval)
    plans = []
    i = start
    while i < steps:
        g = min(interval, steps - i)
        plans.append(WindowPlan(index=len(plans), start=i, size=g))
        i += g
    return plans


def iter_windows(items: Iterable[Any], interval: int):
    """Chunk a finite iterable of per-step items into window-sized lists."""
    interval = max(1, interval)
    buf: list = []
    for x in items:
        buf.append(x)
        if len(buf) == interval:
            yield buf
            buf = []
    if buf:
        yield buf


class WindowScheduler:
    """Owns the host loop shared by training, co-emulation, and serving.

    Parameters
    ----------
    interval : the clock-gating granularity (steps per window) — used only
        by :meth:`windows` convenience chunking; ``run`` consumes whatever
        window lists it is given.
    overlap : double-buffer the shell and defer each window's drain until
        the next window's compute is in flight. ``False`` drains serially
        in place (the per-step baselines and the bench's serial control).
    reset : device-side shell reset deriving the NEXT window's shell from
        the current snapshot (``pshell.group_reset`` jitted — the default
        whenever overlapping with the P-Shell ``drain_fn``, since an
        un-reset live shell would re-accumulate and re-drain prior
        windows' FIFO rows). Explicit ``None`` + ``drain_fn=None`` passes
        the snapshot through (shell-less clients).
    drain_fn : host-side ``shell -> (records, reset_shell)``; ``None``
        for clients whose results ride entirely in ``ys`` (co-emulation).
    stack_fn : stacks a window's item list into the engine payload;
        ``None`` hands the engine the raw item list (per-step engines —
        no redundant window copy).
    timer : a :class:`~repro.core.profiler.Profiler` bound to the calling
        thread for each :meth:`run` (``None``: whatever the thread has
        bound). Its phases are the farm slot thread's: ``slot.stack``
        (window assembly), ``slot.dispatch`` (engine call, shell reset,
        ``on_dispatch``), ``slot.fetch`` (the drain's blocking read),
        ``slot.verify`` (``on_drain``) and ``slot.commit`` (barrier
        actions). With ``overlap`` the fetch of window *i* runs while
        window *i+1* is in flight.
    """

    def __init__(self, interval: int = 1, *, overlap: bool = True,
                 reset: Optional[Callable] = None,
                 drain_fn: Optional[Callable] = shell_drain,
                 stack_fn: Optional[Callable] = stack_batches,
                 timer: Optional[Profiler] = None):
        self.interval = max(1, interval)
        self.overlap = overlap
        if overlap and reset is None and drain_fn is not None:
            if drain_fn is shell_drain:
                reset = _reset_jitted()
            else:
                raise ValueError(
                    "overlap=True with a drain_fn needs a device-side "
                    "`reset` to double-buffer the shell — without one the "
                    "un-reset snapshot becomes the live shell and every "
                    "drain re-reads prior windows' rows (pass reset=, or "
                    "an explicit identity lambda for non-accumulating "
                    "shells)")
        self.reset = reset
        self.drain_fn = drain_fn
        self.stack_fn = stack_fn
        self.timer = timer

    def windows(self, items: Iterable[Any]):
        return iter_windows(items, self.interval)

    # ------------------------------------------------------------- single --
    def run(self, engine, windows, state, shell, *, start_step: int = 0,
            on_drain: Optional[Callable] = None,
            on_dispatch: Optional[Callable] = None,
            barriers: Sequence[DrainBarrier] = (),
            scope: Any = None):
        """Drive ``engine`` over ``windows`` (an iterable of per-step item
        lists, e.g. from :meth:`windows`). Returns ``(state, last_ys,
        shell)``.

        Callbacks: ``on_dispatch(plan, state)`` fires right after a
        window's dispatch is enqueued (watchdog heartbeats);
        ``on_drain(plan, records, ys)`` fires once per window in window
        order with the drained shell records and the window's ys — raising
        here vetoes any barrier commit that depends on the window.

        ``scope`` (a ``ScopeSpec`` or ``ScopePlane``) opts this pass into
        the ZP-Scope instrumentation plane: on-device counters ride beside
        the shell and are fetched at the plane's read rate; the returned
        state/ys/shell are bit-identical to an un-instrumented pass
        (``plane.finalize`` unwraps the composite before returning).
        """
        drain_fn, reset = self.drain_fn, self.reset
        plane = None
        if scope is not None:
            plane = as_plane(scope)
            engine, shell, drain_fn, reset = plane.bind(
                engine, shell, drain_fn, reset)
        verify = None
        if on_drain is not None:
            def verify(plan, records, ys):
                with phase("slot.verify"):
                    on_drain(plan, records, ys)
        pending = None              # (plan, shell_snapshot, ys)
        last_ys = None
        step = start_step
        index = 0
        it = iter(windows)
        with self.timer.bind() if self.timer is not None else nullcontext():
            while True:
                with phase("slot.stack"):
                    try:
                        items = next(it)
                    except StopIteration:
                        break
                    if not items:
                        continue
                    stack = self.stack_fn(items) if self.stack_fn else items
                plan = WindowPlan(index=index, start=step, size=len(items))
                with phase("slot.dispatch"):
                    state, snap, ys = engine(state, shell, stack)
                    if self.overlap:
                        shell = reset(snap) if reset else snap
                    if on_dispatch is not None:
                        on_dispatch(plan, state)
                if self.overlap:
                    self._flush(pending, verify, drain_fn=drain_fn)
                    pending = (plan, snap, ys)
                else:
                    with phase("slot.fetch"):
                        records, shell = self._drain_now(snap,
                                                         drain_fn=drain_fn)
                    self._emit(plan, records, ys, verify)
                fired = [b for b in barriers if b.fires(plan)]
                if fired:
                    # commit barrier: every window up to the boundary must
                    # be drained and accepted before the action
                    self._flush(pending, verify, drain_fn=drain_fn)
                    pending = None
                    with phase("slot.commit"):
                        for b in fired:
                            b.action(state, plan.boundary)
                last_ys = ys
                step += len(items)
                index += 1
            self._flush(pending, verify, drain_fn=drain_fn)
        if plane is not None:
            shell = plane.finalize(shell)
        return state, last_ys, shell

    # -------------------------------------------------------------- multi --
    def _normalize_client(self, c) -> Client:
        if not isinstance(c, Client):
            engine, windows, state, shell = c
            c = Client(engine, windows, state, shell)
        drain_fn = self.drain_fn if c.drain_fn is _INHERIT else c.drain_fn
        stack_fn = self.stack_fn if c.stack_fn is _INHERIT else c.stack_fn
        reset = self.reset if c.reset is _INHERIT else c.reset
        if self.overlap and drain_fn is not None and reset is None:
            if drain_fn is shell_drain:
                reset = _reset_jitted()
            else:
                raise ValueError(
                    "run_many client with overlap=True and a drain_fn "
                    "needs a device-side `reset` to double-buffer its "
                    "shell (see WindowScheduler.__init__)")
        if c.scope is None:
            return dataclasses.replace(c, drain_fn=drain_fn,
                                       stack_fn=stack_fn, reset=reset)
        # ZP-Scope opt-in: bind the resolved plumbing so the counter tree
        # rides beside the DUT shell. Applied LAST so the counters see the
        # same engine/drain the un-instrumented client would run — the
        # bit-identity invariant the scope CI gate checks.
        plane = as_plane(c.scope, lanes=c.lanes)
        engine, shell, drain_fn, reset = plane.bind(
            c.engine, c.shell, drain_fn, reset)
        return dataclasses.replace(c, engine=engine, shell=shell,
                                   drain_fn=drain_fn, stack_fn=stack_fn,
                                   reset=reset, scope=plane)

    def driver(self, client, *, key=None,
               on_drain: Optional[Callable] = None,
               on_dispatch: Optional[Callable] = None,
               place_fn: Optional[Callable] = None,
               on_commit: Optional[Callable] = None,
               inject: Optional[Callable] = None) -> "ClientDriver":
        """A thread-confinable per-client pipeline over this scheduler's
        window/overlap settings (see :class:`ClientDriver`)."""
        return ClientDriver(self, client, key=key, on_drain=on_drain,
                            on_dispatch=on_dispatch, place_fn=place_fn,
                            on_commit=on_commit, inject=inject)

    def run_many(self, clients, on_drain: Optional[Callable] = None, *,
                 on_dispatch: Optional[Callable] = None,
                 place_fn: Optional[Callable] = None,
                 on_commit: Optional[Callable] = None):
        """Multi-board pass: ``clients`` is a list of ``(engine, windows,
        state, shell)`` tuples or :class:`Client`\\ s (per-client drain /
        stack / reset / barriers). Window *w* of EVERY client is dispatched
        before any client's window *w-1* is drained, so each engine's drain
        overlaps every engine's in-flight compute. Clients may have
        different window counts; a finished client's last pending window
        drains in the round it stops dispatching (after every still-alive
        client's dispatch, preserving the dispatch-before-fetch order).

        The per-client machinery lives in :class:`ClientDriver`; this
        method composes one driver per client round-robin on the CALLING
        thread — the plain reference the farm is tested against. The farm
        composes the same drivers one-per-slot-thread instead
        (``repro.farm.manager``), which is why the driver owns all of a
        client's JAX interactions.

        ``on_drain(client_idx, plan, records, ys)``;
        ``on_dispatch(client_idx, plan, state)`` fires right after a
        client's window dispatch is enqueued; ``place_fn(client_idx,
        stack)`` maps the stacked window payload right before the engine
        call (device placement); ``on_commit(client_idx, plan, state,
        shell)`` fires after a client's barrier actions committed a window
        boundary. Returns the list of final ``(state, shell)`` per client
        index."""
        drivers = [self.driver(c, key=k, on_drain=on_drain,
                               on_dispatch=on_dispatch, place_fn=place_fn,
                               on_commit=on_commit)
                   for k, c in enumerate(clients)]
        while not all(d.exhausted for d in drivers):
            progressed, finished = [], []
            for d in drivers:
                if not d.exhausted:
                    (progressed if d.dispatch() is not None
                     else finished).append(d)
            for d in finished:          # after every live client dispatched
                d.flush()
            for d in progressed:
                d.advance()
        for d in drivers:
            d.flush()
        return [(d.state, d.shell) for d in drivers]

    # ----------------------------------------------------------- plumbing --
    def _drain_now(self, snap, drain_fn=_INHERIT):
        drain_fn = self.drain_fn if drain_fn is _INHERIT else drain_fn
        if drain_fn is None:
            return {}, snap
        return drain_fn(snap)

    def _flush(self, pending, on_drain, client=None, drain_fn=_INHERIT):
        if pending is None:
            return
        drain_fn = self.drain_fn if drain_fn is _INHERIT else drain_fn
        plan, snap, ys = pending
        records = {}
        if drain_fn is not None:
            # the snapshot's reset state is discarded: the live shell was
            # reset on device
            with phase("slot.fetch"):
                records, _ = drain_fn(snap)
        self._emit(plan, records, ys, on_drain, client=client)

    @staticmethod
    def _emit(plan, records, ys, on_drain, client=None):
        if on_drain is None:
            return
        if client is None:
            on_drain(plan, records, ys)
        else:
            on_drain(client, plan, records, ys)


@thread_confined
class ClientDriver:
    """Thread-confined window pipeline for ONE client (one board's host
    driver).

    Owns every host<->device interaction for its client — window stacking,
    device placement, engine dispatch, shell double-buffer reset, deferred
    drains, and per-client :class:`DrainBarrier` commits — so a caller can
    confine a client's JAX dispatches to one thread (the async farm's
    per-slot dispatcher threads) or compose many drivers round-robin on a
    single thread (the reference :meth:`WindowScheduler.run_many`). The
    driver itself takes no locks: it must only ever be touched from the
    thread that drives it.

    Protocol per window:

      ``dispatch()`` — enqueue the next window (stack -> place -> engine
          call -> shell reset) and return its :class:`WindowPlan`, or
          ``None`` once the window stream is exhausted.
      ``advance()`` — retire ONE window's drain: in overlap mode the
          PREVIOUS window's (its blocking fetch runs while the window just
          dispatched is in flight), in serial mode the window just
          dispatched. Runs any barriers the dispatched window crossed —
          a barrier flushes the in-flight window first, so an ``on_drain``
          verifier that raises vetoes the commit action. When at least one
          barrier committed, ``on_commit(key, plan, state, shell)`` fires
          with the accepted boundary's state handle — the shell is the
          live (post-reset) one the NEXT window consumes, i.e. exactly
          what a resumed run must start from.
      ``flush()`` — retire the final pending window (stream end).
      ``cancel()`` — drop pending + dispatched windows undelivered and
          mark the driver exhausted (eviction: a requeued job re-runs its
          uncommitted tail elsewhere, so partial results must never reach
          ``on_drain``).

    Resume: the client's ``start_step``/``start_index`` seed the window
    cursor, so a driver over the TAIL of a window stream emits plans with
    the same global ids an uninterrupted run would.

    Fault injection: ``inject(key, point, plan)`` (optional, ``None`` in
    production) fires at the driver's three named points — ``"dispatch"``
    right before the engine call, ``"drain"`` as ``advance()`` starts
    retiring a window, ``"commit"`` right before a crossed barrier's
    actions run. A raising hook models the board failing exactly there; a
    sleeping hook models a hang. The chaos harness
    (``repro.farm.chaos``) drives these from a seeded schedule.
    """

    def __init__(self, sched: "WindowScheduler", client, *, key=None,
                 on_drain: Optional[Callable] = None,
                 on_dispatch: Optional[Callable] = None,
                 place_fn: Optional[Callable] = None,
                 on_commit: Optional[Callable] = None,
                 inject: Optional[Callable] = None):
        self.sched = sched
        self.c = sched._normalize_client(client)
        self.key = key
        self.on_drain = on_drain
        self.on_dispatch = on_dispatch
        self.place_fn = place_fn
        self.on_commit = on_commit
        self.inject = inject
        self._it = iter(self.c.windows)
        self.state = self.c.state
        self.shell = self.c.shell
        self.step = self.c.start_step
        self.index = self.c.start_index
        self.pending = None             # (plan, snapshot, ys) awaiting drain
        self._dispatched = None         # window in flight this round
        self.exhausted = False

    def dispatch(self) -> Optional[WindowPlan]:
        if self.exhausted:
            return None
        c = self.c
        with phase("slot.stack"):
            items = None
            while not items:            # skip empty windows, don't stall
                try:
                    items = next(self._it)
                except StopIteration:
                    self.exhausted = True
                    return None
            stack = c.stack_fn(items) if c.stack_fn else items
            if self.place_fn is not None:
                stack = self.place_fn(self.key, stack)
        plan = WindowPlan(index=self.index, start=self.step,
                          size=len(items))
        with phase("slot.dispatch"):
            if self.inject is not None:
                self.inject(self.key, "dispatch", plan)
            self.state, snap, ys = c.engine(self.state, self.shell, stack)
            if self.sched.overlap:
                self.shell = c.reset(snap) if c.reset else snap
            if self.on_dispatch is not None:
                self.on_dispatch(self.key, plan, self.state)
        self._dispatched = (plan, snap, ys)
        self.step += len(items)
        self.index += 1
        return plan

    def advance(self):
        cur, self._dispatched = self._dispatched, None
        if cur is None:
            return
        plan = cur[0]
        if self.inject is not None:
            self.inject(self.key, "drain", plan)
        if self.sched.overlap:
            self.flush()                # previous window's deferred drain
            self.pending = cur
        else:
            _, snap, ys = cur
            with phase("slot.fetch"):
                records, self.shell = self.sched._drain_now(
                    snap, drain_fn=self.c.drain_fn)
            self.sched._emit(plan, records, ys, self.on_drain,
                             client=self.key)
        fired = [b for b in self.c.barriers if b.fires(plan)]
        if fired:
            # commit barrier: every window up to the boundary must be
            # drained and accepted before the action (forfeits ONE
            # window's drain/compute overlap)
            self.flush()
            with phase("slot.commit"):
                if self.inject is not None:
                    self.inject(self.key, "commit", plan)
                for b in fired:
                    b.action(self.state, plan.boundary)
                if self.on_commit is not None:
                    self.on_commit(self.key, plan, self.state, self.shell)

    def flush(self):
        pending, self.pending = self.pending, None
        self.sched._flush(pending, self.on_drain, client=self.key,
                          drain_fn=self.c.drain_fn)

    def cancel(self):
        self.pending = None
        self._dispatched = None
        self.exhausted = True


# ------------------------------------------------------------------ lanes --
def lane_pack(trees):
    """Stack N same-structure pytrees along a NEW leading lane axis.

    The packing is identity-aware (the stacked-weight memory fix): a leaf
    that is the SAME object in every lane — a weight tree shared across
    boards — is NOT stacked; it passes through as ONE array with a ``None``
    vmap axis, so N lanes hold one device copy instead of N. Returns
    ``(packed, axes_tree, flat_axes)`` where ``axes_tree`` is the pytree
    handed to ``vmap`` as in/out_axes (0 = stacked, None = broadcast) and
    ``flat_axes`` is the same information in flat leaf order, which is what
    :func:`lane_slice` consumes to undo the packing per lane.
    """
    if all(t is None for t in trees):
        return None, None, []
    treedef = jax.tree.structure(trees[0])
    for t in trees[1:]:
        if jax.tree.structure(t) != treedef:
            raise ValueError("lane_pack: lane trees differ in structure "
                             f"({treedef} vs {jax.tree.structure(t)})")
    packed, axes = [], []
    for group in zip(*(jax.tree.leaves(t) for t in trees)):
        if all(g is group[0] for g in group[1:]):
            packed.append(group[0])
            axes.append(None)
        else:
            packed.append(jnp.stack([jnp.asarray(g) for g in group]))
            axes.append(0)
    return (jax.tree.unflatten(treedef, packed),
            jax.tree.unflatten(treedef, axes), axes)


def lane_slice(tree, flat_axes, k):
    """Lane ``k``'s view of a packed tree: stacked leaves are indexed at
    the lane axis, broadcast (shared) leaves pass through untouched."""
    if tree is None:
        return None
    leaves, treedef = jax.tree.flatten(tree)
    out = [x if a is None else x[k] for x, a in zip(leaves, flat_axes)]
    return jax.tree.unflatten(treedef, out)


def lane_fetch(tree, flat_axes):
    """ONE host fetch for a packed tree's stacked leaves (broadcast leaves
    pass through as their device arrays — a shared weight tree is never
    pulled to the host). Per-lane fan-out then takes numpy views of the
    fetched leaves instead of issuing one device gather + transfer per
    lane — N gathers per window is exactly the dispatch overhead lane
    batching exists to remove."""
    if tree is None:
        return None
    leaves, treedef = jax.tree.flatten(tree)
    fetched = iter(jax.device_get(
        [x for x, a in zip(leaves, flat_axes) if a == 0]))
    out = [next(fetched) if a == 0 else x
           for x, a in zip(leaves, flat_axes)]
    return jax.tree.unflatten(treedef, out)


# (engine-or-reset, packed treedefs, vmap axes) -> jitted vmap wrapper.
# Without this every LaneBatch built over the same base engine — e.g. each
# farm pass that coalesces a fresh batch of compatible jobs — would wrap a
# NEW jit(vmap(engine)) object and recompile from scratch, costing more
# than the dispatch fusion saves. Keyed on the engine OBJECT (kept alive
# by the key, same rationale as CoEmulator._group_fns: object keys make
# no-aliasing unconditional where id() keys would not).
_FUSED_CACHE: Dict[Any, Callable] = {}


class LaneBatch:
    """N identical-arch boards fused into ONE dispatch stream.

    The solo engine is wrapped in ``jit(vmap(...))`` over a leading lane
    axis, the per-lane window streams are zipped step-for-step, and the
    per-lane states/shells are :func:`lane_pack`-ed — so the existing
    ``lax.scan`` window dispatch drives N boards per device call while
    ``WindowPlan`` ids, barrier cadences, and drain ordering stay exactly
    what each solo board would have seen.

    Compatibility contract (what "identical-arch" means here):

      * ONE shared jax-traceable ``engine`` object — host side effects
        (sleeps, python counters) do not survive the vmap trace;
      * equal window counts AND equal per-window sizes across lanes
        (streams are zipped per step, tail windows included);
      * same state/shell tree structure with stackable leaf shapes; a leaf
        shared BY IDENTITY across every lane broadcasts as one device
        copy with a ``None`` vmap axis (the stacked-weight fix);
      * a ``stack_fn`` is required (raw per-step item lists cannot stack
        across lanes); ``drain_fn``/``reset`` are optional and are applied
        per lane against shell slices, with drains fanned out as
        ``{"lanes": [records_0, ...records_{N-1}]}``.

    The fused engine never donates: member state/shell objects stay valid
    replay sources if a lane is evicted and requeued as a solo board.
    """

    def __init__(self, engine, windows, states, shells, *, stack_fn,
                 drain_fn=None, reset=None):
        n = len(states)
        if n < 1 or not (len(windows) == len(shells) == n):
            raise ValueError("LaneBatch: windows/states/shells must be "
                             "equal-length and non-empty")
        if stack_fn is None:
            raise ValueError("LaneBatch requires a stack_fn")
        if drain_fn is shell_drain and reset is None:
            reset = _reset_jitted()     # same default a solo client gets
        if drain_fn is not None and reset is None:
            raise ValueError("LaneBatch: a custom drain_fn needs an "
                             "explicit reset (fused drains are deferred)")
        self.n = n
        self.base_engine = engine
        self.base_stack = stack_fn
        self.base_drain = drain_fn
        self.base_reset = reset
        self.state, self.state_axes, self._state_flat = lane_pack(states)
        self.shell, self.shell_axes, self._shell_flat = lane_pack(shells)
        self.windows = self.zip_windows(windows)
        self.engine = self._fuse_engine(engine)
        self.stack_fn = self._fused_stack
        self.drain_fn = self._fused_drain if drain_fn is not None else None
        self.reset = self._fuse_reset(reset)

    # ---------------------------------------------------------- builders --
    @staticmethod
    def zip_windows(window_lists):
        """Zip per-lane window streams into one fused stream whose plans
        (window count, per-window sizes, step ids) match every solo lane."""
        counts = {len(w) for w in window_lists}
        if len(counts) != 1:
            raise ValueError("LaneBatch: lanes disagree on window count: "
                             f"{sorted(counts)}")
        fused = []
        for w, row in enumerate(zip(*window_lists)):
            sizes = {len(items) for items in row}
            if len(sizes) != 1:
                raise ValueError(f"LaneBatch: window {w} sizes differ "
                                 f"across lanes: {sorted(sizes)}")
            fused.append([tuple(step) for step in zip(*row)])
        return fused

    def _tree_key(self, tree, flat):
        return (None if tree is None else jax.tree.structure(tree),
                tuple(flat))

    def _fuse_engine(self, engine):
        key = ("engine", engine,
               self._tree_key(self.state, self._state_flat),
               self._tree_key(self.shell, self._shell_flat))
        if key not in _FUSED_CACHE:
            _FUSED_CACHE[key] = jax.jit(jax.vmap(
                engine, in_axes=(self.state_axes, self.shell_axes, 0),
                out_axes=(self.state_axes, self.shell_axes, 0)))
        return _FUSED_CACHE[key]

    def _fuse_reset(self, reset):
        if reset is None:
            return None
        if not any(a == 0 for a in self._shell_flat):
            return reset            # fully shared shell: nothing to map
        key = ("reset", reset,
               self._tree_key(self.shell, self._shell_flat))
        if key not in _FUSED_CACHE:
            _FUSED_CACHE[key] = jax.jit(jax.vmap(
                reset, in_axes=(self.shell_axes,),
                out_axes=self.shell_axes))
        return _FUSED_CACHE[key]

    def _fused_stack(self, items):
        # items: [step][lane]; restack per lane with the base stack_fn so
        # each lane's payload is byte-identical to its solo run's, then add
        # the leading lane axis (one contiguous upload per leaf). The
        # cross-lane stack is jitted (cached): eager jnp.stack re-traces
        # expand_dims + concat per window, which costs more per window
        # than the fused dispatch saves.
        per_lane = list(zip(*items))
        stacks = [self.base_stack(list(steps)) for steps in per_lane]
        key = ("stack", self.n, jax.tree.structure(stacks[0]))
        if key not in _FUSED_CACHE:
            _FUSED_CACHE[key] = jax.jit(
                lambda *xs: jax.tree.map(lambda *ys: jnp.stack(ys), *xs))
        return _FUSED_CACHE[key](*stacks)

    def _fused_drain(self, snap):
        recs, resets = [], []
        for k in range(self.n):
            r, s = self.base_drain(self.slice_shell(snap, k))
            recs.append(r)
            resets.append(s)
        # re-pack the per-lane reset shells: serial (non-overlap) mode makes
        # this the live shell, overlap mode discards it after the drain
        treedef = jax.tree.structure(resets[0])
        packed = [g[0] if a is None
                  else jnp.stack([jnp.asarray(x) for x in g])
                  for g, a in zip(zip(*(jax.tree.leaves(s) for s in resets)),
                                  self._shell_flat)]
        return {"lanes": recs}, jax.tree.unflatten(treedef, packed)

    # ------------------------------------------------------------ fan-out --
    def slice_state(self, state, k):
        return lane_slice(state, self._state_flat, k)

    def slice_shell(self, shell, k):
        return lane_slice(shell, self._shell_flat, k)

    def fetch_state(self, state):
        """See :func:`lane_fetch` — host views for per-lane state fan-out."""
        return lane_fetch(state, self._state_flat)

    def fetch_shell(self, shell):
        return lane_fetch(shell, self._shell_flat)

    def fan_out_one(self, records, ys, k):
        """Lane ``k``'s (records, ys) exactly as its solo run would have
        delivered them to ``on_drain``."""
        rec = records["lanes"][k] if self.drain_fn is not None else records
        return rec, jax.tree.map(lambda y: y[k], ys)

    def fan_out(self, records, ys):
        return [self.fan_out_one(records, ys, k) for k in range(self.n)]

    def client(self, *, barriers=()) -> Client:
        """A ready-to-run fused :class:`Client` for this batch."""
        return Client(self.engine, self.windows, self.state, self.shell,
                      drain_fn=self.drain_fn, stack_fn=self.stack_fn,
                      reset=self.reset, barriers=barriers, lanes=self.n)
