"""Lane-batched boards: N identical-arch DUTs fused into ONE vmap-ed
dispatch stream. The contract under test: lane packing broadcasts
identity-shared weight trees as one device copy; a fused LaneBatch run is
bit-identical to the N solo runs it replaces (through the raw scheduler
AND through the farm, on several slots and on one, tail windows included);
the farm coalesces compatible queued jobs up to the slot's lane capacity
and refuses incompatible ones for a nameable reason; a verify failure
vetoes ONE lane — detached and requeued solo from its per-lane barrier
snapshot — while the surviving lanes keep running; and divergences,
watchdog observations, and subsystem verification all stay lane-aware."""
import threading
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DrainBarrier
from repro.core.coemu import CommitDivergence, CommitStreamVerifier
from repro.core.schedule import (Client, LaneBatch, WindowScheduler,
                                 lane_pack, lane_slice)
from repro.core.watchdog import Watchdog
from repro.farm import FarmJob, FarmManager, lane_compatible

jax.config.update("jax_platform_name", "cpu")

W = jnp.asarray(np.random.RandomState(0).randn(8, 8).astype(np.float32))


# ----------------------------------------------------------- toy workload --
@jax.jit
def _body(state, stack):
    def step(s, x):
        y = jnp.tanh(x @ s["w"]) + s["bias"]
        return ({"bias": s["bias"] + 0.01 * jnp.sum(y), "w": s["w"]},
                jnp.sum(y, axis=-1))
    return jax.lax.scan(step, state, stack)


def _engine(state, shell, stack):
    s, ys = _body(state, stack)
    return s, shell, ys


def _stack(items):
    return jnp.asarray(np.stack(items))


def _state(i):
    return {"bias": jnp.float32(i) * 0.5, "w": W}


def _windows(seed, n_steps=7, group=2):
    rng = np.random.RandomState(seed)
    items = [rng.randn(4, 8).astype(np.float32) for _ in range(n_steps)]
    return [items[i:i + group] for i in range(0, n_steps, group)]


def _solo_outputs(n_boards, n_steps=7, group=2):
    """Each board run alone through the scheduler: the bit-identity
    oracle for every fused variant below."""
    outs = []
    for i in range(n_boards):
        got = []
        sched = WindowScheduler(stack_fn=_stack, drain_fn=None)
        sched.run(_engine, _windows(i, n_steps, group), _state(i), {},
                  on_drain=lambda p, r, y: got.append(
                      (p.index, p.start, np.asarray(y))))
        outs.append(got)
    return outs


# ------------------------------------------------------------- lane_pack --
def test_lane_pack_broadcasts_identity_shared_leaves():
    """The stacked-weight memory fix: a leaf that is the SAME object in
    every lane passes through as ONE array with a None vmap axis; only
    genuinely differing leaves get stacked."""
    states = [_state(i) for i in range(4)]
    packed, axes, flat = lane_pack(states)
    assert packed["w"] is W                     # one device copy, not 4
    assert axes["w"] is None and axes["bias"] == 0
    assert packed["bias"].shape == (4,)
    for k in range(4):
        sl = lane_slice(packed, flat, k)
        assert sl["w"] is W
        np.testing.assert_array_equal(np.asarray(sl["bias"]),
                                      np.asarray(states[k]["bias"]))


def test_lane_pack_rejects_structure_mismatch():
    with pytest.raises(ValueError, match="structure"):
        lane_pack([{"a": W}, {"b": W}])


def test_zip_windows_rejects_shape_mismatch():
    with pytest.raises(ValueError, match="window count"):
        LaneBatch.zip_windows([_windows(0, 7, 2), _windows(1, 9, 2)])
    with pytest.raises(ValueError, match="sizes differ"):
        LaneBatch.zip_windows([_windows(0, 7, 2), _windows(1, 8, 2)])


# ------------------------------------------------- scheduler bit-identity --
def test_lane_batch_scheduler_bit_identity():
    """One fused client through the raw WindowScheduler delivers, per
    lane, exactly the (plan ids, ys) each solo run delivers."""
    n = 4
    solo = _solo_outputs(n)
    lb = LaneBatch(_engine, [_windows(i) for i in range(n)],
                   [_state(i) for i in range(n)], [{} for _ in range(n)],
                   stack_fn=_stack)
    assert lb.state["w"] is W                   # fix survives the fuse
    fused = []
    sched = WindowScheduler(stack_fn=None, drain_fn=None)
    sched.run_many([lb.client()],
                   on_drain=lambda k, p, r, y: fused.append((p, r, y)))
    assert len(fused) == len(solo[0])
    for (plan, records, ys), *_ in zip(fused):
        for k in range(n):
            _, lane_ys = lb.fan_out_one(records, ys, k)
            idx, start, want = solo[k][plan.index]
            assert (plan.index, plan.start) == (idx, start)
            np.testing.assert_array_equal(np.asarray(lane_ys), want)


# ------------------------------------------------------ farm bit-identity --
def _submit_lane_jobs(mgr, n, *, n_steps=7, group=2, lane_key="arch-a",
                      verify_for=None, verify=None, max_requeues=2):
    outs = {}
    for i in range(n):
        name = f"b{i}"
        outs[name] = []
        mgr.submit(FarmJob(
            name=name, engine=_engine, windows=_windows(i, n_steps, group),
            state=_state(i), shell={}, stack_fn=_stack,
            on_drain=lambda p, r, y, nm=name: outs[nm].append(
                (p.index, p.start, np.asarray(y))),
            barriers=(DrainBarrier(every=1, action=lambda s, b: None),),
            verify=verify if verify_for == i else None,
            lane_key=lane_key, max_requeues=max_requeues))
    return outs


@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
@pytest.mark.parametrize("n_steps,group", [(7, 2), (8, 2), (9, 4)])
def test_farm_lanes_bit_identical_to_solo(slots, n_steps, group):
    """The acceptance oracle: a lane-coalesced farm pass (tail windows
    included) delivers every board's outputs and final state bit-identical
    to the solo farm pass, and actually coalesced (one dispatch stream)."""
    n = 4
    solo_mgr = FarmManager(slots=slots, evict_stragglers=False)
    solo = _submit_lane_jobs(solo_mgr, n, n_steps=n_steps, group=group,
                             lane_key=None)
    solo_mgr.run()

    mgr = FarmManager(slots=slots, evict_stragglers=False, lanes=n)
    outs = _submit_lane_jobs(mgr, n, n_steps=n_steps, group=group)
    rep = mgr.run()
    assert rep["telemetry"]["lanes_per_dispatch_max"] == n
    for name in solo:
        assert len(outs[name]) == len(solo[name])
        for (ia, sa, ya), (ib, sb, yb) in zip(solo[name], outs[name]):
            assert ia == ib and sa == sb
            np.testing.assert_array_equal(ya, yb)
        for a, b in zip(jax.tree.leaves(solo_mgr.results[name][0]),
                        jax.tree.leaves(mgr.results[name][0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_farm_lane_capacity_splits_queue():
    """5 compatible jobs on a capacity-4 slot: one 4-lane dispatch plus
    one solo run — never a partial merge beyond capacity."""
    mgr = FarmManager(slots=1, evict_stragglers=False,
                      lanes=4)
    outs = _submit_lane_jobs(mgr, 5)
    rep = mgr.run()
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    stats = [d["lanes_per_dispatch"]
             for d in rep["telemetry"]["devices"].values()
             if "lanes_per_dispatch" in d]
    assert rep["telemetry"]["lanes_per_dispatch_max"] == 4
    # one 4-lane dispatch + one solo: two samples, mean 2.5
    assert [s["n"] for s in stats] == [2]
    assert stats[0]["mean"] == pytest.approx(2.5)
    assert all(len(v) == 4 for v in outs.values())


# ---------------------------------------------------------- compatibility --
def test_lane_compatible_names_the_mismatch():
    def job(**kw):
        base = dict(name="j", engine=_engine, windows=_windows(0),
                    state=_state(0), shell={}, stack_fn=_stack,
                    lane_key="arch-a")
        base.update(kw)
        return FarmJob(**base)

    a = job()
    assert lane_compatible(a, job(name="k")) is None
    assert "lane_key" in lane_compatible(a, job(lane_key="arch-b"))
    assert "engine" in lane_compatible(a, job(engine=lambda s, h, x: 0))
    assert "stack_fn" in lane_compatible(
        a, job(stack_fn=lambda it: jnp.asarray(np.stack(it))))
    assert "window" in lane_compatible(a, job(windows=_windows(1, 9, 2)))
    assert "shape" in lane_compatible(
        a, job(state={"bias": jnp.zeros((3,)), "w": W}))
    assert "cadence" in lane_compatible(
        a, job(barriers=(DrainBarrier(every=2,
                                      action=lambda s, b: None),)))
    b = job()
    b.committed_outputs = [np.float32(1)]
    assert "resume" in lane_compatible(a, b)


# ------------------------------------------------------ lane-granular veto --
@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_lane_veto_evicts_only_the_faulted_lane(slots, n=4, bad=2):
    """A verify failure mid-stream names ONE lane: that member is
    detached and requeued solo (resuming from its per-lane snapshot, not
    window 0), the survivors keep running, and every board — including
    the vetoed one — still delivers exactly-once outputs bit-identical
    to its solo run."""
    solo_mgr = FarmManager(slots=slots, evict_stragglers=False)
    solo = _submit_lane_jobs(solo_mgr, n, lane_key=None)
    solo_mgr.run()

    marked = {"done": False}

    def chaos_verify(plan, records, ys):
        if plan.index == 2 and not marked["done"]:
            marked["done"] = True
            raise RuntimeError("injected lane fault")

    mgr = FarmManager(slots=slots, evict_stragglers=False, lanes=n)
    outs = _submit_lane_jobs(mgr, n, verify_for=bad, verify=chaos_verify)
    rep = mgr.run(strict=False)

    vetoes = rep["telemetry"]["lane_vetoes"]
    assert len(vetoes) == 1 and vetoes[0]["job"] == f"b{bad}"
    assert vetoes[0]["lane"] == bad
    assert all(j["status"] == "done" for j in rep["jobs"].values())
    assert rep["jobs"][f"b{bad}"]["requeues"] == 1
    assert all(rep["jobs"][f"b{i}"]["requeues"] == 0
               for i in range(n) if i != bad)
    # snapshot resume, not full-stream replay
    j = rep["jobs"][f"b{bad}"]
    assert j["windows_committed"] > 0
    assert j["windows_replayed"] < len(_windows(bad))
    for name in solo:
        # exactly-once delivery, in order, bit-identical
        assert Counter(i for i, _, _ in outs[name]) \
            == Counter(range(len(solo[name])))
        for (ia, sa, ya), (ib, sb, yb) in zip(solo[name], outs[name]):
            assert ia == ib and sa == sb
            np.testing.assert_array_equal(ya, yb)


# ------------------------------------------------------- fused shell path --
def _shell_engine(state, shell, stack):
    s, ys = _body(state, stack)
    # gather, not a reduction: a vmap-ed sum may reassociate and drift in
    # low mantissa bits, and this test's contract is exact fan-out
    return s, {"acc": shell["acc"] + ys[-1, 0]}, ys


def _shell_drain(shell):
    return {"acc": shell["acc"]}, {"acc": jnp.zeros_like(shell["acc"])}


def _shell_reset(shell):
    return {"acc": jnp.zeros_like(shell["acc"])}


@pytest.mark.parametrize("slots", [pytest.param(1, id="async"),
                                   pytest.param(2, id="two-slot")])
def test_fused_custom_drain_fans_records_out_per_lane(slots, n=3):
    """Boards with a custom drain_fn/reset shell: the fused drain runs the
    base drain per lane against shell SLICES and each member's on_drain
    sees exactly the records its solo run produces (the solo runs spread
    over the slots; the fused run takes one)."""
    def run(lanes):
        mgr = FarmManager(slots=slots, evict_stragglers=False,
                          lanes=lanes)
        recs = {}
        for i in range(n):
            name = f"b{i}"
            recs[name] = []
            mgr.submit(FarmJob(
                name=name, engine=_shell_engine, windows=_windows(i),
                state=_state(i), shell={"acc": jnp.float32(0)},
                stack_fn=_stack, drain_fn=_shell_drain,
                reset=_shell_reset,
                on_drain=lambda p, r, y, nm=name: recs[nm].append(
                    float(np.asarray(r["acc"]))),
                lane_key="shelly"))
        rep = mgr.run()
        return recs, rep

    solo, _ = run(lanes=1)
    fused, rep = run(lanes=n)
    assert rep["telemetry"]["lanes_per_dispatch_max"] == n
    assert fused == solo


# ------------------------------------------------------------- lane extras --
def test_commit_stream_verifier_stamps_the_lane():
    def oracle_step(state, batch):
        b = jnp.float32(batch)
        aux = {"scanned": (),
               "tail": ({"checksum": jnp.stack([b, b * 2.0])},)}
        return state + b, {}, aux

    rows = np.asarray([[0.0, 5.0, 999.0]], np.float64)   # diverged row
    records = {"fifos": {"commits": {"data": rows, "count": 1,
                                     "dropped": 0}}}
    v = CommitStreamVerifier(oracle_step, jnp.float32(0), [5.0],
                             layers=1, lane=3)
    with pytest.raises(CommitDivergence, match="lane 3") as ei:
        v(0, records)
    assert ei.value.lane == 3 and ei.value.step == 0


def test_watchdog_observe_normalizes_by_lane_count():
    """A 16-lane dispatch does 16 boards of work per window: its wall is
    recorded per board so the straggler detector never flags the fused
    run as a 16x straggler against solo boards."""
    wd = Watchdog(timeout_s=10.0, clock=lambda: 0.0)
    wd.observe("solo", 0.1)
    wd.observe("fused", 1.6, lanes=16)
    assert wd.durations["fused"][-1] == pytest.approx(0.1)
    assert wd.stragglers(factor=2.0, min_fleet=2) == []


def test_verify_subsystems_lanes_matches_solo_and_localizes_faults():
    """The ZP-Farm subsystem pass under lane coalescing: same-spec blocks
    share one engine and pack into lanes, the reports match the solo pass
    field-for-field, and an injected fault still localizes to its layer."""
    from repro.configs import get_smoke_config
    from repro.core.coemu import inject_fault, verify_subsystems
    from repro.models import build_model
    from repro.models.runtime import Runtime
    from repro.utils import dtype_of

    cfg = get_smoke_config("recurrentgemma-2b")   # layers 0,1 share a spec
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    B, S = 2, 16
    xs = [jax.random.normal(jax.random.key(i), (B, S, cfg.d_model))
          .astype(dtype_of(cfg.dtype)) for i in range(4)]
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))

    solo = verify_subsystems(params, cfg, Runtime(), xs, pos,
                             layer_idxs=[0, 1])
    laned = verify_subsystems(params, cfg, Runtime(), xs, pos,
                              layer_idxs=[0, 1], lanes=True)
    for k in solo:
        assert laned[k].diverged == solo[k].diverged is False
        assert laned[k].steps == solo[k].steps
        assert laned[k].max_rel_err == pytest.approx(solo[k].max_rel_err)

    bad = inject_fault(params, cfg, layer=1)
    rep = verify_subsystems(params, cfg, Runtime(), xs, pos,
                            layer_idxs=[0, 1], dut_params=bad, lanes=True)
    assert not rep["layer0"].diverged
    assert rep["layer1"].diverged and rep["layer1"].first.layer == 1
