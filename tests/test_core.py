"""Core-layer tests: P-Shell semantics, non-interference, co-emulation
mutation localization, coverage, Scale-Down decomposition, watchdog, timing.
These verify the paper's claims as executable properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.core import (
    FifoSpec, ShellConfig, PShell, shell_init, fifo_push, fifo_push_many,
    drain, default_shell_config, make_ingest, CoEmulator, CoverageMap,
    Timeline, Watchdog)
from repro.core.coemu import inject_fault
from repro.core import decompose
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.train import make_train_step, init_state

jax.config.update("jax_platform_name", "cpu")


def small_shell(depth=4, shape=(2,)):
    return ShellConfig(fifos={"f": FifoSpec(depth=depth, shape=shape)},
                       csrs={})


# --------------------------------------------------------- FIFO semantics ---
def test_fifo_push_and_drop():
    cfg = small_shell(depth=3)
    s = shell_init(cfg)
    for i in range(5):
        s = fifo_push(s, "f", jnp.full((2,), float(i)))
    rec, s = drain(s)
    assert rec["fifos"]["f"]["count"] == 3
    assert rec["fifos"]["f"]["dropped"] == 2       # credit exhaustion, no block
    np.testing.assert_array_equal(rec["fifos"]["f"]["data"][:, 0],
                                  [0.0, 1.0, 2.0])
    # drain resets count, preserves the cumulative dropped CSR-style counter
    rec2, _ = drain(s)
    assert rec2["fifos"]["f"]["count"] == 0
    assert rec2["fifos"]["f"]["dropped"] == 2


@settings(max_examples=20, deadline=None)
@given(depth=st.integers(1, 16), pushes=st.lists(st.integers(1, 8),
                                                 min_size=1, max_size=6))
def test_fifo_credit_accounting_property(depth, pushes):
    """Property: count + dropped == total pushed; count <= depth; payloads
    that fit are stored in order (semi-blocking contract)."""
    cfg = small_shell(depth=depth, shape=(1,))
    s = shell_init(cfg)
    total = 0
    for n in pushes:
        batch = jnp.arange(total, total + n, dtype=jnp.float32)[:, None]
        s = fifo_push_many(s, "f", batch)
        total += n
    rec, _ = drain(s)
    count, dropped = rec["fifos"]["f"]["count"], rec["fifos"]["f"]["dropped"]
    assert count + dropped == total
    assert count == min(depth, total)
    np.testing.assert_array_equal(rec["fifos"]["f"]["data"][:, 0],
                                  np.arange(count, dtype=np.float32))


def test_fifo_push_many_count_capped_and_partial_overflow_exact():
    """count never exceeds depth; a partially-overflowing push stores
    exactly the entries that fit (in order) and counts the rest dropped."""
    cfg = small_shell(depth=4, shape=(1,))
    s = shell_init(cfg)
    s = fifo_push_many(s, "f", jnp.arange(3, dtype=jnp.float32)[:, None])
    assert int(s["fifo"]["f"]["count"]) == 3
    # 5 more into 1 free slot: 1 stored, 4 dropped
    s = fifo_push_many(s, "f",
                       jnp.arange(10, 15, dtype=jnp.float32)[:, None])
    assert int(s["fifo"]["f"]["count"]) == 4
    assert int(s["fifo"]["f"]["dropped"]) == 4
    rec, s = drain(s)
    np.testing.assert_array_equal(rec["fifos"]["f"]["data"][:, 0],
                                  [0.0, 1.0, 2.0, 10.0])
    # push into the fully-drained FIFO: count restarts, dropped accumulates
    s = fifo_push_many(s, "f",
                       jnp.arange(20, 26, dtype=jnp.float32)[:, None])
    assert int(s["fifo"]["f"]["count"]) == 4
    assert int(s["fifo"]["f"]["dropped"]) == 4 + 2
    rec2, _ = drain(s)
    np.testing.assert_array_equal(rec2["fifos"]["f"]["data"][:, 0],
                                  [20.0, 21.0, 22.0, 23.0])


def test_fifo_drain_preserves_cumulative_dropped():
    cfg = small_shell(depth=2, shape=(1,))
    s = shell_init(cfg)
    dropped = 0
    for round_ in range(3):
        s = fifo_push_many(s, "f", jnp.ones((5, 1), jnp.float32))
        dropped += 3                      # 2 fit, 3 drop each round
        rec, s = drain(s)
        assert rec["fifos"]["f"]["count"] == 2
        assert rec["fifos"]["f"]["dropped"] == dropped
    rec, _ = drain(s)
    assert rec["fifos"]["f"]["count"] == 0         # drain resets occupancy
    assert rec["fifos"]["f"]["dropped"] == dropped  # counter survives


def test_grouped_ingest_undersized_fifo_drops_deterministically():
    """A fused group pushing into an undersized FIFO drops the SAME entries
    with the SAME credit accounting on every identical run (never blocks,
    never races)."""
    cfg = small_shell(depth=5, shape=(2,))

    @jax.jit
    def group(s, stacks):
        def body(s, payload):
            return fifo_push_many(s, "f", payload), None
        s, _ = jax.lax.scan(body, s, stacks)
        return s

    stacks = jnp.arange(4 * 3 * 2, dtype=jnp.float32).reshape(4, 3, 2)
    out = []
    for _ in range(2):
        rec, _ = drain(group(shell_init(cfg), stacks))
        out.append(rec)
    for rec in out:
        f = rec["fifos"]["f"]
        assert f["count"] == 5                 # capped at depth
        assert f["dropped"] == 4 * 3 - 5       # exact credit accounting
        # the stored prefix is the first 5 pushes in scan order
        np.testing.assert_array_equal(
            f["data"], stacks.reshape(-1, 2)[:5])
    np.testing.assert_array_equal(out[0]["fifos"]["f"]["data"],
                                  out[1]["fifos"]["f"]["data"])


def test_fifo_push_many_under_jit():
    cfg = small_shell(depth=4, shape=(3,))

    @jax.jit
    def step(s, x):
        return fifo_push_many(s, "f", x)

    s = shell_init(cfg)
    s = step(s, jnp.ones((6, 3)))
    rec, _ = drain(s)
    assert rec["fifos"]["f"]["count"] == 4
    assert rec["fifos"]["f"]["dropped"] == 2


# -------------------------------------------------------- non-interference --
@pytest.mark.parametrize("arch", ["glm4-9b", "qwen3-moe-30b-a3b"])
def test_shell_non_interference(arch):
    """Model state after N steps is BITWISE identical with the shell on
    (any sample interval) or off — the clock-gating non-interference claim."""
    cfg = get_smoke_config(arch)
    key = jax.random.key(0)
    batches = [{"tokens": jax.random.randint(jax.random.key(i), (2, 16), 0,
                                             cfg.vocab_size),
                "labels": jax.random.randint(jax.random.key(i + 99), (2, 16),
                                             0, cfg.vocab_size)}
               for i in range(2)]

    def run(taps, interval):
        model = build_model(cfg, Runtime(taps=taps))
        state = init_state(model, key)
        step = jax.jit(make_train_step(model, with_aux=True))
        shell_cfg = default_shell_config(cfg, sample_interval=interval)
        shell = PShell(shell_cfg, make_ingest(cfg))
        if "commits" in taps:
            wrapped = shell.wrap(step)
            sh = shell.init()
            for b in batches:
                state, m, sh = wrapped(state, b, sh)
        else:
            for b in batches:
                state, m, _ = step(state, b)
        return state["params"]

    p_off = run(frozenset(), 1)
    p_on1 = run(frozenset({"commits", "coverage", "router"}), 1)
    p_on3 = run(frozenset({"commits", "coverage", "router"}), 3)
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(p_on1), jax.tree.leaves(p_on3)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ co-emulation --
def _mk_step(cfg, params_xform=None):
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    key = jax.random.key(1)
    state = init_state(model, key)
    if params_xform:
        state = {**state, "params": params_xform(state["params"])}
    step = jax.jit(make_train_step(model, with_aux=True))
    return step, state


def test_coemu_pass_and_determinism():
    cfg = get_smoke_config("granite-8b")
    step, state = _mk_step(cfg)
    batches = [{"tokens": jax.random.randint(jax.random.key(7), (2, 16), 0,
                                             cfg.vocab_size),
                "labels": jax.random.randint(jax.random.key(8), (2, 16), 0,
                                             cfg.vocab_size)}]
    emu = CoEmulator(step, step, rtol=1e-6)
    rep = emu.verify(state, state, batches)
    assert not rep.diverged, rep.summary()
    assert CoEmulator.determinism(step, state, batches[0])


@pytest.mark.parametrize("fault_layer", [0, 1])
def test_coemu_localizes_injected_fault(fault_layer):
    """Mutation test: a fault injected at layer k must be reported with
    first-divergence layer == k (the Dromajo-style debugging contract)."""
    cfg = get_smoke_config("glm4-9b")
    step, state_good = _mk_step(cfg)
    _, state_bad = _mk_step(
        cfg, params_xform=lambda p: inject_fault(p, cfg, fault_layer))
    batch = {"tokens": jax.random.randint(jax.random.key(9), (2, 16), 0,
                                          cfg.vocab_size),
             "labels": jax.random.randint(jax.random.key(10), (2, 16), 0,
                                          cfg.vocab_size)}
    emu = CoEmulator(step, step, rtol=5e-2)
    rep = emu.verify(state_bad, state_good, [batch])
    assert rep.diverged
    assert rep.first.layer == fault_layer, rep.summary()


def test_commit_checksum_is_relative_to_the_activation_scale():
    """The commit checksum is (abs-mean, rms), both of the activation's
    own scale: rounding every element of a zero-mean tensor by a bf16 ulp
    moves it by far less than the verifier's tolerance, where a signed
    mean, which sits near zero, would move by its own size."""
    from repro.core.coemu import _rel_err
    from repro.utils import checksum

    kx, kn = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (2, 512, 896))
    x = x - jnp.mean(x)
    sign = jnp.where(jax.random.bernoulli(kn, 0.5, x.shape), 1.0, -1.0)
    y = x * (1.0 + 2.0 ** -8 * sign)
    np.testing.assert_allclose(
        np.asarray(checksum(x)),
        [float(jnp.mean(jnp.abs(x))), float(jnp.sqrt(jnp.mean(x * x)))],
        rtol=1e-6)
    assert np.all(np.asarray(checksum(x)) > 0)
    assert _rel_err(np.asarray(checksum(y), np.float64),
                    np.asarray(checksum(x), np.float64)).max() < 1e-3
    signed = _rel_err(np.float64(jnp.mean(y)), np.float64(jnp.mean(x)))
    assert signed > 1e-2


# ---------------------------------------------------------------- coverage --
def test_coverage_accumulates_and_saturates():
    cfg = get_smoke_config("mixtral-8x7b")
    model = build_model(cfg, Runtime(taps=frozenset({"commits", "coverage",
                                                     "router"})))
    state = init_state(model, jax.random.key(2))
    step = jax.jit(make_train_step(model, with_aux=True))
    shell_cfg = default_shell_config(cfg)
    shell = PShell(shell_cfg, make_ingest(cfg))
    sh = shell.init()
    cov = CoverageMap()
    incs = []
    for i in range(4):
        batch = {"tokens": jax.random.randint(jax.random.key(i), (4, 16), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.key(i + 50), (4, 16),
                                              0, cfg.vocab_size)}
        state, m, sh = shell.wrap(step)(state, batch, sh)
        rec, sh = drain(sh)
        incs.append(cov.update(rec["csrs"]))
    assert 0.0 < cov.fraction("expert_toggles") <= 1.0
    assert incs[0] > 0
    assert incs[-1] <= incs[0]          # coverage increments shrink


# -------------------------------------------------------------- decompose ---
@pytest.mark.parametrize("arch,layer", [("glm4-9b", 1),
                                        ("recurrentgemma-2b", 2),
                                        ("falcon-mamba-7b", 0)])
def test_scale_down_extraction_bitwise(arch, layer):
    """Extracted-block replay of captured in-situ traffic is bit-identical:
    the interface-preservation (non-interference of the DUT) claim."""
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(3))
    x = (jax.random.normal(jax.random.key(4), (2, 16, cfg.d_model))
         .astype(jnp.bfloat16))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    rep = decompose.verify_extraction(params, cfg, x, pos, model.rt, layer)
    assert rep["bitwise_identical"], rep


def test_scanned_matches_unrolled():
    cfg = get_smoke_config("recurrentgemma-2b")   # hybrid pattern + tail
    model = build_model(cfg)
    params = model.init(jax.random.key(5))
    x = (jax.random.normal(jax.random.key(6), (2, 16, cfg.d_model))
         .astype(jnp.bfloat16))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    d = decompose.scanned_vs_unrolled(params, cfg, x, pos, model.rt)
    assert d < 2e-2, f"scan-vs-unrolled diff {d}"


# ------------------------------------------------------- watchdog / timing --
def test_watchdog_detects_death_and_stragglers():
    t = [0.0]
    wd = Watchdog(timeout_s=5.0, clock=lambda: t[0])
    for i in range(5):
        wd.heartbeat("slow")        # slow beats once per 4s cycle
        for _ in range(4):
            wd.heartbeat("fast0")   # fast workers beat every 1s
            wd.heartbeat("fast1")
            t[0] += 1.0
    assert wd.stragglers(factor=1.5) == ["slow"]
    t[0] += 10.0
    assert set(wd.dead_workers()) == {"fast0", "fast1", "slow"}
    assert wd.should_restart()


def test_timing_timeline_overlap():
    groups = [{"compute_s": 1.0, "memory_s": 0.4, "collective_s": 0.8}] * 4
    t_ov = Timeline(overlap=True).simulate(groups)
    t_ser = Timeline(overlap=False).simulate(groups)
    assert t_ov["total_s"] == pytest.approx(4.0)      # max(1.0, 0.8) x4
    assert t_ser["total_s"] == pytest.approx(7.2)     # (1.0 + 0.8) x4
    assert t_ov["dominant"] == "compute"


def test_watchdog_concurrent_observe_forget_stragglers_stress():
    """Watchdog is hammered from slot threads (observe/heartbeat) while
    the control thread polls stragglers()/dead_workers() and forgets
    evicted workers: no exceptions, per-worker duration rings stay
    bounded at 64 samples, and no sample ever lands on the wrong worker
    (each worker observes only its own constant)."""
    import threading

    wd = Watchdog(timeout_s=60.0)
    n_workers, iters, errors = 8, 300, []
    stop = threading.Event()

    def worker(i):
        name = f"w{i}"
        try:
            for _ in range(iters):
                wd.heartbeat(name, gap=False)
                wd.observe(name, float(i + 1))
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                wd.stragglers(2.0)
                wd.dead_workers()
                wd.forget("ghost")                  # unknown name: no-op
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,),
                                name=f"stress-w{i}")
               for i in range(n_workers)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers + threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    for t in readers:
        t.join()

    assert not errors
    for i in range(n_workers):
        name = f"w{i}"
        samples = list(wd.durations[name])
        assert len(samples) == 64                   # ring stays bounded
        assert all(s == float(i + 1) for s in samples)
        assert wd.threads[name] == f"stress-w{i}"

    # concurrent forget vs observe on the SAME workers: still no
    # exceptions, and any surviving ring holds only that worker's value
    def churn(i):
        name = f"w{i}"
        try:
            for _ in range(200):
                wd.observe(name, float(i + 1))
                wd.forget(name)
        except Exception as e:                      # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=churn, args=(i,))
               for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    for i in range(n_workers):
        assert all(s == float(i + 1)
                   for s in wd.durations.get(f"w{i}", []))
