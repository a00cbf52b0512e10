"""Substrate tests: data determinism, checkpoint integrity/retention,
gradient accumulation equivalence, EF compression properties, loop resume."""
import os
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.checkpoint import CheckpointManager
from repro.data import make_batch_fn, SyntheticPipeline
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.train import make_train_step, init_state
from repro.train.compress import (quantize, dequantize, ef_compress_leaf,
                                  make_compressor, init_residuals)
from repro.train.loop import LoopConfig, train_loop
from repro.train.optim import OptConfig

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------------------- data ---
def test_data_determinism():
    cfg = get_smoke_config("granite-8b")
    f1 = make_batch_fn(cfg, 4, 16, seed=7)
    f2 = make_batch_fn(cfg, 4, 16, seed=7)
    for step in (0, 3, 100):
        a, b = f1(step), f2(step)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    assert not np.array_equal(f1(0)["tokens"], f1(1)["tokens"])


def test_pipeline_restart_replays():
    cfg = get_smoke_config("granite-8b")
    direct = make_batch_fn(cfg, 2, 8, seed=3)
    pipe = SyntheticPipeline(cfg, 2, 8, seed=3, start_step=5)
    try:
        got = next(pipe)
        np.testing.assert_array_equal(got["tokens"], direct(5)["tokens"])
    finally:
        pipe.close()


def test_vlm_encdec_batch_shapes():
    for arch in ("internvl2-1b", "whisper-small"):
        cfg = get_smoke_config(arch)
        b = make_batch_fn(cfg, 2, 16)(0)
        assert b["tokens"].shape[0] == 2
        assert ("patches" in b) == (cfg.family == "vlm")
        assert ("frames" in b) == (cfg.family == "encdec")


# ------------------------------------------------------------- checkpoint ---
def test_checkpoint_roundtrip_and_integrity(tmp_path):
    cfg = get_smoke_config("glm4-9b")
    model = build_model(cfg)
    state = init_state(model, jax.random.key(0))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(state, 1, blocking=True)
    restored, step = mgr.restore(state)
    assert step == 1
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # corruption detection
    mgr.save(state, 2, blocking=True)
    d = tmp_path / "step_00000002"
    victim = sorted(d.glob("*.npy"))[0]
    arr = np.load(victim)
    np.save(victim, arr + 1 if arr.dtype.kind in "fiu" else arr)
    with pytest.raises(IOError):
        mgr.restore(state, step=2)

    # retention
    for s in (3, 4, 5):
        mgr.save(state, s, blocking=True)
    assert mgr.steps() == [4, 5]


# ------------------------------------------------------------ accumulation --
def test_grad_accumulation_equivalence():
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    key = jax.random.key(1)
    batch = {k: jnp.asarray(v)
             for k, v in make_batch_fn(cfg, 4, 16)(0).items()}

    def run(accum):
        state = init_state(model, key)
        step = jax.jit(make_train_step(model, OptConfig(lr=1e-3),
                                       accum_steps=accum))
        state, m, _ = step(state, batch)
        return m["loss"], state["params"]

    l1, p1 = run(1)
    l2, p2 = run(2)
    assert abs(float(l1) - float(l2)) < 3e-2
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-2)


# -------------------------------------------------------------- compression --
@settings(max_examples=10, deadline=None)
@given(scale=st.floats(1e-3, 1e3), n=st.integers(4, 64))
def test_ef_compression_conservation_property(scale, n):
    """EF invariant: g_hat + residual' == g + residual exactly (f32)."""
    g = (jax.random.normal(jax.random.key(n), (n,)) * scale)
    r = (jax.random.normal(jax.random.key(n + 1), (n,)) * scale * 0.1)
    g_hat, r2 = ef_compress_leaf(g, r)
    np.testing.assert_allclose(np.asarray(g_hat + r2), np.asarray(g + r),
                               rtol=1e-6, atol=1e-6)
    # quantization error bounded by scale/2 per element
    q, s = quantize(g + r)
    assert float(jnp.max(jnp.abs(dequantize(q, s) - (g + r)))) <= float(s)


def test_ef_sgd_converges_on_quadratic():
    """EF-compressed SGD reaches the optimum of a deterministic quadratic —
    the classic error-feedback convergence guarantee."""
    A = jnp.diag(jnp.asarray([1.0, 0.5, 0.1, 2.0]))
    b = jnp.asarray([1.0, -2.0, 3.0, 0.5])
    x = jnp.zeros(4)
    r = jnp.zeros(4)
    for _ in range(400):
        g = A @ x - b
        g_hat, r = ef_compress_leaf(g, r)
        x = x - 0.3 * g_hat
    x_star = jnp.linalg.solve(A, b)
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_star),
                               rtol=1e-2, atol=1e-2)


def test_grad_compress_tracks_uncompressed():
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    batchf = make_batch_fn(cfg, 4, 16)

    def run(compress):
        state = init_state(model, jax.random.key(2), grad_compress=compress)
        step = jax.jit(make_train_step(model, OptConfig(lr=1e-3,
                                                        warmup_steps=2),
                                       grad_compress=compress))
        losses = []
        for i in range(10):
            b = {k: jnp.asarray(v) for k, v in batchf(i).items()}
            state, m, _ = step(state, b)
            losses.append(float(m["loss"]))
        return losses

    plain = run(False)
    comp = run(True)
    assert np.isfinite(comp).all()
    # int8+EF tracks the uncompressed trajectory loosely at this horizon
    assert abs(np.mean(comp[-3:]) - np.mean(plain[-3:])) < 1.0


# ------------------------------------------------------------------ resume --
def test_train_loop_checkpoint_resume(tmp_path):
    cfg = get_smoke_config("granite-8b")

    def model():
        return build_model(cfg, Runtime(taps=frozenset({"commits"})))

    lc = dict(batch=2, seq=16, checkpoint_every=4, sample_interval=2,
              checkpoint_dir=str(tmp_path))
    full = train_loop(model(), LoopConfig(steps=6, **lc), resume=False)
    # the default measured-window roofline capture rode the run
    assert full["roofline"]["windows"] == 3
    assert full["roofline"]["steps"] == 6
    assert full["roofline"]["s_per_step"] > 0
    # simulate preemption: a fresh process resumes from step 4's checkpoint
    resumed = train_loop(model(), LoopConfig(steps=6, **lc), resume=True)
    # the resumed run re-executes steps 4..5 on identical data
    np.testing.assert_allclose(resumed["losses"], full["losses"][4:],
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------- verified checkpoints ----
def test_commit_verifier_clean_oracle_publishes_checkpoints(tmp_path):
    """The verified-snapshot workflow: with a clean oracle replaying the
    same deterministic stream, every window's commit rows are accepted and
    checkpoints publish normally."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    oracle = jax.jit(make_train_step(model))
    lc = LoopConfig(steps=8, batch=2, seq=16, sample_interval=2,
                    checkpoint_every=4, checkpoint_dir=str(tmp_path))
    out = train_loop(model, lc, resume=False, oracle_step=oracle)
    assert len(out["losses"]) == 8
    assert CheckpointManager(str(tmp_path)).steps() == [4, 8]
    # on the CPU the fused engine and the per-step oracle agree closely
    assert 0.0 <= out["oracle_max_rel_err"] <= 1e-5


def test_commit_verifier_faulted_engine_blocks_checkpoint(tmp_path):
    """A diverging commit stream raises at the drain, which vetoes the
    checkpoint DrainBarrier: the save never publishes."""
    from repro.core.coemu import CommitDivergence

    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    oracle = jax.jit(make_train_step(model))
    # a faulted engine: its commit stream comes from different params than
    # the oracle replays, so the very first window's rows diverge
    bad_state = init_state(model, jax.random.key(99))
    lc = LoopConfig(steps=8, batch=2, seq=16, sample_interval=2,
                    checkpoint_every=4, checkpoint_dir=str(tmp_path))
    with pytest.raises(CommitDivergence):
        train_loop(model, lc, resume=False, oracle_step=oracle,
                   oracle_state=bad_state)
    assert CheckpointManager(str(tmp_path)).steps() == []   # save vetoed


def test_commit_verifier_survives_checkpoint_resume(tmp_path):
    """On resume the default oracle starts from the RESTORED state (not a
    fresh step-0 init), so a healthy resumed run verifies clean and keeps
    publishing checkpoints."""
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    oracle = jax.jit(make_train_step(model))
    lc = LoopConfig(steps=8, batch=2, seq=16, sample_interval=2,
                    checkpoint_every=4, checkpoint_dir=str(tmp_path))
    # first process: verified run to step 4, then "preemption"
    train_loop(model, LoopConfig(**{**lc.__dict__, "steps": 4}),
               resume=False, oracle_step=oracle)
    assert CheckpointManager(str(tmp_path)).steps() == [4]
    # fresh process resumes from step 4 with the verifier still armed
    out = train_loop(model, lc, resume=True, oracle_step=oracle)
    assert len(out["losses"]) == 4                  # steps 4..7 replayed
    assert CheckpointManager(str(tmp_path)).steps() == [4, 8]


def test_commit_verifier_vetoes_per_step_engine_too(tmp_path):
    """Both scheduler engines share the barrier semantics: the per-step
    baseline's checkpoint is equally vetoed by a diverging stream."""
    from repro.core.coemu import CommitDivergence

    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    oracle = jax.jit(make_train_step(model))
    bad_state = init_state(model, jax.random.key(99))
    lc = LoopConfig(steps=4, batch=2, seq=16, sample_interval=2,
                    checkpoint_every=4, checkpoint_dir=str(tmp_path),
                    fused=False)
    with pytest.raises(CommitDivergence):
        train_loop(model, lc, resume=False, oracle_step=oracle,
                   oracle_state=bad_state)
    assert CheckpointManager(str(tmp_path)).steps() == []
