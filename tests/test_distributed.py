"""Multi-device semantics tests. Each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest process
must keep 1 device for the smoke tests, per the assignment)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> dict:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("RESULT::" + json.dumps(out, default=float))
    """)
    env = {**os.environ,
           "PYTHONPATH": os.path.join(REPO, "src"),
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT::")][-1]
    return json.loads(line[len("RESULT::"):])


def test_moe_a2a_matches_sort():
    """shard_map all-to-all EP == local sort dispatch (same routing/caps)."""
    out = run_sub("""
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_mod
        import dataclasses
        cfg = get_smoke_config("qwen3-moe-30b-a3b")  # 8 experts
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        key = jax.random.key(0)
        p = moe_mod.init_moe(key, cfg)
        x = (jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
             .astype(jnp.bfloat16))
        y_sort, st_sort = jax.jit(
            lambda p, x: moe_mod.moe_apply(p, cfg, x, impl="sort"))(p, x)
        y_a2a, st_a2a = jax.jit(
            lambda p, x: moe_mod.moe_apply(
                p, cfg, x, impl="a2a", mesh=mesh,
                data_axes=("data",), model_axis="model"))(p, x)
        d = float(jnp.max(jnp.abs(y_sort.astype(jnp.float32)
                                  - y_a2a.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(y_sort.astype(jnp.float32)))) + 1e-6
        out = {"rel_diff": d / scale,
               "drop_sort": float(st_sort["dropped_frac"]),
               "drop_a2a": float(st_a2a["dropped_frac"])}
    """)
    assert out["drop_sort"] == 0.0 and out["drop_a2a"] == 0.0
    assert out["rel_diff"] < 3e-2, out


def test_pipeline_parallel_matches_single_stage():
    """GPipe loss AND grads == plain model (2 stages x 2 microbatches)."""
    out = run_sub("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.train.pipeline import make_pp_loss
        cfg = get_smoke_config("granite-8b")     # 2 layers, pattern len 1
        mesh = jax.make_mesh((2,), ("pipe",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        model = build_model(cfg, Runtime())
        params = model.init(jax.random.key(0))
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (4, 16), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.key(2), (4, 16), 0,
                                         cfg.vocab_size),
        }
        pp_loss = make_pp_loss(cfg, mesh, n_stages=2, n_micro=2)
        ref_loss = lambda p, b: model.loss(p, b)[0]
        l_pp, g_pp = jax.jit(jax.value_and_grad(pp_loss))(params, batch)
        l_rf, g_rf = jax.jit(jax.value_and_grad(ref_loss))(params, batch)
        gd = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))
                 for a, b in zip(jax.tree.leaves(g_pp),
                                 jax.tree.leaves(g_rf)))
        out = {"l_pp": float(l_pp), "l_ref": float(l_rf), "grad_max_diff": gd}
    """)
    assert abs(out["l_pp"] - out["l_ref"]) < 2e-2, out
    assert out["grad_max_diff"] < 6e-2, out


def test_elastic_checkpoint_restore_across_meshes():
    """Save on a (2,2) mesh, restore re-sharded onto (4,2), keep training."""
    out = run_sub("""
        import tempfile
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.train import make_train_step, init_state
        from repro.checkpoint import CheckpointManager
        from repro.sharding import param_shardings, opt_shardings, replicated
        cfg = get_smoke_config("glm4-9b")
        model = build_model(cfg)
        state = init_state(model, jax.random.key(0))
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (8, 16), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.key(2), (8, 16), 0,
                                         cfg.vocab_size),
        }
        step = jax.jit(make_train_step(model))

        def shardings_for(mesh):
            sspec = jax.eval_shape(lambda: state)
            psh = param_shardings(mesh, sspec["params"], "train")
            return {"params": psh, "opt": opt_shardings(mesh, psh),
                    "step": replicated(mesh)}

        mesh1 = jax.make_mesh((2, 2), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sh1 = shardings_for(mesh1)
        state1 = jax.tree.map(jax.device_put, state, sh1)
        state1, m1, _ = step(state1, batch)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(state1, 1, blocking=True)
            mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                                  axis_types=(jax.sharding.AxisType.Auto,) * 2)
            sh2 = shardings_for(mesh2)
            state2, got_step = mgr.restore(state1, shardings=sh2)
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(state1),
                                   jax.tree.leaves(state2)))
        resharded = any(
            l.sharding.mesh.shape.get("data") == 4
            for l in jax.tree.leaves(state2) if hasattr(l, "sharding")
            and hasattr(l.sharding, "mesh"))
        state2, m2, _ = step(state2, batch)      # still trains on new mesh
        out = {"roundtrip_exact": bool(same), "resharded": bool(resharded),
               "step_ok": float(m2["loss"]) == float(m2["loss"]),
               "got_step": got_step}
    """)
    assert out["roundtrip_exact"] and out["resharded"] and out["step_ok"]


def test_compressed_pmean_groups():
    """compressed_pmean over a real 4-way axis == f32 mean within int8 error."""
    out = run_sub("""
        from repro.train.compress import compressed_pmean
        mesh = jax.make_mesh((4,), ("dp",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.key(0), (4, 64))
        r = jnp.zeros((4, 64))
        def body(g, r):
            out, r2 = compressed_pmean(g, "dp", r)
            return out, r2
        f = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                          out_specs=(P("dp"), P("dp")), check_vma=False)
        got, resid = f(g, r)
        want = jnp.mean(g, axis=0, keepdims=True)
        err = float(jnp.max(jnp.abs(got[:1] - want)))
        bound = float(jnp.max(jnp.abs(g))) / 127.0 + 1e-6
        out = {"err": err, "bound": bound,
               "resid_nonzero": float(jnp.max(jnp.abs(resid))) > 0}
    """)
    assert out["err"] <= out["bound"], out
    assert out["resid_nonzero"]


def test_sequence_parallel_numerics():
    """seq_parallel=True is a sharding hint only: loss identical (it halves
    train-cell TP wire; see EXPERIMENTS §Perf change #5)."""
    out = run_sub("""
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.train import make_train_step, init_state
        cfg = get_smoke_config("granite-8b")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.key(2), (4, 32), 0,
                                         cfg.vocab_size),
        }
        losses = []
        for sp in (False, True):
            rt = Runtime(mesh=mesh, data_axes=("data",), seq_parallel=sp)
            model = build_model(cfg, rt)
            state = init_state(model, jax.random.key(0))
            step = jax.jit(make_train_step(model))
            state, m, _ = step(state, batch)
            losses.append(float(m["loss"]))
        out = {"l_off": losses[0], "l_on": losses[1]}
    """)
    assert abs(out["l_off"] - out["l_on"]) < 1e-3, out


def test_sharded_decode_matches_one_device():
    """Decode under a (data, model) mesh (the sequence-sharded flash-decode
    path, each scanned layer's cache slice read and written back into the
    carried stack) gives one device's logits and caches, to float32
    rounding of the psum-combined softmax: a KV cache and RG-LRU states."""
    out = run_sub("""
        import dataclasses
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.models.runtime import Runtime
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        out = {}
        for arch in ("granite-8b", "recurrentgemma-2b"):
            cfg = dataclasses.replace(get_smoke_config(arch),
                                      dtype="float32")
            plain = build_model(cfg)
            sharded = build_model(cfg, Runtime(mesh=mesh,
                                               data_axes=("data",)))
            params = plain.init(jax.random.key(0))
            toks = jax.random.randint(jax.random.key(1), (2, 12), 0,
                                      cfg.vocab_size)
            cache, logits = jax.jit(lambda p, b: plain.prefill(p, b, 32))(
                params, {"tokens": toks})
            c1 = c2 = cache
            s1 = jax.jit(plain.decode_step)
            s2 = jax.jit(sharded.decode_step)
            gap = 0.0
            for _ in range(6):
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
                c1, logits = s1(params, c1, tok)
                c2, l2 = s2(params, c2, tok)
                gap = max([gap, float(jnp.max(jnp.abs(logits - l2)))] + [
                    float(jnp.max(jnp.abs(a - b)))
                    for a, b in zip(jax.tree.leaves(c1),
                                    jax.tree.leaves(c2))])
            out[arch] = gap
    """)
    assert all(gap < 1e-4 for gap in out.values()), out


def test_dryrun_cell_small_mesh():
    """The dry-run machinery itself on an 8-device mesh (fast CI variant)."""
    out = run_sub("""
        from repro.configs import get_smoke_config, ShapeConfig
        from repro.models import build_model, input_specs
        from repro.models.runtime import Runtime
        from repro.sharding import (param_shardings, batch_shardings,
                                    opt_shardings, replicated)
        from repro.train import make_train_step, state_specs
        from repro.roofline.hlo import collective_summary
        cfg = get_smoke_config("glm4-9b")
        shape = ShapeConfig("t", 64, 8, "train")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rt = Runtime(mesh=mesh, data_axes=("data",),
                     taps=frozenset({"commits"}))
        model = build_model(cfg, rt)
        step = make_train_step(model)
        ss = state_specs(model)
        psh = param_shardings(mesh, ss["params"], "train")
        rep = replicated(mesh)
        ssh = {"params": psh, "opt": opt_shardings(mesh, psh), "step": rep}
        bs = input_specs(cfg, shape)
        bsh = batch_shardings(mesh, bs, "train")
        c = jax.jit(step, in_shardings=(ssh, bsh),
                    out_shardings=(ssh, rep, rep)).lower(ss, bs).compile()
        colls = collective_summary(c.as_text(), 8)
        out = {"eff_bytes": colls["total_effective_bytes"],
               "n_sites": colls["n_sites"]}
    """)
    assert out["n_sites"] > 0 and out["eff_bytes"] > 0


def test_train_boards_verify_on_their_own_chip():
    """Four verified train boards on four devices: each board's oracle
    (a CommitStreamVerifier built by its state factory from state and
    batches that live on device 0) replays on its DUT's device, and every
    commit is accepted."""
    out = run_sub("""
        from repro.configs import get_smoke_config
        from repro.core.coemu import CommitStreamVerifier
        from repro.farm import FarmJob, FarmManager
        from repro.launch.farm import _train_board_parts
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.train.step import init_state, make_train_step
        cfg = get_smoke_config("granite-8b")
        parts = _train_board_parts(cfg, 4, 2, batch=2, seq=16, seed=0)
        model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
        s0 = jax.jit(lambda k: init_state(model, k))(jax.random.key(0))
        oracle = jax.jit(make_train_step(model))
        batches = [jax.device_put(b) for w in parts["windows"] for b in w]
        dev = lambda tree: sorted({str(d) for leaf in jax.tree.leaves(tree)
                                   for d in leaf.devices()})
        seen = {}
        mgr = FarmManager(slots=4, evict_stragglers=False)
        for b in range(4):
            box = {}

            def state(box=box):
                box["v"] = CommitStreamVerifier(
                    oracle, s0, batches=lambda: iter(batches),
                    layers=cfg.num_layers, rtol=1e-3)
                return jax.tree.map(jnp.copy, s0)

            def verify(plan, records, ys, box=box, name=f"train{b}"):
                box["v"](plan.last, records)
                seen[name] = {"dut": dev(ys), "oracle": dev(box["v"].state)}

            mgr.submit(FarmJob(
                name=f"train{b}", engine=parts["engine"],
                windows=parts["windows"], state=state, shell=parts["shell"],
                drain_fn=parts["drain_fn"], stack_fn=parts["stack_fn"],
                verify=verify, max_requeues=0))
        report = mgr.run()
        out = {"seen": seen, "status": {n: j["status"]
                                        for n, j in report["jobs"].items()},
               "s0": dev(s0)}
    """, devices=4)
    assert set(out["status"].values()) == {"done"}, out
    assert out["s0"] == ["TFRT_CPU_0"]
    duts = [v["dut"] for v in out["seen"].values()]
    assert all(len(d) == 1 for d in duts)
    assert len({d[0] for d in duts}) == 4, out
    for v in out["seen"].values():
        assert v["oracle"] == v["dut"], out
