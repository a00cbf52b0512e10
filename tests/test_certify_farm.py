"""ZP-Cert farm integration: the admission gate dead-letters an
uncertifiable board with a durable ``certify_fail`` record while
co-submitted healthy jobs finish bit-identical to an uncertified oracle;
registry duplicate protection; JobSpec kwargs validation; every shipped
smoke arch certifies clean."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS
from repro.farm import FarmJob, FarmManager
from repro.farm.registry import FactoryRegistry, JobSpec

jax.config.update("jax_platform_name", "cpu")


# ------------------------------------------------------- admission gate --
def _poison_job():
    def engine(state, shell, stack):
        host = jax.pure_callback(
            lambda x: np.asarray(x),
            jax.ShapeDtypeStruct((), jnp.float32), state)
        return state + host, shell, stack * 2.0

    return FarmJob(name="poison", engine=engine,
                   windows=[[np.float32(i)] for i in range(4)],
                   state=jnp.float32(0), shell={},
                   stack_fn=lambda it: jnp.asarray(np.stack(it)))


def _healthy_job(name="healthy", n=6):
    @jax.jit
    def _body(state, stack):
        return state + jnp.sum(stack), stack * 2.0

    def engine(state, shell, stack):
        s, ys = _body(state, stack)
        return s, shell, ys

    outs = []
    job = FarmJob(name=name, engine=engine,
                  windows=[[np.float32(i)] for i in range(n)],
                  state=jnp.float32(0), shell={},
                  stack_fn=lambda it: jnp.asarray(np.stack(it)),
                  on_drain=lambda p, r, y: outs.append(np.asarray(y)))
    return job, outs


@pytest.mark.parametrize("slots", [pytest.param(2, id="async"),
                                   pytest.param(1, id="one-slot")])
def test_certify_gate_dead_letters_poison_board(slots):
    mgr = FarmManager(slots=slots, evict_stragglers=False,
                      poll_s=0.01, certify=True)
    job, outs = _healthy_job()
    mgr.submit(job)
    poison = mgr.submit(_poison_job())
    # dead-lettered AT SUBMIT: quarantined, never queued, rule named
    assert poison.status == "quarantined"
    assert "ZC101" in poison.error
    assert all(j.name != "poison" for j in mgr.queue)

    report = mgr.run(strict=False)
    assert report["jobs"]["healthy"]["status"] == "done"
    assert report["jobs"]["poison"]["status"] == "quarantined"
    certs = report["telemetry"]["certifications"]
    assert any(c["job"] == "poison" and not c["ok"]
               and "ZC101" in c["rules"] for c in certs)
    assert any(q["job"] == "poison"
               for q in report["telemetry"]["quarantined"])

    # the healthy board's stream is bit-identical to an uncertified run
    oracle_mgr = FarmManager(slots=slots, evict_stragglers=False,
                             poll_s=0.01)
    ojob, oouts = _healthy_job()
    oracle_mgr.submit(ojob)
    oracle_mgr.run(strict=False)
    assert len(outs) == len(oouts) > 0
    for a, b in zip(outs, oouts):
        np.testing.assert_array_equal(a, b)


def test_certify_gate_journals_certify_fail(tmp_path):
    from repro.farm import FarmLedger
    ledger = FarmLedger(str(tmp_path))
    mgr = FarmManager(slots=2, evict_stragglers=False,
                      ledger=ledger, certify=True)
    mgr.submit(_poison_job())
    recs = [r for r in ledger.records() if r["kind"] == "certify_fail"]
    assert len(recs) == 1
    assert recs[0]["job"] == "poison" and recs[0]["rules"] == ["ZC101"]
    # no submit record: the job never entered the durable queue
    assert not any(r["kind"] == "submit" and r["job"] == "poison"
                   for r in ledger.records())
    # replaying the journal shows the job terminally quarantined
    assert ledger.replay().jobs["poison"].status == "quarantined"
    ledger.close()


def test_certify_off_by_default():
    mgr = FarmManager(slots=2, evict_stragglers=False)
    poison = mgr.submit(_poison_job())
    assert poison.status == "queued"    # uncertified farms behave as before


def test_certify_smoke_gate(tmp_path):
    from repro.launch.farm import run_certify_smoke
    out = run_certify_smoke(work_dir=str(tmp_path), n_boards=2,
                            n_windows=4)
    assert out["ok"], out["problems"]


# ----------------------------------------------------- registry guards --
def test_registry_duplicate_name_raises():
    reg = FactoryRegistry()

    def board_a():
        return {"engine": lambda s, sh, st: (s, sh, st)}

    def board_b():
        return {"engine": lambda s, sh, st: (s, sh, st)}

    reg.register("zp.test_board", board_a)
    reg.register("zp.test_board", board_a)      # same fn: idempotent
    with pytest.raises(ValueError, match="already registered"):
        reg.register("zp.test_board", board_b)
    reg.register("zp.test_board", board_b, override=True)
    assert reg.get("zp.test_board") is board_b


def test_registry_duplicate_decorator_form():
    reg = FactoryRegistry()

    @reg.register("zp.deco_board")
    def board_a():
        return {}

    with pytest.raises(ValueError, match="override=True"):
        @reg.register("zp.deco_board")
        def board_b():
            return {}


# ------------------------------------------------- JobSpec validation --
def test_jobspec_rejects_non_json_kwarg_naming_key():
    with pytest.raises(ValueError, match=r"kwargs\['weights'\]"):
        JobSpec(name="j", factory="zp.train_board",
                kwargs={"steps": 2, "weights": jnp.zeros((2,))})
    with pytest.raises(ValueError, match=r"kwargs\['fn'\]"):
        JobSpec(name="j", factory="zp.train_board",
                kwargs={"fn": lambda: None})


def test_jobspec_rejects_non_dict_kwargs():
    with pytest.raises(TypeError, match="must be a dict"):
        JobSpec(name="j", factory="f", kwargs=[("a", 1)])


def test_jobspec_accepts_json_kwargs():
    spec = JobSpec(name="j", factory="f",
                   kwargs={"arch": "granite-8b", "steps": 2,
                           "nested": {"a": [1, 2.5, None, True]}})
    assert spec.to_json()["kwargs"]["nested"]["a"] == [1, 2.5, None, True]


# ------------------------------------------- shipped boards stay clean --
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_every_smoke_arch_certifies_clean(arch):
    import repro.launch.farm  # noqa: F401 — registers the factories
    from repro.analysis.boardcheck import certify_spec
    r = certify_spec(JobSpec(
        name=f"cert:{arch}", factory="zp.train_board",
        kwargs={"arch": arch, "steps": 2, "interval": 2}))
    assert r.errors == [], r.summary()


def test_shipped_factories_certify_clean_trace_only():
    import repro.launch.farm  # noqa: F401
    from repro.analysis.boardcheck import certify_job, no_dispatch_guard
    from repro.farm.registry import REGISTRY
    job = JobSpec(name="cert:ledger", factory="zp.ledger_board",
                  kwargs={"n_windows": 4}).build(REGISTRY)
    with no_dispatch_guard():       # the ENGINE certification is trace-only
        assert certify_job(job).ok
