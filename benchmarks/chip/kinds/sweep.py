"""Traffic kind ``sweep``: the Scale-Down layer sweep.

``core/coemu.py subsystem_boards`` captures ``batches`` seeded activation
batches in situ and extracts one verify board per layer; each board
replays its layer over the batches ``passes`` times in windows of
``window_steps`` and compares each window's checksums with the capture
(``verify_rtol``). Boards are resubmitted round after round on ``slots``
virtual slots. The reference runs the layer chain from the same seeded
activations, and every counted window's checksums are compared with it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip import reference, weights
from chip.cells import Base, rel_gap
from chip.harness import Recorder, release


class Kind(Base):
    rate_metric = "subsys_steps_per_s"

    def setup(self):
        from repro.core.coemu import subsystem_boards
        from repro.models import build_model
        from repro.models.runtime import Runtime

        mix, spec = self.mix, self.spec
        B, S, N = int(mix["batch"]), int(mix["seq"]), int(mix["batches"])
        model = build_model(self.cfg, Runtime())
        self.canon = weights.make_weights(spec, self.seed)
        params = weights.to_program(self.canon, model)
        key = weights.sub_key(weights.seed_key(self.seed), "activations")
        self.xs = jax.jit(lambda k: jax.random.normal(
            k, (N, B, S, spec.d_model), jnp.float32).astype(spec.dtype))(key)
        self.positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None],
                                  (B, 1))
        xs = [self.xs[i] for i in range(N)]
        self.boards = subsystem_boards(params, self.cfg, Runtime(), xs,
                                       self.positions,
                                       list(range(spec.layers)))
        if self.fault == "answer_altered":
            self.boards = [(_altered(e), *rest) for e, *rest in self.boards]
        # warm every shape the window uses: one short pass of every board
        self.farm(Recorder(), rounds=1, passes=1).run(strict=False)

    def jobs(self, rec, mgr, rounds=None, passes=None):
        from repro.core.coemu import _stack_on_device
        from repro.farm import FarmJob
        g, N = int(self.mix["window_steps"]), int(self.mix["batches"])
        rounds = rounds or int(self.mix["rounds"])
        passes = passes or int(self.mix["passes"])
        rtol = float(self.mix["verify_rtol"])
        idx = [[(w * g + i) % N for i in range(g)]
               for w in range(passes * N // g)]
        jobs = []
        for r in range(rounds):
            for li, (engine, state, x_ins, ocks, _) in enumerate(self.boards):
                name = f"layer{li}.r{r}"

                def check(plan, records, ys, li=li, ocks=ocks):
                    got = np.asarray(ys, np.float64)
                    rows = idx[plan.index]
                    bad = bool(np.any(rel_gap(got, ocks[rows]) > rtol))
                    return plan.size, bad, (li, rows, got)

                jobs.append(FarmJob(
                    name=name, engine=rec.engine(name, engine), state=state,
                    windows=[[x_ins[i] for i in w] for w in idx], shell={},
                    stack_fn=_stack_on_device, verify=rec.verify(name, check),
                    on_drain=release(mgr, name), max_requeues=0))
        return jobs

    def release(self):
        self.boards = None

    def check(self, rec) -> dict:
        ref = self.reference(None)
        worst = None
        for r in self.rows:
            if r.payload is None:
                continue
            li, rows, got = r.payload
            gap = float(np.max(rel_gap(got, ref[rows, li])))
            worst = gap if worst is None else max(worst, gap)
        return {"replay_rel": worst}

    def reference(self, quant):
        with jax.default_matmul_precision("highest"):
            out = reference.layer_checksums(
                self.canon, self.xs, self.positions, spec=self.spec,
                act_dtype=jnp.dtype(self.spec.dtype), quant=quant)
        return np.asarray(out, np.float64)

    def control(self) -> dict:
        ref = self.reference(None)
        return {"replay_rel": float(np.max(rel_gap(self.reference("fp8"),
                                                   ref)))}


def _altered(engine):
    """A subsystem window whose answer is changed where it is produced."""
    def run(state, shell, stack):
        state, shell, ys = engine(state, shell, stack)
        return state, shell, ys * 1.01
    return run
