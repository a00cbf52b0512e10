"""Traffic kind ``train``: verified train campaigns back to back on one
slot.

Each campaign is one farm board built from the program's own parts
(``launch/farm.py _train_board_parts``): the fused train window, which
donates its state, fed ``window_steps`` seeded batches per window, with
a ``CommitStreamVerifier`` replaying every step through
``jax.jit(make_train_step(model))`` from the oracle's own state. Every
campaign starts from the seed's weights on a fresh state and verifier
and trains ``board_windows`` windows over the same cycle of ``batches``
distinct batches, so every seed gives the same work.

What is compared with the reference comes from the timed window itself:
the loss of every step of each campaign's first ``check_windows``
windows drained inside it (recorded in the verify hook), and, read from
each campaign's own state as its window returns it, the leaf norms of
the first moment after its first window and of the parameters' change
after ``check_windows`` windows.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from chip import reference, weights
from chip.cells import Base, rel_gap
from chip.harness import HarnessError, Recorder


def train_batches(spec, mix, seed):
    """``mix['batches']`` distinct batches (host numpy): text tokens and
    next-token labels drawn uniformly over the vocabulary, and patch
    embeddings in the configuration's dtype for a VLM."""
    import ml_dtypes
    rng = weights.np_rng(seed, "train_batches")
    B, S = int(mix["batch"]), int(mix["seq"])
    T = S - spec.patches
    out = []
    for _ in range(int(mix["batches"])):
        toks = rng.integers(0, spec.vocab, (B, T + 1), dtype=np.int32)
        b = {"tokens": toks[:, :-1].copy(), "labels": toks[:, 1:].copy()}
        if spec.patches:
            b["patches"] = rng.standard_normal(
                (B, spec.patches, spec.patch_dim), np.float32).astype(
                    getattr(ml_dtypes, spec.dtype))
        out.append(b)
    return out


class Kind(Base):
    rate_metric = "train_steps_per_s"

    def setup(self):
        from repro.core.coemu import CommitStreamVerifier
        from repro.core.pshell import _reset_jitted, drain, stack_batches
        from repro.launch.farm import _train_board_parts
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.train.optim import OptConfig, adamw_init
        from repro.train.step import make_train_step

        mix, spec, cfg = self.mix, self.spec, self.cfg
        opt = self.cell.config["optimizer"]
        if OptConfig(**opt) != OptConfig():
            raise HarnessError("the train board runs OptConfig(); the "
                               f"configuration states {opt}")
        g, B, S = int(mix["window_steps"]), int(mix["batch"]), int(mix["seq"])
        parts = _train_board_parts(cfg, g, g, batch=B, seq=S, seed=0)
        model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
        self.batches = train_batches(spec, mix, self.seed)
        self.canon = weights.make_weights(spec, self.seed)
        p0 = weights.to_program(self.canon, model)
        dev_batches = [jax.device_put(b) for b in self.batches]
        oracle = jax.jit(make_train_step(model))
        rtol = float(mix["commit_rtol"])

        def fresh():
            """A campaign's start: the DUT's own train state (its engine
            donates it) and a verifier replaying from the oracle's own
            state, both from the seed's weights."""
            dut = {"params": jax.tree.map(jnp.copy, p0),
                   "opt": adamw_init(p0), "step": jnp.zeros((), jnp.int32)}
            orc = {"params": p0, "opt": adamw_init(p0),
                   "step": jnp.zeros((), jnp.int32)}
            return dut, CommitStreamVerifier(
                oracle, orc, batches=lambda: itertools.cycle(dev_batches),
                layers=cfg.num_layers, rtol=rtol)

        self.fresh, self.shell0 = fresh, parts["shell"]
        engine = parts["engine"]
        if self.fault == "state_unchanged":
            engine = _frozen(engine)
        self.engine, self.drain = engine, drain
        self.stack, self.reset = stack_batches, _reset_jitted()
        self.first = int(mix["check_windows"])
        self.labels = weights.leaf_labels(self.canon)
        # read from a campaign's state as its window returns it, before
        # the next window takes (donates) that state
        self.tap_m = jax.jit(lambda s: weights.norms(
            weights.from_program(s["opt"]["m"])))
        self.tap_d = jax.jit(lambda s, c: weights.norms(
            weights.from_program(s["params"]), c))
        self.taps = {"m": {}, "d": {}}
        self.max_rel_err = 0.0
        # warm every shape the window uses: one campaign's first windows
        # through a farm of its own
        self.farm(Recorder(), boards=1, windows=self.first).run(strict=False)
        self.taps = {"m": {}, "d": {}}

    def tapped(self, name, engine):
        calls = itertools.count()
        tap_m, tap_d, canon, taps = (self.tap_m, self.tap_d, self.canon,
                                     self.taps)
        last = self.first - 1

        def run(state, shell, stack):
            new, snap, ys = engine(state, shell, stack)
            i = next(calls)
            if i == 0:
                taps["m"][name] = tap_m(new)
            if i == last:
                taps["d"][name] = tap_d(new, canon)
            return new, snap, ys
        return run

    def jobs(self, rec, mgr, boards=None, windows=None):
        """Verified campaigns of ``board_windows`` windows back to back,
        each from the seed's weights on its own fresh state and
        verifier."""
        from repro.farm import FarmJob
        g, n = int(self.mix["window_steps"]), len(self.batches)
        W = windows or int(self.mix["board_windows"])
        wins = [[self.batches[(w * g + i) % n] for i in range(g)]
                for w in range(W)]
        verifiers = {}
        kind, first = self, self.first

        def board(b):
            name = f"train{b}"

            def state():
                dut, verifiers[name] = kind.fresh()
                return dut

            def check(plan, records, ys):
                v = verifiers[name]
                try:
                    v(plan, records)
                finally:
                    kind.max_rel_err = max(kind.max_rel_err, v.max_rel_err)
                return plan.size, False, (ys["loss"] if plan.index < first
                                          else None)

            def done(plan, records, ys):
                verifiers.pop(name, None)
                mgr.results.pop(name, None)
                mgr.outputs.pop(name, None)

            return FarmJob(
                name=name,
                engine=rec.engine(name, self.tapped(name, self.engine)),
                windows=wins, state=state, shell=self.shell0,
                drain_fn=rec.drain(self.drain), stack_fn=self.stack,
                reset=self.reset, verify=rec.verify(name, check),
                on_drain=done, max_requeues=0)

        return [board(b)
                for b in range(boards or int(self.mix["max_boards"]))]

    def detail(self) -> dict:
        return {"commit_max_rel_err": self.max_rel_err,
                "campaigns_compared": getattr(self, "compared", None),
                "leaves_left_out": getattr(self, "left_out", None)}

    def release(self):
        self.fresh = None

    def observed(self) -> dict:
        """What the counted windows produced: per step, the losses of every
        campaign; per campaign, the tapped norms whose window counted."""
        g = int(self.mix["window_steps"])
        counted = {(r.job, r.index) for r in self.rows if not r.failed}
        losses = {}
        for r in self.rows:
            if r.payload is None:
                continue
            for i, lv in enumerate(np.asarray(r.payload, np.float64)):
                losses.setdefault(r.index * g + i, []).append(float(lv))
        m = [np.asarray(v, np.float64) for k, v in self.taps["m"].items()
             if (k, 0) in counted]
        d = [np.asarray(v, np.float64) for k, v in self.taps["d"].items()
             if (k, self.first - 1) in counted]
        self.compared = len(d)
        return {"losses": losses, "m_norms": m, "delta": d}

    def check(self, rec) -> dict:
        return self.compare(self.ref(), self.observed())

    def ref(self) -> dict:
        if getattr(self, "_ref", None) is None:
            self._ref = self.reference(None)
        return self._ref

    def reference(self, quant):
        """Losses of the first ``check_windows`` windows' steps, the first
        moment after the first window, and the parameter change after
        ``check_windows`` windows, from the reference (``quant=None``) or
        the control."""
        g = int(self.mix["window_steps"])
        opt = reference.Opt(**self.cell.config["optimizer"])
        w0 = self.canon
        w, m, v = w0, *[jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), w0)] * 2
        losses = []
        with jax.default_matmul_precision("highest"):
            for step in range(self.first * g):
                batch = jax.device_put(self.batches[step % len(self.batches)])
                lv, w, m, v = reference.train_step(
                    w, m, v, jnp.int32(step), batch, spec=self.spec, opt=opt,
                    quant=quant)
                losses.append(float(lv))
                if step == g - 1:
                    m_norms = weights.leaf_norms(m)
        delta = weights.leaf_norms(w, minus=w0)
        return {"losses": losses, "m_norms": m_norms, "delta": delta}

    def compare(self, ref, prog) -> dict:
        """The worst gap of each compared number over the campaigns; a
        number with nothing to compare is ``None`` (not correct)."""
        keep = ref["m_norms"] >= 1e-3 * np.median(ref["m_norms"])
        self.left_out = [n for n, k in zip(self.labels, keep) if not k]
        gaps = [float(np.max(rel_gap(v, ref["losses"][s])))
                for s, v in prog["losses"].items()]
        out = {"loss_rel": max(gaps) if gaps else None}
        for key, name in (("m_norms", "grad_leaf_gap"),
                          ("delta", "step_leaf_gap")):
            r = ref[key][keep]
            gaps = [float(np.max(rel_gap(p[keep], r, floor=np.median(r))))
                    for p in prog[key]]
            out[name] = max(gaps) if gaps else None
        return out

    def control(self) -> dict:
        ctl = self.reference("fp8")
        prog = {"losses": {s: [lv] for s, lv in enumerate(ctl["losses"])},
                "m_norms": [ctl["m_norms"]], "delta": [ctl["delta"]]}
        return self.compare(self.ref(), prog)


def _frozen(engine):
    """A train window that returns the state it was given (a fault)."""
    def run(state, shell, stack):
        new, shell, ys = engine(jax.tree.map(jnp.copy, state), shell, stack)
        return state, shell, ys
    return run
