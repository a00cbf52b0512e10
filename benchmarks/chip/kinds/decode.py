"""Traffic kind ``decode``: decode boards back to back on one slot.

Each board copies the weights (as ``submit_decode_job``'s state factory
does), prefills a ``prompt``-token prompt of ``batch`` rows at admission
inside the window, then decodes ``gen`` greedy tokens through the cache
in windows of ``window_tokens`` (``launch/serve.py make_decode_engine``).
Prompts cycle through ``prompt_sets`` seeded sets. The check draws
``check_boards`` boards that finished inside the window from the seed
and compares every served token with the reference's logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chip import reference, weights
from chip.cells import Base
from chip.harness import Recorder, release


class Kind(Base):
    rate_metric = "decode_tokens_per_s"

    def setup(self):
        from repro.core.pshell import _reset_jitted, drain, stack_batches
        from repro.core.schedule import plan_windows
        from repro.launch.serve import make_decode_engine
        from repro.models import build_model
        from repro.models.runtime import Runtime
        from repro.serve import make_prefill_step

        mix, spec = self.mix, self.spec
        B, P = int(mix["batch"]), int(mix["prompt"])
        self.gen, g = int(mix["gen"]), int(mix["window_tokens"])
        model = build_model(self.cfg, Runtime())
        self.canon = weights.make_weights(spec, self.seed)
        self.params = weights.to_program(self.canon, model)
        rng = weights.np_rng(self.seed, "prompts")
        self.prompts = [rng.integers(0, spec.vocab, (B, P), dtype=np.int32)
                        for _ in range(int(mix["prompt_sets"]))]
        self.prefill = jax.jit(make_prefill_step(model, P + self.gen + 8))
        engine = make_decode_engine(model)
        if self.fault == "token_altered":
            engine = _token_altered(engine)
        self.engine = engine
        self.windows = [list(range(p.start, p.boundary))
                        for p in plan_windows(self.gen - 1, g)]
        self.plumbing = dict(drain_fn=drain, stack_fn=stack_batches,
                             reset=_reset_jitted())
        self.sequences = {}
        # warm every shape the window uses: one board of two windows
        self.farm(Recorder(), boards=1, windows=2).run(strict=False)

    def board(self, rec, mgr, b, windows=None):
        from jax.profiler import TraceAnnotation
        from repro.core.pshell import shell_init
        from repro.farm import FarmJob
        from repro.launch.serve import decode_shell_config
        name = f"board{b}"
        prompt = self.prompts[b % len(self.prompts)]
        params, prefill = self.params, self.prefill
        toks = self.sequences.setdefault(name, {"prompt": prompt,
                                                "toks": []})

        def state():
            with TraceAnnotation("bench.board_build"):
                w = jax.tree.map(jnp.copy, params)
            with TraceAnnotation("bench.prefill"):
                cache, logits = prefill(params, {"tokens": prompt})
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(
                    jnp.int32)[:, None]
                toks["toks"] = [np.asarray(tok)]
            return (w, cache, tok)

        def check(plan, records, ys):
            got = np.asarray(ys)[:, :, 0].T          # (B, g)
            toks["toks"].append(got)
            return got.size + (prompt.shape[0] if plan.index == 0 else 0), \
                False, None

        g = int(self.mix["window_tokens"])
        wins = self.windows[:windows] if windows else self.windows
        return FarmJob(
            name=name, engine=rec.engine(name, self.engine), windows=wins,
            state=state,
            shell=lambda: shell_init(decode_shell_config(g)),
            drain_fn=rec.drain(self.plumbing["drain_fn"]),
            stack_fn=self.plumbing["stack_fn"], reset=self.plumbing["reset"],
            verify=rec.verify(name, check), on_drain=release(mgr, name),
            max_requeues=0)

    def jobs(self, rec, mgr, boards=None, windows=None):
        self.sequences.clear()
        return [self.board(rec, mgr, b, windows)
                for b in range(boards or int(self.mix["max_boards"]))]

    def account(self, rec, report, mgr, t_start, t_end) -> dict:
        out = super().account(rec, report, mgr, t_start, t_end)
        done = {r.job for r in self.rows
                if r.index == len(self.windows) - 1}
        self.done = sorted(done, key=lambda n: int(n[5:]))
        B = int(self.mix["batch"])
        bad = {n for n, j in report["jobs"].items()
               if j["status"] in ("failed", "quarantined")}
        out["attempted"] = B * (len(done) + len(bad))
        out["failed"] = B * len(bad)
        return out

    def release(self):
        self.params = None

    def sample(self):
        """Finished sequences drawn from the seed: ``check_boards``
        finished boards, every sequence of each."""
        rng = weights.np_rng(self.seed, "decode_sample")
        k = min(int(self.mix["check_boards"]), len(self.done))
        names = sorted(rng.choice(self.done, size=k, replace=False).tolist())
        out = []
        for n in names:
            s = self.sequences[n]
            served = np.concatenate(s["toks"], axis=1)     # (B, gen)
            for i in range(served.shape[0]):
                out.append((s["prompt"][i], served[i]))
        return out

    def gaps(self, seqs, quant=None):
        """Per sequence, the gap by which each served token's reference
        logit lies below the reference's best (``quant=None``); with a
        control, the reference gap of the token the control puts first."""
        P = int(self.mix["prompt"])
        out = []
        with jax.default_matmul_precision("highest"):
            for prompt, served in seqs:
                toks = jnp.asarray(np.concatenate([prompt, served])[None])
                ref = np.asarray(reference.next_token_logits(
                    self.canon, toks, spec=self.spec, start=P - 1),
                    np.float64)[0]                            # (gen, V)
                if quant is None:
                    pick = served
                else:
                    ctl = reference.next_token_logits(
                        self.canon, toks, spec=self.spec, start=P - 1,
                        quant=quant)
                    pick = np.asarray(jnp.argmax(ctl[0], axis=-1))
                best = ref.max(axis=-1)
                out.append(float(np.max(best - ref[np.arange(len(pick)),
                                                   pick])))
        return out

    def check(self, rec) -> dict:
        """``None`` (not correct) when no board finished in the window."""
        return {"logit_gap": max(self.gaps(self.sample()), default=None)}

    def control(self) -> dict:
        return {"logit_gap": max(self.gaps(self.sample(), quant="fp8"),
                                 default=None)}


def _token_altered(engine):
    """A decode window whose tokens are changed where they are produced."""
    def run(state, shell, stack):
        state, shell, toks = engine(state, shell, stack)
        return state, shell, (toks + 1) % 7
    return run
