"""Serving CLI: batched prefill + decode, driven through the core
WindowScheduler — the proof that the overlapped-drain harness is
workload-agnostic, not a training-loop special case.

Decode runs as scan-fused windows of ``sample_interval`` autoregressive
steps: ONE jit dispatch per window (donated cache), with a decode FIFO in
the P-Shell carrying per-token telemetry ([step, mean token id, max
logit]), a ``tokens`` CSR counting emissions and a ``moe_routing`` CSR
counting the experts' routing (decode steps, token-expert pairs, distinct
experts touched summed over layer-steps, the largest group). The scheduler
double-buffers the shell so the host drain of window *i* — where the
blocking token fetch and the per-window decode-latency sample land —
overlaps window *i+1*'s in-flight decode.

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \\
      --batch 4 --prompt-len 32 --gen 16 --sample-interval 4
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import Watchdog, WindowScheduler
from repro.core.pshell import (FifoSpec, ShellConfig, csr_accum, csr_read,
                               csr_write, drain, fifo_push, shell_init)
from repro.data.pipeline import make_batch_fn
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.roofline.capture import WindowCapture
from repro.serve import make_prefill_step
from repro.utils import enable_compile_cache


def decode_shell_config(sample_interval: int) -> ShellConfig:
    """Decode-telemetry shell: one FIFO row per generated token (depth one
    clock-gated window — lossless at any interval), a token counter and
    the routing counter (:func:`count_routing`)."""
    return ShellConfig(
        csrs={"tokens": jax.ShapeDtypeStruct((), jnp.int32),
              "moe_routing": jax.ShapeDtypeStruct((4,), jnp.int32)},
        fifos={"decode": FifoSpec(depth=max(1, sample_interval), shape=(3,),
                                  dtype=jnp.float32)},
        sample_interval=sample_interval)


def count_routing(shell, routing):
    """Add one decode step's routing (``Model.decode_step_routed``) to the
    ``moe_routing`` CSR: [steps, pairs, experts touched, largest group],
    the first three summed, the last the largest seen."""
    cur = csr_read(shell, "moe_routing")
    step = jnp.concatenate([jnp.ones((1,), jnp.int32), routing[:2]])
    return csr_write(shell, "moe_routing", jnp.concatenate(
        [cur[:3] + step, jnp.maximum(cur[3:], routing[2:])]))


#: Compiler options of the decode window on a TPU. XLA's memory-space
#: assignment can hold a weight stack that the layer scan reads one layer
#: at a time in fast memory across the window, then evict it and fetch it
#: back every layer step: two copies of the whole stack for a read of one
#: layer, in a window bound by memory bandwidth. A ratio of 1 refuses any
#: such placement whose copies move more bytes than its uses read.
TPU_DECODE_OPTIONS = {"xla_tpu_msa_inefficient_use_to_copy_ratio": 1.0}


def make_decode_engine(model):
    """Scheduler engine for decode: state=(params, cache, last_token);
    scans one decode step per window slot, pushing telemetry into the
    shell. The weights ride in the state, so placing a board's state on
    its slot's device places its weights there too. Donates the state
    ONLY — the shell snapshot must survive on the host until its
    overlapped drain — so the weights pass through each window in place
    instead of being copied to a new output buffer."""
    def engine(state, shell, idx_stack):
        params = state[0]

        def body(carry, idx):
            cache, tok, sh = carry
            cache, logits, routing = model.decode_step_routed(params, cache,
                                                              tok)
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
            payload = jnp.stack([idx.astype(jnp.float32),
                                 jnp.mean(tok.astype(jnp.float32)),
                                 jnp.max(logits).astype(jnp.float32)])
            sh = fifo_push(sh, "decode", payload)
            sh = csr_accum(sh, "tokens", jnp.int32(tok.shape[0]), op="add")
            sh = count_routing(sh, routing)
            return (cache, tok, sh), tok

        (cache, tok, shell), toks = jax.lax.scan(
            body, (state[1], state[2], shell), idx_stack)
        return (params, cache, tok), shell, toks

    return jax.jit(engine, donate_argnums=(0,), compiler_options=(
        TPU_DECODE_OPTIONS if jax.default_backend() == "tpu" else None))


def serve(cfg, batch: int, prompt_len: int, gen: int, seed: int = 0,
          sample_interval: int = 4, scope=None):
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(seed))
    bf = make_batch_fn(cfg, batch, prompt_len, seed)
    b = {k: jnp.asarray(v) for k, v in bf(0).items() if k != "labels"}
    max_len = prompt_len + (cfg.num_patches if cfg.family == "vlm" else 0) \
        + gen + 8
    prefill = jax.jit(make_prefill_step(model, max_len))
    wd = Watchdog(timeout_s=120.0)

    t0 = time.perf_counter()
    cache, logits = prefill(params, b)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    t1 = time.perf_counter()

    # measured-window roofline capture rides the decode loop by default;
    # attach_engine makes the loop's own first compile the HLO cost source
    capture = WindowCapture()
    engine = capture.attach_engine(make_decode_engine(model))
    # reset defaults to the cached jitted group_reset (P-Shell drain_fn)
    sched = WindowScheduler(interval=max(1, sample_interval), overlap=True,
                            drain_fn=drain)
    sh = shell_init(decode_shell_config(sample_interval))

    out_tokens = [np.asarray(tok)]
    dispatch_t: dict = {}
    window_ms: list = []
    fifo_rows = 0

    def on_dispatch(plan, state):
        dispatch_t[plan.index] = time.perf_counter()
        wd.heartbeat()

    def on_drain(plan, records, toks):
        nonlocal fifo_rows
        out_tokens.append(np.asarray(toks)[:, :, 0].T)  # blocking fetch
        # dispatch-to-drain PIPELINED latency: the drain of window i runs
        # after window i+1's dispatch, so this includes the overlapped
        # host-side assembly of the next window — "time until window i's
        # tokens were in hand", not pure device decode time
        window_ms.append((time.perf_counter() - dispatch_t[plan.index])
                         * 1e3)
        fifo_rows += records["fifos"]["decode"]["count"]

    scope_plane = None
    if scope is not None:
        from repro.core.scope import as_plane
        scope_plane = as_plane(scope)
        capture.attach_scope(scope_plane)
    od, odr = capture.callbacks(on_dispatch=on_dispatch, on_drain=on_drain)
    (_, cache, tok), _, sh = sched.run(
        engine, sched.windows(range(gen - 1)), (params, cache, tok), sh,
        on_dispatch=od, on_drain=odr, scope=scope_plane)
    t2 = time.perf_counter()
    toks = np.concatenate(out_tokens, axis=1)
    out_scope = ({} if scope_plane is None
                 else {"scope": scope_plane.report()})
    return {
        **out_scope,
        "prefill_s": t1 - t0,
        "decode_s": t2 - t1,
        "decode_tok_per_s": batch * (gen - 1) / max(t2 - t1, 1e-9),
        "decode_window_ms": [round(x, 2) for x in window_ms],
        "decode_fifo_rows": fifo_rows,
        "generated": toks[:, :8].tolist(),
        "hung": wd.should_restart(),
        "roofline": capture.report(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="glm4-9b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sample-interval", type=int, default=4)
    ap.add_argument("--scope", type=int, default=0, metavar="N",
                    help="enable the ZP-Scope instrumentation plane with "
                         "a read rate of every N window drains")
    ap.add_argument("--save-measured", action="store_true",
                    help="persist the run's measured-window roofline "
                         "record for repro.roofline.report")
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    scope = None
    if args.scope > 0:
        from repro.core.scope import ScopeSpec
        scope = ScopeSpec(every_n_windows=args.scope)
    out = serve(cfg, args.batch, args.prompt_len, args.gen,
                sample_interval=args.sample_interval, scope=scope)
    if args.save_measured:
        from repro.roofline import save_measured
        save_measured(out["roofline"], cfg.name, "serve")
    print(json.dumps(out, indent=1, default=float))


if __name__ == "__main__":
    main()
