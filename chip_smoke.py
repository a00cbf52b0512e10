"""Bring-up check: the co-emulation farm's main path on a TPU chip.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # one board per chip on a 4-chip host

One chip runs three phases, one after another in this one process:

* ``kernels`` — each Pallas kernel compiled (not interpreted) once at a
  published model width, checked against its ``ref.py``;
* ``farm`` — ``repro.launch.farm.run_farm`` on internvl2-1b at its
  published config (24 layers, d_model 896, vocab 151655): a fused train
  board (8 steps, batch 2 x seq 512), a scan-fused decode board (32
  tokens after a 512-position prompt) and two subsystem verify boards
  (layers 0 and 1), async, one slot;
* ``donation`` — ``train_loop`` with a commit-stream oracle attached, its
  fused engine donating the train state on the device.

``--chips 4`` runs only ``multi_chip``: the same four boards on four
slots, one per chip, against the same jobs on one slot on chip 0; the
delivered outputs must be bit-identical and every board's carry must end
on its own slot's device.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``. With no TPU the script exits non-zero
and prints no result. Weights and data are random, made from seed 0.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

ARCH = "internvl2-1b"
FARM_SHAPES = dict(steps=8, gen=32, batch=2, seq=512, prompt_len=512,
                   verify_seq=512, interval=2)
# the train_loop phase holds the DUT's train state and the oracle's own
# copy at once: 2 x 5.9 GiB at 24 layers, plus the window's temporaries,
# exceeds a v5e's 16 GB, so that phase keeps the widths and cuts depth
DONATION_LAYERS = 6
DONATION_SHAPES = dict(steps=8, batch=1, seq=512, sample_interval=2)
MULTI_CHIP_SHAPES = dict(steps=4, gen=8, batch=2, seq=512, prompt_len=512,
                         verify_seq=512, interval=2)


# --------------------------------------------------------------- kernels --
def kernel_phase(small: bool = False, interpret: bool = False) -> dict:
    """Run each kernel of ``repro.kernels.cases`` once at its published
    width and compare it with its reference at ``highest`` matmul
    precision. ``ok`` needs every output within ``atol = rtol = tol``."""
    from repro.kernels.cases import kernel_cases

    rows = {}
    key = jax.random.key(0)
    for name, case in kernel_cases(small).items():
        key, sub = jax.random.split(key)
        args = case.inputs(sub)
        t0 = time.perf_counter()
        got = jax.block_until_ready(case.call(*args, interpret=interpret))
        seconds = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = case.ref(*args)
        err = 0.0
        close = True
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            err = max(err, float(np.max(np.abs(g - w))))
            close &= bool(np.all(np.isfinite(g))
                          and np.allclose(g, w, rtol=case.tol,
                                          atol=case.tol))
        rows[name] = {"max_abs_err": err, "tol": case.tol, "ok": close,
                      "first_call_s": seconds}
    return {"phase": "kernels", "interpret": interpret, "kernels": rows,
            "ok": all(r["ok"] for r in rows.values())}


# ------------------------------------------------------------------ farm --
def farm_ok(out: dict, shapes: dict) -> bool:
    """Every job done, no verify board diverged, every train loss finite,
    and the decode board delivered all its tokens."""
    return bool(out["ok"]
                and all(j["status"] == "done" for j in out["jobs"].values())
                and out["train"]["steps"] == shapes["steps"]
                and out["train"]["all_finite"]
                and out["decode"]["tokens"]
                == shapes["batch"] * shapes["gen"])


def farm_phase(cfg, slots, shapes: dict) -> dict:
    from repro.launch.farm import run_farm

    t0 = time.perf_counter()
    out = run_farm(cfg, slots, **shapes)
    return {
        "phase": "farm", "arch": cfg.name, **shapes,
        "setup_s": out["setup_s"], "prewarm_s": out["prewarm_s"],
        "run_s": out["run_s"], "wall_s": time.perf_counter() - t0,
        "jobs": {n: j["status"] for n, j in out["jobs"].items()},
        "loss_first": out["train"]["loss_first"],
        "loss_last": out["train"]["loss_last"],
        "tokens_decoded": out["decode"]["tokens"],
        "verify_max_rel_err": {k: v["max_rel_err"]
                               for k, v in out["verify"].items()},
        "peak_bytes_in_use": peak_bytes(),
        "ok": farm_ok(out, shapes),
    }


# -------------------------------------------------------------- donation --
def donation_phase(cfg, shapes: dict) -> dict:
    """``train_loop`` with its own per-step oracle attached: the fused
    engine donates the train state on the device, and the oracle, a
    separately compiled one-step program, replays every step from its own
    copy of the start state. A commit row further than ``train_loop``'s
    default ``oracle_rtol`` from the oracle's raises at the drain."""
    from repro.core.commit import default_shell_config, make_ingest
    from repro.core.pshell import PShell, stack_batches
    from repro.data.pipeline import make_batch_fn
    from repro.models import build_model
    from repro.models.runtime import Runtime
    from repro.train.loop import LoopConfig, train_loop
    from repro.train.step import init_state, make_group_step, make_train_step

    model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
    interval = shapes["sample_interval"]

    # the engine train_loop builds must really donate on this backend
    ingest = make_ingest(cfg)
    shell = PShell(default_shell_config(cfg, sample_interval=interval),
                   ingest)
    engine = shell.compile_group(make_group_step(model, ingest=ingest))
    state = jax.jit(lambda k: init_state(model, k))(jax.random.key(0))
    bf = make_batch_fn(cfg, shapes["batch"], shapes["seq"], 0)
    out = engine(state, shell.init(),
                 stack_batches([bf(i) for i in range(interval)]))
    jax.block_until_ready(out)
    donated = all(x.is_deleted() for x in jax.tree.leaves(state))
    del state, out, engine

    t0 = time.perf_counter()
    res = train_loop(model, LoopConfig(**shapes), resume=False,
                     oracle_step=jax.jit(make_train_step(model)))
    losses = res["losses"]
    max_err = res["oracle_max_rel_err"]
    del res
    return {
        "phase": "donation", "arch": cfg.name,
        "num_layers": cfg.num_layers, **shapes, "state_donated": donated,
        "steps_verified": len(losses), "oracle_max_rel_err": max_err, "loss_first": losses[0],
        "loss_last": losses[-1], "wall_s": time.perf_counter() - t0,
        "peak_bytes_in_use": peak_bytes(),
        "ok": bool(donated and len(losses) == shapes["steps"]
                   and np.all(np.isfinite(losses))),
    }


# ------------------------------------------------------------ multi-chip --
def multi_chip_phase(cfg, devices, shapes: dict) -> dict:
    """The same four boards on one slot per device, against one slot on
    the first device, in this process. ``ok`` needs both runs ok,
    bit-identical delivered outputs per board, and every board's final
    carry on its own slot's device (its windows ran there: JAX refuses a
    dispatch whose committed inputs sit on two devices)."""
    from repro.farm.placement import enumerate_slots
    from repro.launch.farm import run_farm

    runs = {}
    for label, devs in (("one_slot", devices[:1]), ("per_device", devices)):
        out = run_farm(cfg, enumerate_slots(devices=devs), **shapes)
        runs[label] = out
        gc.collect()
    one, many = runs["one_slot"], runs["per_device"]
    slot_device = {f"{d.platform}:{d.id}": d.id for d in devices}
    placed = many["placement"]
    on_own_device = all(p["devices"] == [slot_device[p["slot"]]]
                        for p in placed.values())
    identical = {n: one["delivered_sha256"][n]
                 == many["delivered_sha256"].get(n)
                 for n in one["delivered_sha256"]}
    distinct = len({p["slot"] for p in placed.values()}) == len(placed)
    return {
        "phase": "multi_chip", "arch": cfg.name, **shapes,
        "devices": len(devices), "placement": placed,
        "bit_identical": identical,
        "loss_last": {k: r["train"]["loss_last"] for k, r in runs.items()},
        "peak_bytes_in_use": peak_bytes(),
        "ok": bool(farm_ok(one, shapes) and farm_ok(many, shapes)
                   and all(identical.values()) and on_own_device
                   and distinct),
    }


# ------------------------------------------------------------------ main --
def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the one-board-per-chip phase")
    args = ap.parse_args(argv)

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(jax.devices())}", file=sys.stderr)
        return 2

    cfg = get_config(ARCH)
    if args.chips == 4:
        phases = [lambda: multi_chip_phase(cfg, jax.devices()[:4],
                                           MULTI_CHIP_SHAPES)]
    else:
        phases = [
            kernel_phase,
            lambda: farm_phase(cfg, 1, FARM_SHAPES),
            lambda: donation_phase(
                dataclasses.replace(cfg, num_layers=DONATION_LAYERS),
                DONATION_SHAPES),
        ]
    for phase in phases:
        result = phase()
        gc.collect()
        print(json.dumps(result, default=float), flush=True)
        if not result["ok"]:
            print(f"chip_smoke: phase {result['phase']} failed",
                  file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
