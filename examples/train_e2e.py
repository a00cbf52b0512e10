"""End-to-end training driver: ~100M-class model for a few hundred steps on
CPU, with checkpoint/restart, watchdog, coverage and live stall profiling —
the full ZP-Farm host loop (deliverable (b)).

  PYTHONPATH=src python examples/train_e2e.py --steps 300
"""
import argparse
import dataclasses
import json

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.train.loop import LoopConfig, train_loop
from repro.train.optim import OptConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    # ~100M-class: widen the granite smoke config
    cfg = dataclasses.replace(
        get_smoke_config("granite-8b"),
        name="granite-100m", num_layers=8, d_model=512, num_heads=8,
        num_kv_heads=4, head_dim=64, d_ff=1408, vocab_size=32768)
    model = build_model(cfg, Runtime(
        taps=frozenset({"commits", "coverage"}), remat="dots"))

    out = train_loop(
        model,
        LoopConfig(steps=args.steps, batch=8, seq=128, sample_interval=10,
                   checkpoint_every=100, checkpoint_dir=args.ckpt),
        OptConfig(lr=3e-4, warmup_steps=50))
    n = len(out["losses"])
    print(json.dumps({
        "params_m": round(cfg.param_count() / 1e6, 1),
        "steps": n,
        "loss_start": sum(out["losses"][:10]) / min(10, n),
        "loss_end": sum(out["losses"][-10:]) / min(10, n),
        "profile": out["profile"],
        "coverage": out["coverage"],
    }, indent=1, default=float))


if __name__ == "__main__":
    main()
