"""Training CLI.

  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \\
      --steps 50 --batch 4 --seq 64

--smoke runs the reduced config on CPU (the end-to-end example driver);
full configs are for real pods (and are exercised compile-only by dryrun).
"""
from __future__ import annotations

import argparse
import json

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import build_model
from repro.models.runtime import Runtime
from repro.train.loop import LoopConfig, train_loop
from repro.train.optim import OptConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="granite-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sample-interval", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--save-measured", action="store_true",
                    help="persist the run's measured-window roofline "
                         "record for repro.roofline.report")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rt = Runtime(taps=frozenset({"commits", "coverage", "router"}))
    model = build_model(cfg, rt)
    out = train_loop(
        model,
        LoopConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                   sample_interval=args.sample_interval,
                   checkpoint_dir=args.checkpoint_dir,
                   grad_compress=args.grad_compress,
                   accum_steps=args.accum_steps),
        OptConfig(lr=args.lr, warmup_steps=10))
    if args.save_measured:
        from repro.roofline import save_measured
        save_measured(out["roofline"], cfg.name, "train")
    print(json.dumps({
        "arch": cfg.name,
        "loss_first": out["losses"][0], "loss_last": out["losses"][-1],
        "coverage": out["coverage"], "profile": out["profile"],
        "roofline": out["roofline"],
    }, indent=1, default=float))


if __name__ == "__main__":
    main()
