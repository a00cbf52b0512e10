"""Readings the correctness limits are set from, in one process:

  python3 benchmarks/chip/calibrate.py --workload <name> --seconds <s> \
      --seeds 11,12,... --control-seeds 21,22,23 [--out DIR]

For each ``--seeds`` seed, one run of the cell (short window, no trace)
and the numbers its check compares. For each ``--control-seeds`` seed,
also the same numbers for the control: the reference computed with
float8 matrix products put in the program's place, on that run's inputs.
Writes one JSON line per reading to ``<DIR>/<workload>.jsonl`` (``DIR``
defaults to ``.bench_calib`` in the checkout) and prints it.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# libtpu logs under /tmp unless told otherwise; write nothing outside
# the checkout and the given HOME / TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[0] = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(1, os.path.join(ROOT, "src"))

from chip import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_calib"))
    args = ap.parse_args(argv)
    import jax
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.jsonl")
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = [int(s) for s in args.control_seeds.split(",") if s]
    for seed in list(dict.fromkeys(seeds + ctl)):
        got = {}

        def after(kind, seed=seed):
            if seed in ctl:
                got["control"] = kind.control()
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, t_process=t0,
                          after=after if seed in ctl else None)
        line = {"workload": args.workload, "seed": seed,
                "program": {k: v["value"] for k, v in out["check"].items()},
                "control": got.get("control"),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "detail": out["detail"], "wall_s": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        gc.collect()


if __name__ == "__main__":
    main()
