"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV and writes the same rows to
``BENCH_results.json`` (machine-readable, for cross-PR perf tracking).
Results MERGE into the existing file by default — an unfiltered run no
longer clobbers entries it did not re-measure (e.g. a bench module that
failed this run, or rows written by another harness); pass ``--fresh`` to
rewrite the file from only this run's rows. Run:

  PYTHONPATH=src python -m benchmarks.run            # all benches (merge)
  PYTHONPATH=src python -m benchmarks.run sampling   # substring filter
  PYTHONPATH=src python -m benchmarks.run --fresh    # clobber stale rows
"""
from __future__ import annotations

import sys
import traceback

from benchmarks.common import write_results
from repro.utils import enable_compile_cache

MODULES = [
    "benchmarks.bench_kernels",       # per-kernel us/call + allclose
    "benchmarks.bench_tco",           # Table I  — TCO model
    "benchmarks.bench_stall_stack",   # Fig. 7   — cycle stacks
    "benchmarks.bench_sampling",      # Fig. 11/12 — interval sweep
    "benchmarks.bench_coverage",      # Fig. 13  — coverage overhead
    "benchmarks.bench_panicroom",     # Table II — portability
    "benchmarks.bench_coemu",         # §IV-A    — verify throughput
    "benchmarks.bench_farm",          # ZP-Farm  — farm-vs-serial boards
    "benchmarks.bench_lanes",         # ZP-Farm  — lane-batched boards
    "benchmarks.bench_scope",         # ZP-Scope — instrumentation overhead
]


def main() -> None:
    enable_compile_cache()
    argv = sys.argv[1:]
    fresh = "--fresh" in argv
    filters = [a for a in argv if not a.startswith("-")]
    mods = [m for m in MODULES
            if not filters or any(f in m for f in filters)]
    print("name,us_per_call,derived")
    failed = []
    for mod_name in mods:
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    write_results(merge=not fresh)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
