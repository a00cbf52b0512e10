"""Smoke-size copies of the benchmark for CPU tests: the same files, with
each configuration cut to a handful of widths and each mix to a few
positions, so that a whole run (set-up, window, reference check) takes
seconds on the CPU. Not a test module; the tests import it."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
REPO = CHIP.parents[1]
if str(CHIP.parent) not in sys.path:
    sys.path.insert(0, str(CHIP.parent))
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

SMOKE_CONFIGS = {
    "internvl2-1b": dict(num_hidden_layers=2, hidden_size=64,
                         intermediate_size=128, num_attention_heads=4,
                         num_key_value_heads=2, head_dim=16, vocab_size=256,
                         num_image_token=8, vit_hidden_size=32),
    "granite-8b": dict(num_hidden_layers=2, hidden_size=64,
                       intermediate_size=192, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16, vocab_size=256),
}
SMOKE_MIXES = {
    "train-verified": dict(seq=16),
    "subsys-sweep": dict(seq=16, rounds=200),
    "decode": dict(batch=2, prompt=32, gen=17, window_tokens=4,
                   max_boards=400),
}


#: limits for the smoke sizes, set by the rule of PERF.md from smoke-size
#: CPU readings (12 seeds, the control on 3); the committed limits are for
#: the cells' own sizes on the chip
SMOKE_LIMITS = {
    "internvl2-1b.train-verified": dict(
        loss_rel=0.0024832391011768646,
        grad_leaf_gap=0.10171492432204472,
        step_leaf_gap=0.24911325639468032),
    "internvl2-1b.subsys-sweep": dict(
        replay_rel=0.002425789520991722),
    "granite-8b.decode": dict(
        logit_gap=0.03685657770914513),
}


def _patch(path: Path, **kw):
    doc = json.loads(path.read_text())
    doc.update(kw)
    path.write_text(json.dumps(doc, indent=1))


def tree(dst: Path) -> Path:
    """A checkout-shaped smoke copy at ``dst``: BENCHMARK.json, the
    benchmark's files (smoke-cut) and a link to the program."""
    dst = Path(dst)
    shutil.copytree(CHIP, dst / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(REPO / "src")
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    here = dst / "benchmarks" / "chip"
    for name, kw in SMOKE_CONFIGS.items():
        _patch(here / "configs" / f"{name}.json", **kw)
    for name, kw in SMOKE_MIXES.items():
        _patch(here / "mixes" / f"{name}.json", **kw)
    for name, kw in SMOKE_LIMITS.items():
        (here / "limits" / f"{name}.json").write_text(json.dumps(
            {"numbers": {k: {"limit": v} for k, v in kw.items()}}))
    return dst


def run(root: Path, workload: str, seed: int = 5, seconds: float = 1.5,
        trace: bool = False, fault=None, control: bool = False,
        after=None) -> dict:
    """One CPU run of ``workload`` in the smoke tree at ``root``."""
    from chip import harness
    cell = harness.load_cell(workload, root=root,
                             here=root / "benchmarks" / "chip")
    return harness.run(cell, seed, seconds, trace,
                       t_process=time.perf_counter(), require_tpu=False,
                       fault=fault, control=control, after=after)
