"""Report generator: dry-run + roofline JSON records -> EXPERIMENTS.md
sections (markdown tables). Run after the sweeps:

  PYTHONPATH=src python -m repro.roofline.report
"""
from __future__ import annotations

import glob
import json
import pathlib
from collections import defaultdict

ARCH_ORDER = ["qwen3-moe-30b-a3b", "mixtral-8x7b", "internlm2-20b",
              "glm4-9b", "command-r-35b", "granite-8b", "whisper-small",
              "recurrentgemma-2b", "internvl2-1b", "falcon-mamba-7b"]
SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def _load(pattern):
    out = {}
    for f in glob.glob(pattern):
        r = json.load(open(f))
        out[(r["arch"], r["shape"], r.get("mesh", "16x16"))] = r
    return out


def _fmt_bytes(b):
    if b >= 1e12:
        return f"{b/1e12:.2f}T"
    if b >= 1e9:
        return f"{b/1e9:.2f}G"
    if b >= 1e6:
        return f"{b/1e6:.1f}M"
    return f"{b/1e3:.0f}K"


def dryrun_section(dryruns) -> str:
    lines = [
        "### §Dry-run — every (arch x shape) lowered AND compiled on both "
        "production meshes",
        "",
        "Mesh 16x16 = one 256-chip v5e pod (`data` x `model`); 2x16x16 adds "
        "the `pod` axis (512 chips). `coll/dev` is effective wire bytes per "
        "device per step from the compiled HLO (while-loop trip counts "
        "applied); `state/dev` is the analytic parameter(+opt/cache) "
        "footprint per device.",
        "",
        "| arch | shape | mesh | status | compile s | HLO flops/dev | "
        "coll/dev | state/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            for mesh in ("16x16", "2x16x16"):
                r = dryruns.get((arch, shape, mesh))
                if r is None:
                    continue
                if r["status"] != "ok":
                    reason = "skip (full attention @500k)" \
                        if r["status"] == "skipped" else r["status"]
                    lines.append(
                        f"| {arch} | {shape} | {mesh} | {reason} | | | | |")
                    continue
                lines.append(
                    f"| {arch} | {shape} | {mesh} | ok "
                    f"| {r['compile_s']:.1f} "
                    f"| {r['cost_analysis']['flops']:.2e} "
                    f"| {_fmt_bytes(r['collectives']['total_effective_bytes'])} "
                    f"| {_fmt_bytes(r['analytic']['state_bytes_per_device'])} |")
    return "\n".join(lines)


def roofline_section(rooflines) -> str:
    lines = [
        "### §Roofline — per (arch x shape), single-pod 16x16 mesh, TPU v5e "
        "targets (197 TF bf16, 819 GB/s HBM, 50 GB/s/link ICI)",
        "",
        "Terms are seconds/step/device from the Scale-Down composition "
        "(per-period dry-runs x depth + embed/head + optimizer; see "
        "DESIGN.md). C = compute, M = memory (analytic TPU-fusion floor; "
        "M_hlo = raw HLO-bytes ceiling), K = collective (2 ICI links, ring "
        "factors). `useful` = MODEL_FLOPS / HLO_FLOPS (6ND vs compiled; "
        "catches remat/redundant compute — and flags cells where the S^2 "
        "attention term, absent from 6ND, is a real fraction of work). "
        "`roofline` = (MODEL_FLOPS/chips/peak) / max(C, M, K).",
        "",
        "| arch | shape | C (ms) | M (ms) | M_hlo (ms) | K (ms) | dominant "
        "| useful | roofline | kernel-adj | bottleneck note |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    notes = {
        "compute": "MXU-bound; gains need sharding/kernel changes",
        "memory": "HBM-bound; gains need fusion/layout/cache residency",
        "collective": "ICI-bound; gains need sharding/collective schedule",
    }
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = rooflines.get((arch, shape, "16x16"))
            if r is None:
                continue
            if r["status"] != "ok":
                lines.append(f"| {arch} | {shape} | skipped ||||||||")
                continue
            ka = r.get("roofline_fraction_kernel", r["roofline_fraction"])
            lines.append(
                f"| {arch} | {shape} "
                f"| {r['compute_s']*1e3:.1f} | {r['memory_s']*1e3:.1f} "
                f"| {r['memory_s_hlo']*1e3:.1f} "
                f"| {r['collective_s']*1e3:.1f} | {r['dominant']} "
                f"| {r['useful_ratio']*100:.0f}% "
                f"| {r['roofline_fraction']*100:.1f}% "
                f"| {ka*100:.1f}% "
                f"| {notes[r['dominant']]} |")
    return "\n".join(lines)


def timing_section(rooflines) -> str:
    """Event-driven timing co-emulation (DESIGN C4): predicted step time
    under the async-collective overlap model vs fully serialized."""
    from repro.core.timing import Timeline
    ov = Timeline(overlap=True)
    ser = Timeline(overlap=False)
    lines = [
        "### §Timing co-emulation — predicted step time (overlap model)",
        "",
        "The VPS-style timing model (core/timing.py) consumes each cell's "
        "roofline terms: `overlap` models XLA async collectives hiding "
        "behind the compute/memory stream; `serial` is the no-overlap "
        "bound. The gap is what compute/comm overlap buys per step.",
        "",
        "| arch | shape | t_overlap (ms) | t_serial (ms) | overlap gain |",
        "|---|---|---|---|---|",
    ]
    for arch in ARCH_ORDER:
        for shape in SHAPE_ORDER:
            r = rooflines.get((arch, shape, "16x16"))
            if r is None or r.get("status") != "ok":
                continue
            g = [{"compute_s": r["compute_s"], "memory_s": r["memory_s"],
                  "collective_s": r["collective_s"]}]
            a = ov.simulate(g)["total_s"]
            b = ser.simulate(g)["total_s"]
            lines.append(f"| {arch} | {shape} | {a*1e3:.1f} | {b*1e3:.1f} "
                         f"| {b/max(a,1e-12):.2f}x |")
    return "\n".join(lines)


def measured_section(measured) -> str:
    """Measured clock-gated windows (``roofline.WindowCapture`` records
    saved by ``capture.save_measured``) next to the static composition:
    per (arch x source) wall seconds/step and — when the capture carried
    an HLO cost attachment — achieved rates against the hardware peaks."""
    lines = [
        "### §Measured windows — WindowCapture records (train / serve / "
        "farm runs)",
        "",
        "`s/step` is pipelined wall (drain of window *i* lands while "
        "window *i+1* is in flight), so achieved rates are a LOWER bound "
        "on device throughput. HLO cost rides the engine's own first "
        "compile (`attach_engine`, the default for train/serve); rows "
        "without cost columns came from a capture with no cost source.",
        "",
        "| arch | source | windows | steps | s/step | achieved TF/s | "
        "peak flops | peak HBM |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for (arch, source) in sorted(measured):
        r = measured[(arch, source)]
        sps = r.get("s_per_step")
        af = r.get("achieved_flops_s")
        row = (f"| {arch} | {source} | {r.get('windows', 0)} "
               f"| {r.get('steps', 0)} "
               f"| {f'{sps:.4f}' if sps is not None else 'n/a'} ")
        pf, ph = r.get("peak_flops_fraction"), r.get("peak_hbm_fraction")
        if af is not None and pf is not None:
            row += (f"| {af/1e12:.3f} "
                    f"| {pf*100:.2f}% "
                    f"| {ph*100:.2f}% |")
        elif af is not None:
            row += f"| {af/1e12:.3f} | not measured | not measured |"
        else:
            row += "| | | |"
        lines.append(row)
    return "\n".join(lines)


def pick_hillclimb_cells(rooflines):
    """worst roofline fraction / most collective-bound / most representative
    (per the assignment)."""
    ok = [r for r in rooflines.values()
          if r.get("status") == "ok" and r.get("mesh", "16x16") == "16x16"]
    worst = min(ok, key=lambda r: r["roofline_fraction"])
    coll = max(ok, key=lambda r: r["collective_s"]
               / max(r["step_time_bound_s"], 1e-30)
               * (r["collective_s"]))
    return worst, coll


def _load_measured(pattern="experiments/measured/*.json"):
    out = {}
    for f in glob.glob(pattern):
        r = json.load(open(f))
        out[(r["arch"], r["source"])] = r
    return out


def main():
    dryruns = _load("experiments/dryrun/*.json")
    rooflines = _load("experiments/roofline/*.json")
    measured = _load_measured()
    out = ["<!-- generated by repro.roofline.report -->", "",
           dryrun_section(dryruns), "", roofline_section(rooflines),
           "", timing_section(rooflines)]
    if measured:
        out += ["", measured_section(measured)]
    path = pathlib.Path("experiments/tables.md")
    path.write_text("\n".join(out))
    print(f"wrote {path}")
    if rooflines:
        worst, coll = pick_hillclimb_cells(rooflines)
        print(f"worst roofline: {worst['arch']} x {worst['shape']} "
              f"({worst['roofline_fraction']*100:.2f}%)")
        print(f"most collective-bound: {coll['arch']} x {coll['shape']} "
              f"(K={coll['collective_s']*1e3:.0f}ms)")


if __name__ == "__main__":
    main()
