"""The four-chip train cell (``internvl2-1b.train-verified-4chip``, kind
``train_per_chip``) at smoke size on four CPU devices, in a process of
its own (the device count is fixed when JAX starts): every slot verifies
windows, none compiles inside the window, and the run is correct."""
import json
import os
import subprocess
import sys
import textwrap

import smoke

WORKLOAD = "internvl2-1b.train-verified-4chip"


def test_four_slots_each_warmed_and_verified(tmp_path):
    script = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(smoke.HERE)!r})
        import smoke
        from pathlib import Path
        root = smoke.tree(Path({str(tmp_path)!r}))
        here = root / "benchmarks" / "chip"
        smoke._patch(here / "mixes" / "train-verified-4chip.json", seq=16)
        lim = smoke.SMOKE_LIMITS["internvl2-1b.train-verified"]
        (here / "limits" / "{WORKLOAD}.json").write_text(json.dumps(
            {{"numbers": {{k: {{"limit": v}} for k, v in lim.items()}}}}))
        out = smoke.run(root, "{WORKLOAD}", seconds=4.0)
        from repro.farm.telemetry import last_report
        out["slot_windows"] = {{s: d["windows"] for s, d in
                                last_report()["devices"].items()}}
        print("RESULT::" + json.dumps(out, default=str))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT::")][-1]
    out = json.loads(line[len("RESULT::"):])
    assert out["device"]["count"] == 4
    assert out["correct"] is True, out["check"]
    assert out["detail"]["compiles_in_window"] == 0
    assert len(out["slot_windows"]) == 4
    assert all(n > 0 for n in out["slot_windows"].values()), out
    assert set(out["metrics"]) == {"setup_s", "train_steps_per_s",
                                   "window_p95_ms"}
