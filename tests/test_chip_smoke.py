"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, its
phases run at smoke size, and the compile-cache helper keeps one fixed
directory. Also the runtime's import hygiene: ``repro.core`` must not load
the static analysers."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, cwd=REPO, env=None, timeout=300):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _reports_ok(stdout: str) -> bool:
    return '"ok": true' in stdout


# ------------------------------------------------------- no chip, no result
def test_main_without_tpu_exits_nonzero_and_prints_no_result():
    proc = _run([SCRIPT])
    assert proc.returncode != 0
    assert not _reports_ok(proc.stdout)
    assert "needs a TPU" in proc.stderr


def test_script_alone_outside_the_repo_fails(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    proc = _run([str(alone)], cwd=str(tmp_path),
                env={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not _reports_ok(proc.stdout)


# ------------------------------------------------------ phases, smoke size
SMALL_FARM = dict(steps=4, gen=4, batch=2, seq=16, prompt_len=16,
                  verify_seq=16, interval=2)


def test_kernel_phase_interpret_mode_agrees_with_refs():
    cs = _load_chip_smoke()
    out = cs.kernel_phase(small=True, interpret=True)
    assert out["ok"], out
    assert set(out["kernels"]) == {"flash_attention", "decode_attention",
                                   "grouped_gemm", "ssm_scan", "rglru_scan"}


def test_farm_phase_runs_the_farm_at_smoke_size():
    cs = _load_chip_smoke()
    cfg = get_smoke_config(cs.ARCH)
    out = cs.farm_phase(cfg, 1, SMALL_FARM)
    assert out["ok"], out
    assert out["jobs"] == {"train": "done", "decode": "done",
                           "layer0": "done", "layer1": "done"}
    assert out["tokens_decoded"] == SMALL_FARM["batch"] * SMALL_FARM["gen"]
    assert set(out["verify_max_rel_err"]) == {"layer0", "layer1"}


def test_donation_phase_donates_and_verifies_against_the_oracle():
    cs = _load_chip_smoke()
    cfg = get_smoke_config(cs.ARCH)
    out = cs.donation_phase(cfg, dict(cs.DONATION_SHAPES, seq=16))
    assert out["ok"], out
    assert out["state_donated"]
    assert out["steps_verified"] == cs.DONATION_SHAPES["steps"]


def test_multi_chip_phase_on_four_virtual_devices():
    """The ``--chips 4`` phase on four CPU devices: one board per device,
    bit-identical to the one-slot run."""
    body = (
        "import json, importlib.util\n"
        "import jax\n"
        "from repro.configs import get_smoke_config\n"
        f"spec = importlib.util.spec_from_file_location('cs', {SCRIPT!r})\n"
        "cs = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(cs)\n"
        "out = cs.multi_chip_phase(get_smoke_config(cs.ARCH),\n"
        f"                         jax.devices()[:4], {SMALL_FARM!r})\n"
        "print('RESULT::' + json.dumps(out, default=float))\n")
    proc = _run(["-c", body], env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT::")][-1]
    out = json.loads(line[len("RESULT::"):])
    assert out["ok"], out
    assert all(out["bit_identical"].values())
    assert sorted(p["devices"][0] for p in out["placement"].values()) \
        == [0, 1, 2, 3]


def test_seeded_init_is_the_same_in_every_process():
    """Weights made from a seed do not depend on the process's string-hash
    salt: two interpreters with different ``PYTHONHASHSEED`` agree."""
    probe = ("import jax, numpy as np\n"
             "from repro.configs import get_smoke_config\n"
             "from repro.models import build_model\n"
             "from repro.models.runtime import Runtime\n"
             "m = build_model(get_smoke_config('internvl2-1b'), Runtime())\n"
             "p = m.init(jax.random.key(0))\n"
             "print(repr([float(np.asarray(x, np.float64).sum())\n"
             "            for x in jax.tree.leaves(p)]))\n")
    outs = []
    for salt in ("1", "2"):
        proc = _run(["-c", probe], env={
            "PYTHONPATH": os.path.join(REPO, "src"), "PYTHONHASHSEED": salt})
        assert proc.returncode == 0, proc.stderr[-2000:]
        outs.append(proc.stdout)
    assert outs[0] == outs[1]


# ---------------------------------------------------------- compile cache
_CACHE_PROBE = (
    "import jax\n"
    "from repro.utils import enable_compile_cache\n"
    "a = enable_compile_cache()\n"
    "b = enable_compile_cache()\n"
    "print(a); print(b); print(jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_follows_the_environment(tmp_path):
    want = str(tmp_path / "cache")
    proc = _run(["-c", _CACHE_PROBE],
                env={"PYTHONPATH": os.path.join(REPO, "src"),
                     "JAX_COMPILATION_CACHE_DIR": want})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want, want]


def test_compile_cache_defaults_to_one_fixed_checkout_path():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    runs = [subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                           capture_output=True, text=True, timeout=120)
            for _ in range(2)]
    assert all(r.returncode == 0 for r in runs), runs[0].stderr[-2000:]
    paths = {p for r in runs for p in r.stdout.split()}
    assert paths == {os.path.join(REPO, ".jax_cache")}


# --------------------------------------------------------- import hygiene
def test_importing_the_runtime_does_not_load_the_analysers():
    probe = ("import sys, repro.core, repro.farm, repro.launch.farm\n"
             "print(sorted(m for m in sys.modules\n"
             "             if m.startswith('repro.analysis')))\n")
    proc = _run(["-c", probe], env={"PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr[-2000:]
    loaded = proc.stdout.strip()
    assert "boardcheck" not in loaded and "racecheck" not in loaded
    assert "repro.analysis.annotations" in loaded


def test_analysis_package_still_exports_lazily():
    import repro.analysis as analysis
    assert callable(analysis.certify_job) and callable(analysis.check_paths)
    with pytest.raises(AttributeError):
        analysis.no_such_name  # noqa: B018
