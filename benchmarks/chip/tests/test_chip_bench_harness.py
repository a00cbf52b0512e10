"""The harness is driven by data: BENCHMARK.json keeps to the contract's
shape, a cell added as new files plus manifest entries runs with no code
change, and the command refuses to run without a TPU or without the
program."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import smoke
from chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def check_manifest(man, root):
    assert set(man) == KEYS["top"]
    assert 1 <= len(man["paths"]) <= 16
    for p in man["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (root / p).is_dir()
    assert 1 <= len(man["command"]) <= 32 and all(map(_line, man["command"]))
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in man["paths"])
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in man[k]}) == len(man[k])
    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == KEYS["config"]
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        assert (root / c["file"]).is_file() and len(c["reduced"]) <= 16
        assert all(NAME.match(r) for r in c["reduced"])
    for w in man["workloads"]:
        assert set(w) == KEYS["workload"] and w["config"] in configs
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert _line(w["why"])
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == KEYS["end_to_end"]
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == KEYS["per_layer"]
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            mv = e2e[m["moves"]]
            assert "workloads" not in mv or w in mv["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
    for w in man["workloads"]:
        rep = [m for m in man["end_to_end"]
               if "workloads" not in m or w["name"] in m["workloads"]]
        assert "setup_s" in {m["name"] for m in rep} and len(rep) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in man["per_layer"])
    assert len(json.dumps(man)) <= 64 * 1024


def test_manifest_keeps_to_the_contract():
    man = harness.manifest(smoke.REPO)
    check_manifest(man, smoke.REPO)
    here = smoke.CHIP
    for w in man["workloads"]:
        mix = here / "mixes" / f"{w['traffic']}.json"
        kind = json.loads(mix.read_text())["kind"]
        assert (here / "kinds" / f"{kind}.py").is_file()
    for m in man["per_layer"]:
        assert harness.metric_path(m["name"], here).is_file()


@pytest.mark.parametrize("bad", [
    {"name": "has space"}, {"name": "a/b"}, {"unit": "tokens per s"},
    {"unit": "µs"}])
def test_manifest_check_refuses_bad_names_and_units(bad):
    man = json.loads(json.dumps(harness.manifest(smoke.REPO)))
    man["end_to_end"][0].update(bad)
    with pytest.raises(AssertionError):
        check_manifest(man, smoke.REPO)


#: a traffic kind of its own, as a later change would add it: boards that
#: square seeded rows window by window, checked against numpy
SQUARES = '''
import jax
import numpy as np

from chip import weights
from chip.cells import Base, rel_gap
from chip.harness import release


class Kind(Base):
    rate_metric = "squares_per_s"

    def setup(self):
        n = int(self.mix["rows"])
        self.xs = weights.np_rng(self.seed, "squares").standard_normal(
            (n, 8)).astype(np.float32)
        self.fn = jax.jit(lambda s, sh, st: (s, sh, st * st))
        self.fn({}, {}, self.xs[:2])

    def jobs(self, rec, mgr):
        from repro.core.coemu import _stack_on_device
        from repro.farm import FarmJob
        out = []
        for b in range(int(self.mix["boards"])):
            name = f"sq{b}"

            def check(plan, records, ys):
                return plan.size, False, (plan.index, np.asarray(ys))
            out.append(FarmJob(
                name=name, engine=rec.engine(name, self.fn), state={},
                shell={}, windows=[[x, y] for x, y in zip(self.xs[::2],
                                                          self.xs[1::2])],
                stack_fn=_stack_on_device, verify=rec.verify(name, check),
                on_drain=release(mgr, name), max_requeues=0))
        return out

    def check(self, rec):
        worst = 0.0
        for r in self.rows:
            i, got = r.payload
            want = self.xs[2 * i:2 * i + 2] ** 2
            worst = max(worst, float(np.max(rel_gap(got, want, 1e-6))))
        return {"square_rel": worst}

    def control(self):
        return {"square_rel": 1.0}
'''


def test_a_cell_added_as_data_runs(tmp_path):
    """A new configuration, traffic mix and per-layer metric, each a new
    file, plus their manifest entries: the harness runs the new cell and
    reports the new metric, with no change to its code."""
    root = smoke.tree(tmp_path)
    here = root / "benchmarks" / "chip"
    doc = json.loads((here / "configs" / "granite-8b.json").read_text())
    doc.update(name="tiny", num_hidden_layers=1, intermediate_size=96)
    (here / "configs" / "tiny.json").write_text(json.dumps(doc))
    mix = json.loads((here / "mixes" / "subsys-sweep.json").read_text())
    mix.update(batches=4, passes=2, slots=2, rounds=3000)
    (here / "mixes" / "tiny-sweep.json").write_text(json.dumps(mix))
    (here / "limits" / "tiny.sweep.json").write_text(json.dumps(
        {"numbers": {"replay_rel": {"limit": 0.0024}}}))
    (here / "metrics" / "windows_seen.tiny.py").write_text(
        "def read(rec):\n    return float(rec['windows'])\n")
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny", "source": "test",
                           "file": "benchmarks/chip/configs/tiny.json",
                           "reduced": [], "why": "test"})
    man["workloads"].append({"name": "tiny.sweep", "config": "tiny",
                             "traffic": "tiny-sweep", "chips": 1,
                             "why": "test"})
    man["per_layer"].append({"name": "windows_seen.tiny", "unit": "1",
                             "better": "higher", "source": "host_clock",
                             "layer": "farm control plane",
                             "moves": "window_p95_ms",
                             "workloads": ["tiny.sweep"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    check_manifest(man, root)

    out = smoke.run(root, "tiny.sweep", trace=True)
    assert out["metrics"]["windows_seen.tiny"]["value"] >= 20
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert "replay_rel" in out["check"]
    out = smoke.run(root, "tiny.sweep", trace=False)
    assert set(out["metrics"]) == {"setup_s", "window_p95_ms"}
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] is True, out["check"]


def test_a_traffic_kind_added_as_a_file_runs(tmp_path):
    """A new kind of traffic is a new generator file, ``kinds/<kind>.py``,
    with a mix that names it, a limits file and a manifest entry; the
    harness finds it by name, runs it through the farm, checks it, and
    reports both the shared metrics and a per-layer metric whose reader
    it finds by the name's stem (``host_ms_per_window.squares`` reads
    ``metrics/host_ms_per_window.py``)."""
    root = smoke.tree(tmp_path)
    here = root / "benchmarks" / "chip"
    (here / "kinds" / "squares.py").write_text(SQUARES)
    (here / "mixes" / "squares.json").write_text(json.dumps(
        {"kind": "squares", "why": "test", "rows": 8, "boards": 4000,
         "slots": 1}))
    (here / "limits" / "granite-8b.squares.json").write_text(json.dumps(
        {"numbers": {"square_rel": {"limit": 1e-6}}}))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "granite-8b.squares",
                             "config": "granite-8b", "traffic": "squares",
                             "chips": 1, "why": "test"})
    man["per_layer"].append({"name": "host_ms_per_window.squares",
                             "unit": "ms", "better": "lower",
                             "source": "program_span",
                             "layer": "farm control plane",
                             "moves": "window_p95_ms",
                             "workloads": ["granite-8b.squares"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    check_manifest(man, root)

    out = smoke.run(root, "granite-8b.squares", trace=True)
    assert out["metrics"]["host_ms_per_window.squares"]["value"] > 0
    assert out["correct"] is True and out["failed"] == 0, out["check"]
    out = smoke.run(root, "granite-8b.squares", control=True)
    assert out["correct"] is False
    assert set(out["metrics"]) == {"setup_s", "window_p95_ms"}


def _run_cell(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run_cell.py", "--workload",
         "granite-8b.decode", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_command_without_a_tpu_prints_no_result():
    res = _run_cell(smoke.REPO)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "needs a TPU" in res.stderr


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copy(smoke.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(smoke.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_cell(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""
