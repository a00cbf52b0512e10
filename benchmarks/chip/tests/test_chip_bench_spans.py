"""The readers of the program's phase spans (``metrics/slot_wait_ms.py``,
``slot_work_ms.py``, ``ctl_work_ms.py``, ``slot_cpu_share.py``,
``oracle_host_ms.py``) read the telemetry of the last farm run
(``repro.farm.telemetry.last_report()``): a positive number after a
smoke-size CPU run of a cell, nothing before any farm has run."""
import pytest

import smoke
from chip import harness

SWEEP = ["slot_wait_ms.subsys", "slot_work_ms.subsys", "ctl_work_ms.subsys",
         "slot_cpu_share.subsys"]
TRAIN = ["slot_wait_ms.train", "slot_work_ms.train", "oracle_host_ms.train"]
SEED = 2 ** 31 + 23


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.tree(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", SWEEP + ["oracle_host_ms.train"])
def test_reader_reads_nothing_before_a_farm_run(name, monkeypatch):
    from repro.farm import telemetry
    monkeypatch.setattr(telemetry, "_last_report", None)
    assert harness.metric_reader(name)({}) is None


@pytest.mark.parametrize("workload,names", [
    ("internvl2-1b.subsys-sweep", SWEEP),
    ("internvl2-1b.train-verified", TRAIN)])
def test_readers_read_the_last_farm_run(root, workload, names):
    out = smoke.run(root, workload, seed=SEED)
    assert out["correct"] is True, out["check"]
    for name in names:
        value = harness.metric_reader(name)({})
        assert value is not None and value > 0, name
    share = harness.metric_reader("slot_cpu_share.subsys")({})
    assert 0 < share <= 105
