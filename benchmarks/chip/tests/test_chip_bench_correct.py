"""What decides ``correct``, at smoke size on the CPU, through the
harness's own run (``harness.run``: set-up, the timed window, the check
against the limits).

For each kind of cell: a sound run comes out correct; the control (the
reference computed with float8 matrix products, the step below the
configurations' bfloat16) put in the program's place comes out not
correct, and reads at least three times what the program reads on some
compared number; and the same run with the timed path broken underneath,
once for each fault the kind can have, comes out not correct, a number
over its limit and at least ten times the sound run's.

Faults: a train window that returns its state unchanged (the train
board runs one chip and batch 1, so no exchange and no half batch to
leave out); a subsystem answer altered by 1% where it is produced; a
decoded token altered where it is produced.
"""
import pytest

import smoke

CASES = [("internvl2-1b.train-verified", "state_unchanged"),
         ("internvl2-1b.subsys-sweep", "answer_altered"),
         ("granite-8b.decode", "token_altered")]
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.tree(tmp_path_factory.mktemp("bench"))


def _values(out):
    return {k: v["value"] for k, v in out["check"].items()}


@pytest.mark.parametrize("workload,fault", CASES)
def test_control_and_fault_come_out_not_correct(root, workload, fault):
    got = {}
    sound = smoke.run(root, workload, seed=SEED,
                      after=lambda kind: got.update(kind.control()))
    assert sound["correct"] is True, sound["check"]
    prog = _values(sound)
    assert set(got) == set(prog)
    assert any(got[k] >= 3 * prog[k] for k in prog), (got, prog)

    ctl = smoke.run(root, workload, seed=SEED, control=True)
    assert ctl["correct"] is False, ctl["check"]
    assert all(v is not None for v in _values(ctl).values())

    bad = smoke.run(root, workload, seed=SEED, fault=fault)
    assert bad["correct"] is False
    over = [k for k, c in bad["check"].items()
            if c["limit"] is not None and c["value"] is not None
            and c["value"] > c["limit"]
            and c["value"] >= 10 * max(prog[k], 1e-12)]
    assert over, (bad["check"], prog)
