"""A whole run at a CPU-sized cell, past the harness's look for a chip: the
program as it is comes out correct, and each planted fault does not."""
import pytest

from chipbench import faults
from chipbench.tests import tiny

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def cell():
    return tiny.cell("ycsb-a")


def test_sound_run_is_correct(cell):
    out = tiny.run(cell, SEED)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ops_per_s", "get_p95_ms",
                                   "update_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_sound_run_with_ycsb_string_keys_is_correct():
    """YCSB's own keys go through the store; 2,000 of them do not alias."""
    out = tiny.run(tiny.cell("ycsb-b", key_format="ycsb"), SEED + 1)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("fault,check", [
    ("unreplicated", "unconverged_slots"),
    ("stale_state", "wrong_answers"),
    ("half_batch", "wrong_answers"),
    ("altered_answer", "wrong_answers"),
])
def test_planted_fault_is_not_correct(cell, fault, check):
    undo = faults.install(fault)
    try:
        out = tiny.run(cell, SEED)
    finally:
        undo()
    assert not out["correct"]
    assert out["checks"][check]["value"] > 0, out["checks"]
