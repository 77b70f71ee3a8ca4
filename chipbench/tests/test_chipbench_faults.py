"""A whole run at a CPU-sized cell, past the harness's look for a chip: the
program as it is comes out correct, and each planted fault does not."""
import time

import pytest

from chipbench import faults, harness
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


class _NoProfiler:
    """Stands in for the profiler, which a CPU run cannot read devices
    from: a window with one device and no op on it."""

    def start(self):
        pass

    def stop(self):
        return {"devices": [{"name": "/device:TPU:0", "ops": [],
                             "modules": []}],
                "host": [["cb.window", 0, 1]]}


def test_window_counters_are_the_windows_own(cell, monkeypatch):
    """The program's counters are taken across the window alone: the load
    and the warm-up, which call the same methods, are not in them."""
    seen = []

    class Kept(harness.Readings):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            seen.append(self)

    monkeypatch.setattr(harness, "Capture", _NoProfiler)
    monkeypatch.setattr(harness, "Readings", Kept)
    out = harness.run_cell(cell, SEED + 2, 0.5, True,
                           t_start=time.perf_counter(), log=lambda m: None)
    assert out["correct"], out["checks"]
    r, = seen
    assert r.calls["update"] > 0
    assert r.counters["pool.upsert.calls"] == r.calls["update"]
    assert r.counters["pool.search.calls"] == r.calls["get"]
    assert r.counters["pool.search.keys"] == r.ops["get"]
    assert r.counters["pool.upsert.attempts"] >= r.calls["update"]
    assert {"upsert_attempts_per_call", "upsert_dispatches_per_call",
            "search_shadowed_pct", "host_syncs_per_call",
            "d2h_bytes_per_op"} <= set(out["metrics"])
