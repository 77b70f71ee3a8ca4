"""The trace reduction gives known busy, idle and kernel times on a small
synthetic trace (``fixtures/trace_small.json``, times in ns)."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import harness
from chipbench.spans import Spans
from chipbench.trace import Reduction, merge

TRACE = json.loads((Path(__file__).parent / "fixtures" /
                    "trace_small.json").read_text())


def test_busy_idle_and_window():
    r = Reduction(TRACE)
    assert r.window_s == pytest.approx(10000e-9)
    # [1000,1500] + [2500,3500] (two ops overlap) + [7000,8000]; the op
    # after the window is left out
    assert r.busy_s == pytest.approx(2500e-9)
    assert sum(r.idle.values()) == pytest.approx(7500e-9)


def test_idle_time_goes_to_the_innermost_open_span():
    r = Reduction(TRACE)
    # cb.get holds [1000,1800] [4000,4200] [4800,5000], busy [1000,1500];
    # cb.pool.search [1800,4000], busy [2500,3500]; cb.update [6000,10500],
    # busy [7000,8000]; no span is open in [5000,6000] and [10500,11000]
    assert r.idle == pytest.approx({"cb.get": 700e-9,
                                    "cb.pool.search": 1200e-9,
                                    "cb.codec.decode_value": 600e-9,
                                    "cb.update": 3500e-9,
                                    "harness": 1500e-9})
    assert [k for k, _ in r.top_idle(3)] == ["cb.update", "harness",
                                             "cb.pool.search"]


def test_module_seconds_are_clipped_to_the_window():
    r = Reduction(TRACE)
    assert r.module_seconds("jit_race_lookup") == pytest.approx(1500e-9)
    assert r.module_seconds("jit_snapshot_epoch") == pytest.approx(1000e-9)
    assert r.module_seconds("jit_copy") == 0
    assert r.top_modules()[0] == ["jit_race_lookup", pytest.approx(1500e-9)]


def test_merge():
    s, e = merge(np.array([5., 0., 1., 9.]), np.array([6., 2., 3., 10.]))
    assert s.tolist() == [0., 5., 9.] and e.tolist() == [3., 6., 10.]


def test_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        Reduction({**TRACE, "host": TRACE["host"][1:]})
    with pytest.raises(ValueError):
        Reduction({**TRACE, "devices": []})


def _readings(**kw):
    cell = harness.Cell("t", {"pool": {"slots_per_bucket": 8,
                                       "replicas": 3}}, {}, 1, [], [])
    spans = Spans()
    spans.sizes["cb.pool.search"] = [10]
    base = dict(cell=cell, ops={"get": 10}, calls={"get": 1},
                frontend_s=0.0, spans=spans, epoch_writers=[6],
                compiles=0, trace=Reduction(TRACE),
                peaks={"hbm_bytes_per_s": 1e9})
    base.update(kw)
    return harness.Readings(**base)


def _read(name, r):
    return harness.load_file(harness.HERE / "layer_metrics" /
                             f"{name}.py").read(r)


def test_roofline_and_idle_readers():
    r = _readings()
    # 730 B at 1 GB/s is 730 ns, over 1500 ns of jit_race_lookup
    assert _read("race_lookup_roofline", r) == pytest.approx(100 * 730 / 1500)
    # 6 writers x 45 B is 270 ns, over 1000 ns of jit_snapshot_epoch
    assert _read("snapshot_epoch_roofline", r) == pytest.approx(27.0)
    assert _read("device_idle_pct", r) == pytest.approx(75.0)


def test_readers_return_nothing_when_nothing_ran():
    r = _readings(epoch_writers=[])
    r.spans.sizes.clear()
    assert _read("race_lookup_roofline", r) is None
    assert _read("snapshot_epoch_roofline", r) is None
    assert _read("pool_search_ms", r) is None
    assert _read("pool_upsert_ms", r) is None
    assert _read("codec_us_per_op", r) is None
