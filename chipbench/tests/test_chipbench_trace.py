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


# the program's kv.* spans of ``fixtures/trace_kv.json``, added to TRACE's
# host events: around cb.pool.search and cb.codec.decode_value, inside
# cb.pool.search and cb.update, and one after the window
KV = json.loads((Path(__file__).parent / "fixtures" /
                 "trace_kv.json").read_text())["host"]
TRACE_KV = {**TRACE, "host": TRACE["host"] + KV}


def test_span_totals_self_times_and_calls():
    r = Reduction(TRACE_KV)
    ns = 1e-9
    assert r.span_calls["kv.pool.upsert.wait"] == 2
    assert r.span_calls["kv.pool.search"] == 1         # one is after it
    assert r.span_total_s == pytest.approx({
        "cb.get": 4000 * ns, "kv.backend.search": 3000 * ns,
        "cb.pool.search": 2200 * ns, "kv.pool.search": 2100 * ns,
        "kv.pool.search.probe": 500 * ns, "kv.pool.search.wait": 1200 * ns,
        "kv.pool.search.collision": 300 * ns,
        "kv.pool.search.collision.wait": 100 * ns,
        "kv.codec.decode": 700 * ns, "cb.codec.decode_value": 600 * ns,
        "cb.update": 4500 * ns, "kv.pool.upsert": 2500 * ns,
        "kv.pool.upsert.wait": 1700 * ns, "kv.pool.reclaim.wait": 100 * ns})
    # less the spans directly inside, of either family
    assert r.span_self_s == pytest.approx({
        "cb.get": 300 * ns, "kv.backend.search": 800 * ns,
        "cb.pool.search": 100 * ns, "kv.pool.search": 100 * ns,
        "kv.pool.search.probe": 500 * ns, "kv.pool.search.wait": 1200 * ns,
        "kv.pool.search.collision": 200 * ns,
        "kv.pool.search.collision.wait": 100 * ns,
        "kv.codec.decode": 100 * ns, "cb.codec.decode_value": 600 * ns,
        "cb.update": 1700 * ns, "kv.pool.upsert": 1000 * ns,
        "kv.pool.upsert.wait": 1700 * ns, "kv.pool.reclaim.wait": 100 * ns})


def test_idle_time_goes_to_the_innermost_span_of_either_family():
    r = Reduction(TRACE_KV)
    assert r.busy_s == pytest.approx(Reduction(TRACE).busy_s)
    assert r.idle == pytest.approx({
        "cb.get": 200e-9, "kv.backend.search": 400e-9,
        "cb.pool.search": 100e-9, "kv.pool.search": 100e-9,
        "kv.pool.search.probe": 500e-9, "kv.pool.search.wait": 200e-9,
        "kv.pool.search.collision": 200e-9,
        "kv.pool.search.collision.wait": 100e-9,
        "kv.codec.decode": 100e-9, "cb.codec.decode_value": 600e-9,
        "cb.update": 1700e-9, "kv.pool.upsert": 1000e-9,
        "kv.pool.upsert.wait": 700e-9, "kv.pool.reclaim.wait": 100e-9,
        "harness": 1500e-9})
    assert sum(r.idle.values()) == pytest.approx(7500e-9)


OLD_READERS = ["frontend_us_per_op", "codec_us_per_op", "pool_search_ms",
               "pool_upsert_ms", "race_lookup_roofline",
               "snapshot_epoch_roofline", "device_idle_pct",
               "window_compiles"]


@pytest.mark.parametrize("name", OLD_READERS)
def test_first_readers_read_the_same_with_the_programs_spans(name):
    spans = Spans()
    for n in ("cb.pool.search", "cb.pool.insert_batch",
              "cb.codec.decode_value"):
        spans.seconds[n], spans.calls[n] = 0.002, 2
    spans.sizes["cb.pool.search"] = [10, 10]
    kw = dict(ops={"get": 10, "update": 10}, calls={"get": 2, "update": 2},
              frontend_s=0.01, spans=spans)
    old = _read(name, _readings(**kw))
    assert old is not None
    assert _read(name, _readings(trace=Reduction(TRACE_KV), **kw)) == old


COUNTERS = {"pool.upsert.calls": 4, "pool.upsert.attempts": 5,
            "pool.upsert.dispatches": 20, "pool.search.keys": 200,
            "pool.search.shadowed": 3, "pool.host_syncs": 14,
            "pool.d2h_bytes": 9000}


@pytest.mark.parametrize("name,want", [
    ("upsert_attempts_per_call", 5 / 4),
    ("upsert_dispatches_per_call", 20 / 4),
    ("upsert_wait_ms", (1700 + 100) * 1e-6 / 4),    # ns -> ms, 4 calls
    ("search_wait_ms", (1200 + 100) * 1e-6 / 4),
    ("search_shadowed_pct", 100 * 3 / 200),
    ("host_syncs_per_call", 14 / 8),
    ("d2h_bytes_per_op", 9000 / 300),
])
def test_program_readers(name, want):
    r = _readings(ops={"get": 200, "update": 100},
                  calls={"get": 4, "update": 4}, trace=Reduction(TRACE_KV),
                  counters=COUNTERS)
    assert _read(name, r) == pytest.approx(want)


NEW_READERS = ["upsert_attempts_per_call", "upsert_dispatches_per_call",
               "upsert_wait_ms", "search_wait_ms", "search_shadowed_pct",
               "host_syncs_per_call", "d2h_bytes_per_op"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_program_readers_return_nothing_when_nothing_ran(name):
    idle = _readings(ops={}, calls={}, trace=Reduction(TRACE_KV),
                     counters={k: 0 for k in COUNTERS})
    assert _read(name, idle) is None
    # calls, but not one of the program's spans or counters to read
    blind = _readings(ops={"get": 200, "update": 100},
                      calls={"get": 4, "update": 4})
    assert _read(name, blind) is None


def test_read_xspace_keeps_both_families_of_host_spans(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench.trace import read_xspace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("cb.window"):
            with TraceAnnotation("kv.pool.search"):
                with TraceAnnotation("other.span"):
                    jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    names = [e[0] for e in read_xspace(str(path))["host"]]
    assert sorted(names) == ["cb.window", "kv.pool.search"]
