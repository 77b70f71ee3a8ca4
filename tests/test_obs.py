"""Telemetry determinism + round-trip tests for the observability layer
(repro.obs): registry semantics, same-seed bit-identical snapshots,
flight-recorder dumps (one per injected fault class), and the Perfetto /
JSON exporters' load round-trips."""
import json
import os

import numpy as np
import pytest

from repro.core import DMConfig, FaultPlan, FuseeCluster, Op
from repro.obs import (EV_FAULT, FlightRecorder, Histogram, Registry,
                       deterministic_view, flight_to_perfetto, load_flight,
                       load_metrics, load_perfetto, metrics_to_json,
                       snapshot_diff, snapshot_merge)


# ------------------------------------------------------------ registry units
def test_histogram_log2_buckets():
    h = Histogram("t", "ticks", n_buckets=8)
    h.observe_many(np.array([0, 1, 2, 3, 4, 7, 8, 1 << 40]))
    # bucket 0={0}, 1={1}, 2=[2,3], 3=[4,7], 4=[8,15], last absorbs overflow
    assert h.counts.tolist() == [1, 1, 2, 2, 1, 0, 0, 1]
    assert h.total == 8
    assert h.upper_edges().tolist() == [0, 1, 3, 7, 15, 31, 63, 127]


def test_histogram_percentiles_conservative():
    h = Histogram("t", "ticks")
    h.observe_many(np.full(99, 2))       # bucket [2,3] -> upper edge 3
    h.observe(1000)                      # [512,1023] -> upper edge 1023
    assert h.percentile(0.5) == 3
    assert h.percentile(0.99) == 3
    assert h.percentile(0.9999) == 1023
    assert Histogram("e").percentile(0.5) == 0


def test_registry_type_conflict_and_snapshot_shape():
    r = Registry()
    r.counter("a.x").inc(3)
    r.gauge("a.g").set_max(7)
    r.histogram("a.h", "rtts").observe(5)
    r.series("a.s", ("tick", "v")).append_rows(np.array([[1.0, 2.0]]))
    r.heat("a.heat", 8).touch(3)
    with pytest.raises(TypeError):
        r.gauge("a.x")
    snap = r.snapshot()
    assert snap["counters"] == {"a.x": 3}
    assert snap["gauges"] == {"a.g": 7}
    assert snap["histograms"]["a.h"]["unit"] == "rtts"
    assert snap["series"]["a.s"]["rows"] == [[1.0, 2.0]]
    assert snap["heat"]["a.heat"][3] == 1
    json.dumps(snap)                     # JSON-pure by construction


def test_snapshot_diff_and_merge():
    r = Registry()
    c = r.counter("n")
    h = r.histogram("h")
    old = r.snapshot()
    c.inc(5)
    h.observe(4)
    new = r.snapshot()
    d = snapshot_diff(new, old)
    assert d["counters"]["n"] == 5
    assert sum(d["histograms"]["h"]["counts"]) == 1
    m = snapshot_merge(new, new)
    assert m["counters"]["n"] == 10
    assert sum(m["histograms"]["h"]["counts"]) == 2


def test_series_ring_wraps_keeping_newest():
    r = Registry()
    s = r.series("s", ("t",), capacity=4)
    s.append_rows(np.arange(6, dtype=np.float64)[:, None])
    assert s.rows()[:, 0].tolist() == [2.0, 3.0, 4.0, 5.0]
    assert s.dropped == 2


def test_flight_ring_wrap_and_roundtrip(tmp_path):
    fr = FlightRecorder(capacity=4)
    rows = np.arange(6 * 10, dtype=np.int64).reshape(6, 10)
    fr.push_rows(rows)
    ev = fr.events()
    assert fr.dropped == 2
    assert ev["tick"].tolist() == rows[2:, 0].tolist()   # oldest dropped
    path = str(tmp_path / "f.npz")
    fr.save(path, ["alpha", "beta"])
    dump = load_flight(path)
    assert dump["labels"] == ["alpha", "beta"]
    assert dump["dropped"] == 2
    assert dump["tick"].tolist() == ev["tick"].tolist()


# ------------------------------------------------------- cluster determinism
def _seeded_run(seed, *, dump_dir=None):
    cl = FuseeCluster(DMConfig(num_mns=4, replication=3), num_clients=3,
                      seed=seed, obs_dump_dir=dump_dir)
    kv = cl.store(0)
    for i in range(30):
        kv.put(f"k{i}", f"v{i}")
    for i in range(30):
        kv.get(f"k{i}")
    kv.drain()
    return cl


def test_same_seed_metrics_bit_identical():
    a = json.dumps(_seeded_run(11).metrics(), sort_keys=True)
    b = json.dumps(_seeded_run(11).metrics(), sort_keys=True)
    assert a == b


def test_metrics_snapshot_contents():
    cl = _seeded_run(3)
    m = cl.metrics()
    assert m["counters"]["op.settled"] == 60
    assert m["counters"]["op.begun"] == 60
    assert m["counters"]["op.crashed"] == 0
    # latency histograms per kind, plus the percentile summary
    ins = m["histograms"]["op.lat_ticks.kind.insert"]
    assert ins["unit"] == "ticks" and sum(ins["counts"]) == 30
    p = m["percentiles"]["op.lat_rtts.kind.search"]
    assert p["count"] == 30 and p["p50"] >= 1 and p["p99"] >= p["p50"]
    # heat sketch saw the cache path
    assert sum(m["heat"]["cache.heat"]) > 0
    # per-shard and per-MN attribution dimensions exist
    assert any(k.startswith("op.lat_ticks.shard.")
               for k in m["histograms"])
    assert any(k.startswith("op.lat_ticks.mn.") for k in m["histograms"])


def test_detached_hub_records_nothing_new():
    cl = _seeded_run(5)
    before = cl.metrics()["counters"]["op.settled"]
    cl.detach_obs()
    assert cl.scheduler.obs is None and cl.pool._obs is None
    kv = cl.store(1)
    for i in range(5):
        kv.put(f"d{i}", b"x")
    kv.drain()
    assert cl.metrics()["counters"]["op.settled"] == before
    cl.attach_obs()
    kv.put("post", b"y")
    kv.drain()
    assert cl.metrics()["counters"]["op.settled"] == before + 1


def test_legacy_counters_deprecated_but_live():
    """The one-release ``counters`` views are gone; ``stats()`` reads the
    same registry handles, and writing to its dict changes no metric."""
    cl = _seeded_run(1)
    fleet = cl.fleet()
    assert not hasattr(fleet, "counters")
    assert not hasattr(cl.migrator, "counters")
    c = fleet.stats()
    ticks = cl.scheduler.metrics.get("fleet.ticks").value
    assert c["ticks"] == ticks
    c["ticks"] = 5
    assert fleet.stats()["ticks"] == ticks
    snap = cl.metrics()["counters"]
    mig = cl.migrator.stats()
    assert mig and mig == {k: snap["migrate." + k] for k in mig}


def test_fleet_run_populates_series_and_heat():
    from benchmarks.common import YCSB, fleet_dmconfig
    n = 16
    cfg = fleet_dmconfig(n, 128)
    cl = FuseeCluster(cfg, num_clients=n, seed=2)
    fleet = cl.fleet()
    backends = [cl.store(c, max_inflight=0).backend for c in range(n)]
    for k in range(128):
        cl.scheduler.submit(k % n, "insert", k, [k])
    fleet.run()
    for r in range(6):                     # several windows of GET waves
        fleet.submit_wave([(be, [Op.get(int(k)) for k in range(8)])
                           for be in backends])
        fleet.run()
    m = cl.metrics()
    rows = m["series"]["mn.load"]["rows"]
    assert rows, "per-MN series never sampled"
    fields = m["series"]["mn.load"]["fields"]
    assert fields == ["tick", "mid", "bytes", "verbs", "qdepth",
                      "cpu_ops", "util"]
    by = {f: i for i, f in enumerate(fields)}
    assert sum(r[by["bytes"]] for r in rows) > 0
    assert sum(r[by["verbs"]] for r in rows) > 0
    assert all(r[by["util"]] >= 0 for r in rows)
    assert sum(m["heat"]["cache.heat"]) > 0
    top = cl.obs.heat.top(4)
    assert top and top[0][1] >= top[-1][1]


# ------------------------------------------------------- dumps + fault storm
def test_storm_dumps_once_per_fault_class(tmp_path):
    dump_dir = str(tmp_path / "dumps")
    cl = FuseeCluster(DMConfig(num_mns=4, replication=3), num_clients=3,
                      seed=9, obs_dump_dir=dump_dir)
    plan = (FaultPlan().crash_mn(2, after_ops=12)
            .crash_client(0, after_ops=18)
            .crash_client(1, after_ops=24)       # same class: no second dump
            .recover_client(0, after_ops=30))
    cl.inject(plan)
    kv = cl.store(2)
    for i in range(60):
        kv.put(i, [i])
    kv.drain()
    files = sorted(os.listdir(dump_dir))
    classes = {f.split("_t")[0] for f in files}
    assert classes == {"flight_fault_crash_mn", "flight_fault_crash_client",
                       "flight_fault_recover_client"}
    assert len(files) == 3                # exactly one per fault class
    dump = load_flight(os.path.join(dump_dir, files[0]))
    assert (dump["etype"] == EV_FAULT).sum() >= 1
    # fault labels intern alongside op kinds
    assert "crash_mn" in dump["labels"]


def test_undumped_cluster_never_writes(tmp_path):
    cl = _seeded_run(4)                   # no dump_dir: disarmed
    assert cl.obs.dump("anything") is None
    cl.crash_mn(1)
    assert cl.obs.dumped == {}


# ------------------------------------------------------------------ exports
def test_metrics_json_roundtrip(tmp_path):
    cl = _seeded_run(6)
    path = str(tmp_path / "m.json")
    metrics_to_json(cl.metrics(), path)
    m = load_metrics(path)
    assert m == json.loads(json.dumps(cl.metrics(), sort_keys=True))


def test_perfetto_export_roundtrip(tmp_path):
    dump_dir = str(tmp_path / "d")
    cl = FuseeCluster(DMConfig(num_mns=3, replication=2, index_shards=4),
                      num_clients=2, seed=8, obs_dump_dir=dump_dir)
    kv = cl.store(0)
    for i in range(20):
        kv.put(i, [i])
    kv.drain()
    cl.crash_mn(2)                        # fault instant + Alg-3 recovery
    kv2 = cl.store(1)
    for i in range(10):
        kv2.put(100 + i, [i])
    kv2.drain()
    cl.add_mn()                           # migration windows (start->cutover)
    path = cl.obs.dump("manual", force=True)
    trace = flight_to_perfetto(load_flight(path),
                               str(tmp_path / "trace.json"))
    loaded = load_perfetto(str(tmp_path / "trace.json"))
    assert loaded == trace
    cats = {e.get("cat") for e in trace["traceEvents"]}
    assert {"op", "fault", "migration"} <= cats
    ops = [e for e in trace["traceEvents"] if e.get("cat") == "op"]
    assert ops and all(e["ph"] == "X" and e["dur"] > 0 for e in ops)
    migs = [e for e in trace["traceEvents"] if e.get("cat") == "migration"]
    assert any(e["args"]["phase"] == "cutover" for e in migs)
    # ts ordering is deterministic
    ts = [e.get("ts", 0) for e in trace["traceEvents"]]
    assert ts == sorted(ts)
    with pytest.raises(ValueError):
        json_path = str(tmp_path / "bogus.json")
        with open(json_path, "w") as f:
            json.dump({"nope": 1}, f)
        load_perfetto(json_path)


def test_deterministic_view_drops_path_dependent():
    cl = _seeded_run(2)
    cl.fleet()                            # registers fleet.* counters
    v = deterministic_view(cl.metrics())
    assert "fleet.fused_ticks" not in v["counters"]
    assert "fleet.array_calls" not in v["counters"]
    assert "op.settled" in v["counters"]


def test_serving_metrics_twin():
    pytest.importorskip("jax")
    from repro.serving import PoolConfig, ServeEngine

    class _Stub:                          # never stepped: metrics-only engine
        def decode_step(self, params, cache, token):
            raise NotImplementedError

    eng = ServeEngine(_Stub(), None, max_batch=2,
                      pool_cfg=PoolConfig(n_pages=64, n_buckets=32,
                                          slots_per_bucket=4))
    m = eng.metrics()
    assert set(m) == {"counters", "gauges", "histograms", "series", "heat"}
    assert all(k.startswith("serve.") for k in m["counters"])
    assert m["gauges"]["serve.slots_free"] == 2
    json.dumps(m)
    merged = snapshot_merge(m, m)
    assert merged["gauges"]["serve.slots_free"] == 2
