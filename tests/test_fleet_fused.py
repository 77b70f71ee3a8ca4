"""Differential replay oracle for the fused fleet tick (core/fleet.py +
heap.DMPool.exec_fused_tick).

The fused path executes a tick's READ/WRITE/CAS/FAA sweeps as ONE pool
dispatch over the flat region slab; the per-kind ``*_batch`` path is the
oracle.  The contract under test: a same-seed run is **bit-identical**
under both — final pool bytes, ``health()`` views, per-kind verb
counters, per-MN byte accounting, and the full per-op history — across
YCSB-A/C/E mixes, a churn fault storm, and an ``add_mn`` fired mid-run
(whose migration dual-write window forces the per-tick fallback, so the
mixed fused/fallback schedule is covered too).  A recording tracer must
force the fallback rather than silently dropping verbs.
"""
import json

import numpy as np
import pytest

from repro.core import (OK, ClientCrashed, DMConfig, FaultPlan,
                        FuseeCluster, Op)
from repro.obs import deterministic_view


# --------------------------------------------------------------- signatures
def _pool_bytes(cl):
    """Every hosted region copy, canonically ordered: the byte-level
    ground truth the fused and oracle paths must agree on."""
    pool = cl.pool
    return b"".join(np.ascontiguousarray(mn.regions[g]).tobytes()
                    for mn in pool.mns for g in sorted(mn.regions))


def _counter_signature(fleet):
    """Every engine counter that must not depend on the execution path.
    ``array_calls`` (the fusion's whole point) and the fused/fallback
    tick tallies are intentionally excluded."""
    c = fleet.stats()
    keys = [k for k in c if k.startswith("verbs")] + [
        "ticks", "master_calls", "max_lanes", "index_probe_verbs",
        "ord_leaf_verbs", "probe_invocations", "probe_keys", "probe_hits",
        "scan_locate_invocations", "scan_locate_keys"]
    return {k: c[k] for k in keys}


def _health_signature(cl):
    h = cl.health()
    return (h.epoch, h.tick, h.crashed_ops, h.client_recoveries,
            h.mn_recoveries,
            tuple((m.mid, m.alive, m.primary_regions, m.hosted_regions,
                   m.bytes_served) for m in h.mns),
            tuple((c.cid, c.status, c.epoch, c.inflight, c.cache_entries,
                   c.completed_ops, c.crashed_ops) for c in h.clients))


def _history_signature(cl):
    return tuple(
        (r.cid, r.op_id, r.kind, r.key, r.inv_tick, r.resp_tick, r.rtts,
         r.bg_rtts, r.result.status,
         tuple(r.result.value) if isinstance(r.result.value, list) else None)
        for r in cl.scheduler.history if r.result is not None)


def _metrics_signature(cl):
    """The whole metrics registry minus the path-dependent names
    (PATH_DEPENDENT): latency histograms, per-MN load series, heat
    sketch, flight-derived counters — all must be bit-identical across
    the fused and oracle paths."""
    return json.dumps(deterministic_view(cl.metrics()), sort_keys=True)


def _signature(cl, fleet):
    return (_pool_bytes(cl), _health_signature(cl), _history_signature(cl),
            _counter_signature(fleet), tuple(cl.pool.mn_bytes.tolist()),
            _metrics_signature(cl))


def _assert_differential(run, *, expect_fused_ticks=True):
    """Run a scenario twice (oracle, then fused) and compare signatures
    component-wise."""
    cl_o, fl_o = run(fused=False)
    cl_f, fl_f = run(fused=True)
    sig_o, sig_f = _signature(cl_o, fl_o), _signature(cl_f, fl_f)
    for name, a, b in zip(("pool_bytes", "health", "history", "counters",
                           "mn_bytes", "metrics"), sig_o, sig_f):
        assert a == b, f"fused/oracle divergence in {name}"
    if expect_fused_ticks:
        assert fl_f.stats()["fused_ticks"] > 0
    assert fl_o.stats()["fused_ticks"] == 0
    # the fusion must not cost MORE dispatches than the per-kind path
    assert fl_f.stats()["array_calls"] <= fl_o.stats()["array_calls"]
    return cl_o, fl_o, cl_f, fl_f


# ----------------------------------------------------------- YCSB scenarios
def _mk_ycsb_runner(mix_name, seed, *, n_clients=24, n_keys=64,
                    ops_per_client=6):
    from benchmarks.common import MAX_SCAN_LEN, YCSB, fleet_dmconfig

    mix = YCSB[mix_name]
    has_scan = "scan" in mix

    def run(*, fused):
        cfg = fleet_dmconfig(n_clients, n_keys, ordered=has_scan)
        cl = FuseeCluster(cfg, num_clients=n_clients, seed=seed)
        fleet = cl.fleet(fused=fused)
        sched = cl.scheduler
        backends = [cl.store(c, max_inflight=0).backend
                    for c in range(n_clients)]
        for k in range(n_keys):
            sched.submit(k % n_clients, "insert", k, [k])
        fleet.run()
        wl = cl.rng.stream("workload")
        kinds = sorted(mix)
        probs = np.array([mix[k] for k in kinds], float)
        probs /= probs.sum()
        plans = [[] for _ in range(n_clients)]
        fresh = n_keys
        for i in range(n_clients * ops_per_client):
            kind = kinds[int(wl.choice(len(kinds), p=probs))]
            if kind == "insert":
                key, fresh = fresh, fresh + 1
            else:
                key = int(wl.integers(n_keys))
            if kind == "scan":
                val = 1 + int(wl.integers(MAX_SCAN_LEN))
            elif kind in ("insert", "update"):
                val = [i, i]
            else:
                val = None
            plans[i % n_clients].append(Op(kind, key, val))
        cursor = [0] * n_clients
        while True:
            wave = []
            for c in range(n_clients):
                room = 4 - sched.inflight(c)
                if room > 0 and cursor[c] < len(plans[c]):
                    ops = plans[c][cursor[c]:cursor[c] + room]
                    cursor[c] += len(ops)
                    wave.append((backends[c], ops))
            if wave:
                fleet.submit_wave(wave)
            if not sched.has_work():
                break
            fleet.tick()
        return cl, fleet

    return run


@pytest.mark.parametrize("mix_name,seed", [
    ("A", 0), ("A", 7), ("C", 0), ("C", 3), ("E", 0), ("E", 5)])
def test_fused_matches_oracle_ycsb(mix_name, seed):
    _assert_differential(_mk_ycsb_runner(mix_name, seed))


# ------------------------------------------------------------- churn storm
def _mk_storm_runner(seed):
    n_clients, n_mns, repl, total_ops = 6, 5, 3, 120

    def run(*, fused):
        cl = FuseeCluster(DMConfig(num_mns=n_mns, replication=repl,
                                   region_words=1 << 15, regions_per_mn=16,
                                   index_shards=4),
                          num_clients=n_clients, seed=seed)
        plan = FaultPlan.storm(cl.rng.stream("faults"),
                               clients=range(n_clients), mns=n_mns,
                               replication=repl, n_client_crashes=2,
                               n_mn_crashes=1, n_add_mns=1,
                               remove_added=True, first_op=10, spacing=14,
                               recover_delay=8)
        cl.inject(plan)
        fleet = cl.fleet(fused=fused)
        stores = {c: cl.store(c, max_inflight=0) for c in range(n_clients)}
        submitted = 0
        while submitted < total_ops:
            for c in range(n_clients):
                if submitted >= total_ops:
                    break
                k = submitted
                submitted += 1
                try:
                    stores[c].submit(Op.put(k, [k, c]))
                except ClientCrashed:
                    pass
            for _ in range(4):
                if cl.scheduler.has_work():
                    fleet.tick()
        fleet.run()
        if cl.migrator.busy:
            cl.migrator.drive()
            fleet.run()
        return cl, fleet

    return run


@pytest.mark.parametrize("seed", [0, 8, 15])
def test_fused_matches_oracle_churn_storm(seed):
    # the storm mixes fused ticks with forced fallbacks (migration
    # dual-write windows) and covers crash/recover of clients and MNs —
    # including the loser-reset seeds the model checker pinned
    _cl_o, _fl_o, _cl_f, fl_f = _assert_differential(_mk_storm_runner(seed))
    assert fl_f.stats()["fused_ticks"] > 0


# ------------------------------------------------------------ add_mn midrun
def _mk_add_mn_runner(seed):
    from benchmarks.common import fleet_dmconfig
    import dataclasses
    n_clients, n_keys = 16, 96

    def run(*, fused):
        cfg = dataclasses.replace(
            fleet_dmconfig(n_clients, n_keys, n_mns=3, replication=2),
            index_shards=8)
        cl = FuseeCluster(cfg, num_clients=n_clients, seed=seed)
        fleet = cl.fleet(fused=fused)
        sched = cl.scheduler
        backends = [cl.store(c, max_inflight=0).backend
                    for c in range(n_clients)]
        for k in range(n_keys):
            sched.submit(k % n_clients, "insert", k, [k])
        fleet.run()
        wl = cl.rng.stream("workload")
        plans = [[] for _ in range(n_clients)]
        for i in range(n_clients * 10):
            kind = "update" if wl.random() < 0.5 else "search"
            key = int(wl.integers(n_keys))
            plans[i % n_clients].append(
                Op(kind, key, [i] if kind == "update" else None))
        cursor, tick, added = [0] * n_clients, 0, False
        while True:
            wave = []
            for c in range(n_clients):
                room = 4 - sched.inflight(c)
                if room > 0 and cursor[c] < len(plans[c]):
                    ops = plans[c][cursor[c]:cursor[c] + room]
                    cursor[c] += len(ops)
                    wave.append((backends[c], ops))
            if wave:
                fleet.submit_wave(wave)
            if tick == 6 and not added:
                cl.add_mn(wait=False)
                added = True
            if not sched.has_work() and not cl.migrator.busy:
                break
            fleet.tick()
            tick += 1
        assert added
        return cl, fleet

    return run


@pytest.mark.parametrize("seed", [0, 11])
def test_fused_matches_oracle_add_mn_midrun(seed):
    _cl_o, _fl_o, _cl_f, fl_f = _assert_differential(_mk_add_mn_runner(seed))
    # the dual-write migration window must have forced per-tick fallbacks
    assert fl_f.stats()["fallback_ticks"] > 0


# ------------------------------------------------- tracer fallback contract
def test_recording_tracer_forces_fallback_not_drop():
    """With a recording tracer attached, a fused engine must fall back to
    the instrumented oracle path — every verb recorded, zero fused ticks
    — and must resume fusing once the tracer detaches."""
    def run(*, fused, trace):
        cl = FuseeCluster(DMConfig(), num_clients=8, seed=2)
        if trace:
            cl.attach_tracer()
        fleet = cl.fleet(fused=fused)
        for c in range(8):
            for k in range(4):
                cl.scheduler.submit(c, "insert", 10 * c + k, [c, k])
        fleet.run()
        return cl, fleet

    cl_t, fl_t = run(fused=True, trace=True)
    assert fl_t.stats()["fused_ticks"] == 0
    assert fl_t.stats()["fallback_ticks"] > 0
    cl_o, fl_o = run(fused=False, trace=True)
    # identical recorded verb streams: nothing was dropped
    ev_t, ev_o = cl_t.pool._tracer.events(), cl_o.pool._tracer.events()
    assert set(ev_t) == set(ev_o)
    for k in ev_t:
        assert np.array_equal(ev_t[k], ev_o[k]), k
    # detached tracer: fusing resumes
    cl_d, fl_d = run(fused=True, trace=False)
    assert fl_d.stats()["fused_ticks"] > 0
    assert _pool_bytes(cl_d) == _pool_bytes(cl_t)


def test_fused_engine_is_deterministic():
    run = _mk_ycsb_runner("A", 4)
    cl_a, fl_a = run(fused=True)
    cl_b, fl_b = run(fused=True)
    assert _signature(cl_a, fl_a) == _signature(cl_b, fl_b)


# ------------------------------------------------------------- 32k smoke
@pytest.mark.slow
def test_fused_fleet_32k_clients_smoke():
    """The scale headline: a 32768-client fused YCSB-C run completes at
    interactive wall-clock with ~1 array dispatch per tick."""
    from benchmarks.common import YCSB, run_fleet_workload
    st = run_fleet_workload(n_clients=32768, mix=YCSB["C"], seed=13,
                            ops_per_client=2, n_keys=8192,
                            read_dist="zipfian")
    assert st.n_ops == 32768 * 2
    assert st.array_calls_per_tick <= 1.5
    assert st.wall_s <= 60, f"32k fused run took {st.wall_s:.1f}s"
