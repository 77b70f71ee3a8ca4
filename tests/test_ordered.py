"""Ordered secondary index (core/ordered.py): scan/range correctness,
leaf splits under concurrency, crash-mid-split repair, migration cutover,
fleet-wide batched locates, the serving twin, and the seeded scan-storm
acceptance invariants (no acked insert lost, no torn scans, bit-identical
same-seed replay)."""
import os

import numpy as np
import pytest

from repro.core import (CRASHED, OK, DMConfig, FaultPlan, FuseeCluster, Op,
                        OrderedIndexDisabled, codec, ordered)
from repro.core.api import SimBackend
from repro.core.events import NOT_FOUND

CFG = dict(num_mns=4, replication=3, ordered_index=True,
           region_words=1 << 15, regions_per_mn=16)


def _cluster(num_clients=2, seed=0, mn_detect_delay=0, **over):
    return FuseeCluster(DMConfig(**{**CFG, **over}), num_clients=num_clients,
                        seed=seed, mn_detect_delay=mn_detect_delay)


def _sound(res, start, end=None):
    """A scan result is well-formed: sorted, deduped, within range."""
    keys = [k for k, _v in res]
    assert keys == sorted(set(keys))
    assert all(k >= start for k in keys)
    if end is not None:
        assert all(k < end for k in keys)


# ----------------------------------------------------------- basic scans --
def test_scan_returns_ordered_keys_and_values():
    kv = _cluster().store(0)
    for k in range(60):
        assert kv.insert(k, [k * 3]).status == OK
    res = kv.scan(10, 20)
    assert [k for k, _ in res] == list(range(10, 30))
    assert all(v == [k * 3] for k, v in res)
    assert [k for k, _ in kv.range(40, 45)] == list(range(40, 45))


def test_scan_count_clips_at_end_of_keyspace():
    kv = _cluster().store(0)
    for k in range(10):
        kv.insert(k, [k])
    assert [k for k, _ in kv.scan(7, 50)] == [7, 8, 9]
    assert kv.scan(100, 5) == []


def test_empty_range_and_inverted_range():
    kv = _cluster().store(0)
    for k in range(10):
        kv.insert(k, [k])
    assert kv.range(5, 5) == []
    assert kv.range(7, 3) == []
    assert kv.range(100, 200) == []


def test_delete_removes_from_scans_update_keeps():
    kv = _cluster().store(0)
    for k in range(30):
        kv.insert(k, [k])
    kv.delete(11)
    kv.update(12, [999])
    res = kv.scan(10, 4)
    assert [k for k, _ in res] == [10, 12, 13, 14]
    assert dict(res)[12] == [999]


def test_scan_through_op_future_surface():
    kv = _cluster().store(0)
    for k in range(20):
        kv.insert(k, bytes([k]) * 3)
    r = kv.submit(Op.scan(5, 4)).result()
    assert r.status == OK
    assert [(k, v) for k, v in r.value] == [
        (k, bytes([k]) * 3) for k in range(5, 9)]
    r = kv.submit(Op.range(5, 8)).result()
    assert [k for k, _ in r.value] == [5, 6, 7]


def test_scan_disabled_raises_typed():
    cl = FuseeCluster(DMConfig(num_mns=2), num_clients=1)
    with pytest.raises(OrderedIndexDisabled):
        cl.store(0).scan(0, 4)


def test_byte_keys_scan_in_hashed_order():
    kv = _cluster().store(0)
    keys = [b"\xff\xfe", b"user:1", "caf\xe9", b"\x00" * 100]
    for i, k in enumerate(keys):
        assert kv.put(k, bytes([i + 1])).status == OK
    res = kv.scan(0, 10)
    assert [k for k, _ in res] == sorted(codec.encode_key(k) for k in keys)


# ----------------------------------------------- splits under concurrency --
def test_many_keys_force_splits_scan_complete():
    cl = _cluster()
    kv = cl.store(0)
    n = 200          # >> 13 entries/leaf: many splits
    for k in range(n):
        assert kv.insert(k, [k]).status == OK
    res = kv.scan(0, n)
    assert [k for k, _ in res] == list(range(n))
    # keydir whitebox agrees
    assert set(ordered.ordered_keys_direct(cl.pool)) >= set(range(n))


@pytest.mark.parametrize("seed", [1, 7, 23])
def test_scan_spanning_split_in_flight(seed):
    """A scan racing concurrent inserts (which split leaves under it) is a
    sound snapshot: sorted/deduped/in-range, contains every key committed
    BEFORE the scan began, and every value it returns was committed."""
    rng = np.random.default_rng(seed)
    cl = _cluster(num_clients=3, seed=seed)
    sched = cl.scheduler
    pre = 26         # two full leaves
    for k in range(pre):
        sched.submit(0, "insert", k, [k])
    sched.run_round_robin()
    scan_rec = sched.submit(0, "scan", 0, 500)
    for c, k in ((1, 100), (2, 101), (1, 13), (2, 14)):
        sched.submit(c, "insert", 300 + k, [k])
    for k in range(40):  # enough inserts to force splits mid-scan
        sched.submit(1 + k % 2, "insert", pre + k, [pre + k])
    sched.run_random(rng=rng)
    res = scan_rec.result
    assert res.status == OK
    _sound(res.value, 0)
    got = dict(res.value)
    for k in range(pre):
        assert k in got and got[k] == [k], f"pre-scan key {k} missing"


def test_naive_and_batched_scans_agree():
    cl = _cluster()
    kv = cl.store(0)
    for k in range(80):
        kv.insert(k, [k])
    sched, client = cl.scheduler, cl.clients[0]
    out = {}
    for mode in (True, False):
        rec = sched.submit(0, "scan", 15, 30,
                           gen=client.op_scan(15, 30, batched=mode))
        sched.run_round_robin()
        out[mode] = rec.result.value
    assert out[True] == out[False]
    assert [k for k, _ in out[True]] == list(range(15, 45))


# -------------------------------------------------------- crash + repair --
@pytest.mark.parametrize("steps", [5, 17, 33, 61, 95])
def test_crash_mid_split_no_acked_insert_lost(steps):
    """Crash a client at an arbitrary verb boundary while its inserts are
    splitting leaves; after Alg-3/§5.3 repair, a quiescent scan contains
    every ACKED insert (the half-split tree is repaired, stranded entries
    re-homed)."""
    cl = _cluster(num_clients=3, seed=steps)
    sched = cl.scheduler
    for k in range(24):      # nearly two full leaves
        sched.submit(1, "insert", k, [k])
    sched.run_round_robin()
    recs = [sched.submit(0, "insert", 24 + i, [24 + i]) for i in range(12)]
    for _ in range(steps):   # partial execution: maybe mid-split
        if not sched.eligible_cids():
            break
        sched.step(0)
    cl.crash_client(0)
    cl.recover_client(0, reassign_to_cid=1)
    cl.drain()
    acked = [24 + i for i, r in enumerate(recs)
             if r.result is not None and r.result.status == OK]
    res = cl.store(1).scan(0, 100)
    got = [k for k, _ in res]
    _sound(res, 0)
    missing = [k for k in list(range(24)) + acked if k not in got]
    assert not missing, f"committed keys missing after repair: {missing}"
    # scans agree with point reads after recovery (no torn results)
    kv1 = cl.store(1)
    for k, v in res:
        assert kv1.get(k) == v


def test_repair_reaps_unlinked_half_split_leaf():
    cl = _cluster()
    kv = cl.store(0)
    for k in range(20):
        kv.insert(k, [k])
    pool = cl.pool
    g = pool.ordered_regions[0]
    # forge a half-split: a fully-written (valid CRC) leaf that was never
    # linked — exactly what a client crash between write_leaf and link
    # leaves behind
    arrays = [pool.mns[m].regions[g] for m in pool.placement[g]]
    new_id = int(arrays[0][ordered.CURSOR_OFF])
    words = ordered.build_leaf(low=7, ver=0, next_id=0, prev=0,
                               entries=[ordered.stored(999)])
    for a in arrays:
        a[ordered.CURSOR_OFF] = np.uint64(new_id + 1)
        a[ordered.leaf_off(new_id):ordered.leaf_off(new_id) + 16] = \
            np.array([w & ordered.MASK64 for w in words], np.uint64)
    assert 999 not in [k for k, _ in kv.scan(0, 100)]   # unreachable
    ordered.repair_ordered(pool)
    lf = ordered.parse_leaf(
        arrays[0][ordered.leaf_off(new_id):ordered.leaf_off(new_id) + 16])
    assert not lf["valid"], "half-split leaf must be voided by repair"
    assert [k for k, _ in kv.scan(0, 100)] == list(range(20))


def test_repair_rehomes_stranded_entries():
    cl = _cluster()
    kv = cl.store(0)
    for k in range(40):
        kv.insert(k, [k])
    pool = cl.pool
    g = pool.ordered_regions[0]
    arrays = [pool.mns[m].regions[g] for m in pool.placement[g]]
    # strand an entry: drop key 5 into the LAST leaf (outside its fences),
    # as a crashed splitter's unfinished move would
    kv.delete(5)
    assert 5 not in [k for k, _ in kv.scan(0, 100)]
    kv.insert(5, [50])       # live again, entry in the right place
    # now strand a DIFFERENT live key: clear 17's entry and graft it into
    # the head leaf's free slot region beyond its window
    mem = arrays[0]
    n = int(mem[ordered.CURSOR_OFF])
    # clear every entry equal to stored(17) everywhere
    sv = ordered.stored(17)
    for i in range(n):
        for j in range(ordered.LEAF_ENTRIES):
            if int(mem[ordered.entry_off(i, j)]) == sv:
                for a in arrays:
                    a[ordered.entry_off(i, j)] = np.uint64(0)
    # graft into the last allocated valid leaf (wrong window w.h.p.)
    lastleaf = n - 1
    for a in arrays:
        a[ordered.entry_off(lastleaf, ordered.LEAF_ENTRIES - 1)] = \
            np.uint64(sv)
    ordered.repair_ordered(pool)
    res = cl.store(0).scan(0, 100)
    assert 17 in [k for k, _ in res], "stranded entry must be re-homed"
    _sound(res, 0)


def test_repair_salvages_acked_entries_from_primary_only_link():
    """A split whose link CAS landed only on the primary is observable
    (all reads go to replica 0): a claim acked into the new leaf before
    the crash must survive repair, even though adopt-backup reverts the
    link and the reap voids the leaf — its entries are salvaged into the
    reachable chain."""
    cl = _cluster()
    kv = cl.store(0)
    for k in range(20):
        kv.insert(k, [k])
    pool = cl.pool
    g = pool.ordered_regions[0]
    arrays = [pool.mns[m].regions[g] for m in pool.placement[g]]
    # forge: new leaf N fully replicated, linked from leaf 0 on the
    # PRIMARY only (splitter crashed before ord:link_backups), holding an
    # independently-acked claim for key 999 (live in RACE)
    assert kv.insert(999, [9990]).status == OK
    sv = ordered.stored(999)
    for a in arrays:       # remove 999's real entry wherever ensure put it
        n = int(a[ordered.CURSOR_OFF])
        for i in range(n):
            for j in range(ordered.LEAF_ENTRIES):
                if int(a[ordered.entry_off(i, j)]) == sv:
                    a[ordered.entry_off(i, j)] = np.uint64(0)
    head = ordered.parse_leaf(arrays[0][ordered.leaf_off(0):
                                        ordered.leaf_off(0) + 16])
    new_id = int(arrays[0][ordered.CURSOR_OFF])
    words = ordered.build_leaf(low=head["low"] + 1, ver=0,
                               next_id=head["next"], prev=0,
                               entries=[sv])
    for a in arrays:       # N fully replicated
        a[ordered.CURSOR_OFF] = np.uint64(new_id + 1)
        a[ordered.leaf_off(new_id):ordered.leaf_off(new_id) + 16] = \
            np.array([w & ordered.MASK64 for w in words], np.uint64)
    link = ordered.pack_meta(head["ver"] + 1, new_id,
                             ordered.leaf_crc(head["low"], head["prev"]))
    arrays[0][ordered.leaf_off(0) + 1] = np.uint64(link)  # primary ONLY
    assert 999 in [k for k, _ in kv.scan(0, 2000)]        # observable
    ordered.repair_ordered(pool)
    cl.clients[0].ord_fences = {}                         # drop stale cache
    got = [k for k, _ in cl.store(0).scan(0, 2000)]
    assert 999 in got, "acked claim in a primary-only-linked leaf lost"
    assert got == sorted(set(got))


def test_repair_rehomes_multiple_stranded_keys_across_split():
    """Re-homing several stranded keys whose covering leaf is full forces
    a master-side direct split mid-repair; the later placements must use
    the POST-split fence windows or a committed key lands outside its
    leaf's range and scans miss it."""
    cl = _cluster()
    kv = cl.store(0)
    for k in range(13):          # exactly one full leaf covering [0, inf)
        kv.insert(k, [k])
    for k in (50, 60):
        assert kv.insert(k, [k]).status == OK
    pool = cl.pool
    g = pool.ordered_regions[0]
    arrays = [pool.mns[m].regions[g] for m in pool.placement[g]]
    mem = arrays[0]
    # strand 50 and 60: clear their entries everywhere, then graft both
    # into a forged linked leaf whose low (100) excludes them
    for key in (50, 60):
        sv = ordered.stored(key)
        n = int(mem[ordered.CURSOR_OFF])
        for a in arrays:
            for i in range(n):
                for j in range(ordered.LEAF_ENTRIES):
                    if int(a[ordered.entry_off(i, j)]) == sv:
                        a[ordered.entry_off(i, j)] = np.uint64(0)
    head = ordered.parse_leaf(mem[ordered.leaf_off(0):
                                  ordered.leaf_off(0) + 16])
    new_id = int(mem[ordered.CURSOR_OFF])
    words = ordered.build_leaf(low=100, ver=0, next_id=head["next"],
                               prev=0, entries=[ordered.stored(50),
                                                ordered.stored(60)])
    link = ordered.pack_meta(head["ver"] + 1, new_id,
                             ordered.leaf_crc(head["low"], head["prev"]))
    for a in arrays:
        a[ordered.CURSOR_OFF] = np.uint64(new_id + 1)
        a[ordered.leaf_off(new_id):ordered.leaf_off(new_id) + 16] = \
            np.array([w & ordered.MASK64 for w in words], np.uint64)
        a[ordered.leaf_off(0) + 1] = np.uint64(link)
    ordered.repair_ordered(pool)
    cl.clients[0].ord_fences = {}
    got = [k for k, _ in cl.store(0).scan(0, 2000)]
    for key in list(range(13)) + [50, 60]:
        assert key in got, f"committed key {key} missing after re-home"
    # scans starting past the mid-repair split still see the re-homed keys
    assert 60 in [k for k, _ in cl.store(0).scan(14, 2000)]


def test_batch_with_scan_on_disabled_cluster_rejects_upfront():
    """OrderedIndexDisabled must fire BEFORE any op of the batch is
    accepted — no stranded futures for already-submitted ops."""
    cl = FuseeCluster(DMConfig(num_mns=2), num_clients=1)
    kv = cl.store(0)
    with pytest.raises(OrderedIndexDisabled):
        kv.submit_batch([Op.put(1, [1]), Op.scan(0, 10)])
    assert cl.scheduler.inflight(0) == 0, "put was submitted before reject"
    assert kv.get(1) is None


def test_mn_crash_during_scans_recovers():
    cl = _cluster(num_clients=2, seed=5, num_mns=4, replication=3)
    kv = cl.store(0)
    for k in range(60):
        kv.insert(k, [k])
    cl.crash_mn(2)
    res = kv.scan(0, 100)
    assert [k for k, _ in res] == list(range(60))


# ----------------------------------------------------- migration cutover --
def test_scan_across_add_mn_cutover():
    cl = _cluster(num_clients=4, seed=9, num_mns=2, replication=2,
                  index_shards=4)
    fleet = cl.fleet()
    sched = cl.scheduler
    backends = [cl.store(c, max_inflight=0).backend for c in range(4)]
    for k in range(120):
        sched.submit(k % 4, "insert", k, [k])
    fleet.run()
    # scans in flight while the ordered region (and shards) re-home
    futs = fleet.submit_wave(
        [(backends[c], [Op.scan(c * 7, 40)]) for c in range(4)])
    cl.add_mn(wait=False)
    fleet.run()
    if cl.migrator.busy:
        cl.migrator.drive()
    for c, fs in enumerate(futs):
        r = fs[0].result()
        assert r.status == OK
        assert [k for k, _ in r.value] == list(range(c * 7, c * 7 + 40))
    # the ordered region was re-homed as a first-class region
    g = cl.pool.ordered_regions[0]
    assert cl.migrator.stats()["cutovers"] >= 1
    res = cl.store(0).scan(0, 200)
    assert [k for k, _ in res] == list(range(120))


# ------------------------------------------------------------- fleet mode --
def test_fleet_locate_wave_single_invocation():
    cl = _cluster(num_clients=8, seed=3)
    fleet = cl.fleet()
    sched = cl.scheduler
    backends = [cl.store(c, max_inflight=0).backend for c in range(8)]
    for k in range(100):
        sched.submit(k % 8, "insert", k, [k])
    fleet.run()
    # warm fences
    fleet.submit_wave([(backends[c], [Op.scan(0, 4)]) for c in range(8)])
    fleet.run()
    base = fleet.stats()["scan_locate_invocations"]
    futs = fleet.submit_wave(
        [(backends[c], [Op.scan(c * 9, 6), Op.scan(c * 3, 2)])
         for c in range(8)])
    assert fleet.stats()["scan_locate_invocations"] == base + 1
    assert fleet.stats()["scan_locate_keys"] >= 16
    fleet.run()
    for c, fs in enumerate(futs):
        assert [k for k, _ in fs[0].result().value] == \
            list(range(c * 9, c * 9 + 6))
    assert fleet.stats()["ord_leaf_verbs"] > 0


def test_ycsbe_fleet_same_seed_bit_identical():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import YCSB, run_fleet_workload

    a = run_fleet_workload(n_clients=8, mix=YCSB["E"], seed=42,
                           ops_per_client=6, n_keys=96)
    b = run_fleet_workload(n_clients=8, mix=YCSB["E"], seed=42,
                           ops_per_client=6, n_keys=96)
    assert a.n_ops == b.n_ops
    assert a.rtts_by_kind == b.rtts_by_kind
    assert a.mix == b.mix
    assert np.array_equal(a.mn_bytes_per_op, b.mn_bytes_per_op)
    assert a.lat_p50_us == b.lat_p50_us and a.lat_p99_us == b.lat_p99_us
    assert "scan" in a.rtts_by_kind and a.mix["scan"] > 0.8


# ------------------------------------------------------------ scan storm --
@pytest.mark.slow
@pytest.mark.parametrize(
    "seed", [int(s) for s in
             os.environ.get("FUSEE_STORM_SEEDS", "0,1").split(",")])
def test_scan_storm_no_acked_write_lost_no_torn_scans(seed):
    """Acceptance: a seeded crash-storm over mixed scan/insert traffic
    loses no acked write and returns no torn scan results after recovery
    — bit-identical under same-seed replay."""
    def run(seed):
        cl = _cluster(num_clients=6, seed=seed, num_mns=4, replication=2,
                      mn_detect_delay=2)
        fleet = cl.fleet()
        sched = cl.scheduler
        backends = {c: cl.store(c, max_inflight=0).backend
                    for c in range(6)}
        for k in range(60):
            sched.submit(k % 6, "insert", k, [k])
        fleet.run()
        plan = FaultPlan.storm(cl.rng.stream("faults"),
                               clients=range(6), mns=4, replication=2,
                               n_client_crashes=2, n_mn_crashes=1,
                               first_op=80, spacing=24, recover_delay=12)
        cl.inject(plan)
        wl = cl.rng.stream("workload")
        acked_inserts = {}
        scan_results = []
        fresh = 60
        futs = []
        for wave_i in range(30):
            wave = []
            for c in range(6):
                if cl.clients.get(c) is None or cl.clients[c].crashed:
                    continue
                if sched.inflight(c) >= 4:
                    continue
                if wl.random() < 0.3:
                    op = Op.insert(fresh, [fresh])
                    futs.append((fresh, backends[c], op,
                                 backends[c].submit_many([op])[0]))
                    fresh += 1
                else:
                    start = int(wl.integers(fresh))
                    n = 1 + int(wl.integers(40))
                    wave.append((backends[c], [Op.scan(start, n)]))
            if wave:
                try:
                    for fs in fleet.submit_wave(wave):
                        scan_results.append(fs[0])
                except Exception:
                    pass
            fleet.tick()
        fleet.run()
        # recover any still-crashed clients, then quiesce
        for c in range(6):
            cli = cl.clients.get(c)
            if cli is not None and cli.crashed:
                cl.recover_client(c)
        cl.drain()
        for key, be, op, f in futs:
            if f.done() and f.result().status == OK:
                acked_inserts[key] = True
        live = next(c for c in range(6)
                    if cl.clients.get(c) is not None
                    and not cl.clients[c].crashed)
        kv = cl.store(live)
        final = kv.scan(0, 10_000)
        # torn-scan audit on every completed mid-storm scan
        torn = 0
        for f in scan_results:
            if not f.done():
                continue
            r = f.result()
            if r.status not in (OK,):
                continue
            if r.value is None:
                continue
            keys = [k for k, _ in r.value]
            if keys != sorted(set(keys)):
                torn += 1
        return (sorted(acked_inserts), [k for k, _ in final],
                [(k, tuple(v)) for k, v in final], torn)

    acked, final_keys, final_full, torn = run(seed)
    assert torn == 0, f"seed={seed}: torn mid-storm scan results"
    missing = [k for k in acked if k not in final_keys]
    assert not missing, \
        f"seed={seed}: acked inserts missing from post-recovery scan: {missing}"
    assert final_keys == sorted(set(final_keys)), f"seed={seed}"
    # bit-identical same-seed replay
    acked2, final_keys2, final_full2, torn2 = run(seed)
    assert (acked, final_keys, final_full, torn) == \
        (acked2, final_keys2, final_full2, torn2), f"seed={seed}: not replayable"


# ------------------------------------------------------------ serving twin --
def test_device_backend_scan_twin():
    from repro.core.api import KVStore
    from repro.serving import DeviceBackend, PoolConfig

    store = KVStore(DeviceBackend(PoolConfig(n_pages=256)))
    for k in range(40):
        store.insert(k, bytes([k % 250]) * 2)
    res = store.submit(Op.scan(10, 8)).result()
    assert res.status == OK
    assert [k for k, _ in res.value] == list(range(10, 18))
    assert all(v == bytes([k % 250]) * 2 for k, v in res.value)
    store.delete(12)
    res = store.submit(Op.scan(10, 8)).result()
    assert 12 not in [k for k, _ in res.value]
    res = store.submit(Op.range(30, 35)).result()
    assert [k for k, _ in res.value] == list(range(30, 35))


def test_leaf_probe_hint_roundtrip():
    """locate_leaves hints agree with the actual covering leaves."""
    cl = _cluster()
    kv = cl.store(0)
    for k in range(100):
        kv.insert(k, [k])
    client = cl.clients[0]
    assert client.ord_fences           # warmed by ensure path
    hints = ordered.locate_leaves(client, [0, 13, 57, 99])
    fences = client.ord_fences
    for start, leaf in zip([0, 13, 57, 99], hints):
        assert fences[leaf] <= start