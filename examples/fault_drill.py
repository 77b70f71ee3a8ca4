"""Fault drill: kill the trainer mid-run, restore from the last committed
checkpoint, finish, and verify the loss curve is seamless.  Then drill the
KV store through the declarative fault surface: an MN crash and a client
crash fire from a ``FaultPlan`` while a pipelined workload is in flight,
in-flight futures settle to the typed retriable ``CRASHED`` outcome, the
crashed client is recovered and replaced via dynamic membership, and
``cluster.health()`` reports the whole story.

    PYTHONPATH=src python examples/fault_drill.py [--skip-train]
"""
import argparse
import shutil

from repro.core import (CRASHED, OK, ClientCrashed, DMConfig, FaultPlan,
                        FuseeCluster, Op)


def train_drill():
    import jax

    from repro.configs import base as C
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.optim import OptConfig
    from repro.train import TrainConfig, Trainer

    print("== training fault drill ==")
    shutil.rmtree("/tmp/repro_fault_ckpt", ignore_errors=True)
    cfg = C.reduced(C.get("smollm-360m"))
    mesh = make_host_mesh((1, 1), ("data", "model"))
    model = build(cfg, mesh)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=4, seed=0))
    tr = Trainer(model, OptConfig(lr=3e-3, warmup_steps=5, total_steps=60),
                 TrainConfig(ckpt_every=10, ckpt_dir="/tmp/repro_fault_ckpt"),
                 data)
    tr.init_state(jax.random.PRNGKey(0))
    losses, recovered = tr.run_with_recovery(40, fail_at=25)
    print(f" killed at step 25, recovered={recovered}, "
          f"resumed from {tr.ckpt.latest()}")
    print(f" finished at step {tr.step}; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


def store_drill():
    print("\n== KV-store fault drill (declarative MN + client crash) ==")
    cluster = FuseeCluster(DMConfig(num_mns=4, replication=3), num_clients=3)
    kv = cluster.store(0)
    for k in range(32):
        kv.insert(k, [k * 10])
    print(" 32 keys inserted on client 0")

    # Declarative plan: MN 2 dies while the UPDATE batch below is in flight
    # (auto-detected and re-homed by the scheduler loop, no master calls),
    # then client 0 crash-stops 16 completed ops later, mid-pipeline.
    injector = cluster.inject(FaultPlan()
                              .crash_mn(2, after_ops=40)
                              .crash_client(0, after_ops=48))
    futs = kv.submit_batch([Op.update(k, [k * 10]) for k in range(32)])
    res = [f.result() for f in futs]
    n_ok = sum(r.status == OK for r in res)
    n_crashed = sum(r.status == CRASHED for r in res)
    print(f" UPDATE x32 under the plan -> {n_ok} OK, {n_crashed} CRASHED "
          f"(all retriable="
          f"{all(r.retriable for r in res if r.status == CRASHED)})")
    assert injector.done, injector.pending

    try:
        kv.get(0)
    except ClientCrashed as e:
        print(f" submit on the dead client -> typed ClientCrashed "
              f"(cid={e.cid}, reason={e.reason!r})")

    retried = [cluster.store(1).get(k) for k in range(32)]
    print(f" retried on live client 1   -> all keys readable="
          f"{retried == [[k * 10] for k in range(32)]}")

    st = cluster.recover_client(0, reassign_to_cid=1)
    print(f" client 0 recovered: reclaimed {st.reclaimed_objects} objects, "
          f"redid {st.redone_ops} ops, ~{st.reconnect_ms:.0f}ms reconnect")

    cid = cluster.add_client()            # elastic replacement joins
    ok = all(cluster.store(cid).get(k) == [k * 10] for k in range(32))
    print(f" replacement client {cid} joined (epoch "
          f"{cluster.clients[cid].epoch}): all keys readable={ok}")

    h = cluster.health()
    print(f" health: {h.summary()}")
    dead = [m.mid for m in h.mns if not m.alive]
    print(f" MNs down={dead}, recovery total "
          f"traverse={h.recovery.traverse_log_rtts} RTTs "
          f"redo={h.recovery.redone_ops} ops")


def elastic_drill():
    """Online MN scale-out under load: 2 -> 4 MNs while a fleet workload
    keeps writing.  Index shards re-home by live migration (bulk copy +
    dual-write window + epoch-bump cutover); nothing acked is lost."""
    print("\n== elastic drill (2 -> 4 MNs under live load) ==")
    n_clients = 8
    cluster = FuseeCluster(DMConfig(num_mns=2, replication=2, index_shards=8,
                                    region_words=1 << 15, regions_per_mn=8),
                           num_clients=n_clients, seed=3)
    fleet = cluster.fleet()
    sched = cluster.scheduler
    backends = [cluster.store(c, max_inflight=0).backend
                for c in range(n_clients)]
    print(f" index shards: {len(cluster.pool.index_regions)} over "
          f"{len(cluster.pool.mns)} MNs "
          f"{dict((g, cluster.pool.placement[g]) for g in cluster.pool.index_regions[:3])}...")
    futs, k = [], 0
    added = []
    while k < 256 or cluster.migrator.busy or sched.has_work():
        for c in range(n_clients):
            if k < 256 and sched.inflight(c) < 4:
                futs.append((k, backends[c].submit_many([Op.put(k, [k])])[0]))
                k += 1
        if k >= 64 and len(added) == 0:
            added.append(cluster.add_mn(wait=False))
            print(f" MN {added[-1]} joined at op {k} (migration rides the "
                  f"workload ticks)")
        if k >= 128 and len(added) == 1:
            added.append(cluster.add_mn(wait=False))
            print(f" MN {added[-1]} joined at op {k}")
        fleet.tick()
    ok = sum(f.result().status == OK for _, f in futs)
    print(f" {ok}/{len(futs)} writes OK across both scale-outs, "
          f"{cluster.migrator.stats()['cutovers']} shard cutovers, "
          f"{cluster.migrator.stats()['copied_words']} words copied")
    reader = cluster.store(1)
    lost = [kk for kk, f in futs
            if f.result().status == OK and reader.get(kk) != [kk]]
    print(f" acked-write loss after migration: {len(lost)} (expect 0)")
    assert not lost, lost
    shards_by_mn = {}
    for g in cluster.pool.index_regions:
        shards_by_mn.setdefault(cluster.pool.placement[g][0], []).append(g)
    print(f" shard primaries by MN: "
          f"{ {m: len(gs) for m, gs in sorted(shards_by_mn.items())} }")
    print(f" health: {cluster.health().summary()}")


def scan_drill():
    """Ordered-index fault drill: crash a client mid-leaf-split while
    YCSB-E traffic (scans + inserts) is live, repair via Alg-3/§5.3, and
    audit that no acknowledged insert is missing from subsequent scans."""
    import numpy as np

    from repro.core import ordered

    print("\n== scan drill (crash mid-leaf-split under live YCSB-E) ==")
    cluster = FuseeCluster(DMConfig(num_mns=4, replication=3,
                                    ordered_index=True,
                                    region_words=1 << 15, regions_per_mn=16),
                           num_clients=4, seed=11)
    sched = cluster.scheduler
    kv1 = cluster.store(1)
    for k in range(24):                     # fill ~2 leaves
        kv1.insert(k, [k])
    print(" 24 keys preloaded; "
          f"{len(ordered.ordered_keys_direct(cluster.pool))} in the keydir")

    # client 0: a pipeline of inserts that will split leaves; clients 2-3:
    # live YCSB-E scans.  Crash client 0 at an arbitrary verb boundary —
    # with splits in flight, that is a half-split tree.
    recs = [sched.submit(0, "insert", 24 + i, [24 + i]) for i in range(12)]
    scan_recs = [sched.submit(2 + (i % 2), "scan", int(i * 7) % 30, 20)
                 for i in range(6)]
    rng = np.random.default_rng(11)
    for _ in range(700):     # far enough that some inserts acked mid-split
        cids = sched.eligible_cids()
        if not cids:
            break
        sched.step(cids[int(rng.integers(len(cids)))],
                   pick=int(rng.integers(4)))
    cluster.crash_client(0)
    acked = [24 + i for i, r in enumerate(recs)
             if r.result is not None and r.result.status == OK]
    n_crashed = sum(1 for r in recs
                    if r.result is not None and r.result.status == CRASHED)
    print(f" client 0 crashed mid-split: {len(acked)} inserts acked, "
          f"{n_crashed} in-flight CRASHED")
    st = cluster.recover_client(0, reassign_to_cid=1)
    cluster.drain()
    print(f" Alg-3/§5.3 repair: {st.redone_ops} redone, "
          f"{st.reclaimed_objects} reclaimed")

    res = cluster.store(1).scan(0, 100)
    got = [k for k, _ in res]
    missing = [k for k in list(range(24)) + acked if k not in got]
    live_scans = sum(1 for r in scan_recs
                     if r.result is not None and r.result.status == OK)
    print(f" scans during the storm: {live_scans}/{len(scan_recs)} OK; "
          f"post-repair scan sees {len(got)} keys")
    print(f" acked-insert loss after repair: {len(missing)} (expect 0)")
    assert not missing, missing
    assert got == sorted(set(got)), "torn scan result"
    print(f" health: {cluster.health().summary()}")


def metrics_drill():
    """Telemetry drill: run a fleet workload through an MN crash with the
    observability hub armed, then read the story back three ways — op
    latency percentiles from the registry histograms, the per-MN load
    table from the ``mn.load`` time-series, and the fault-triggered
    flight-recorder dump exported to a Perfetto trace."""
    import tempfile

    from repro.obs import flight_to_perfetto, load_flight

    print("\n== telemetry drill (histograms / per-MN load / flight dump) ==")
    dump_dir = tempfile.mkdtemp(prefix="fusee_flight_")
    n_clients = 8
    cluster = FuseeCluster(DMConfig(num_mns=4, replication=3,
                                    region_words=1 << 15, regions_per_mn=16,
                                    index_shards=4),
                           num_clients=n_clients, seed=7,
                           obs_dump_dir=dump_dir)
    cluster.inject(FaultPlan().crash_mn(3, after_ops=120))
    fleet = cluster.fleet()
    stores = {c: cluster.store(c, max_inflight=0) for c in range(n_clients)}
    for k in range(256):
        stores[k % n_clients].submit(Op.put(k, [k]))
        if k % 32 == 31:
            fleet.run()
    for k in range(256):
        stores[k % n_clients].submit(Op.get(k))
        if k % 32 == 31:
            fleet.run()
    fleet.run()

    m = cluster.metrics()
    print(f" ops: {m['counters']['op.begun']} begun, "
          f"{m['counters']['op.settled']} settled, "
          f"{m['counters']['op.crashed']} crashed")
    print(" op latency percentiles (conservative bucket upper edges):")
    print(f"  {'metric':<34}{'count':>7}{'p50':>6}{'p99':>6}{'p999':>7}")
    for name, p in sorted(m["percentiles"].items()):
        if ".kind." in name:
            print(f"  {name:<34}{p['count']:>7}{p['p50']:>6}"
                  f"{p['p99']:>6}{p['p999']:>7}")

    series = m["series"]["mn.load"]
    by = {f: i for i, f in enumerate(series["fields"])}
    per_mn = {}
    for row in series["rows"]:
        agg = per_mn.setdefault(int(row[by["mid"]]),
                                {"bytes": 0.0, "verbs": 0.0,
                                 "cpu_ops": 0.0, "util": []})
        agg["bytes"] += row[by["bytes"]]
        agg["verbs"] += row[by["verbs"]]
        agg["cpu_ops"] += row[by["cpu_ops"]]
        agg["util"].append(row[by["util"]])
    print(f" per-MN load ({len(series['rows'])} window samples):")
    print(f"  {'mn':>4}{'bytes':>10}{'verbs':>8}{'cpu_ops':>9}"
          f"{'peak util':>11}")
    for mid, agg in sorted(per_mn.items()):
        print(f"  {mid:>4}{agg['bytes']:>10.0f}{agg['verbs']:>8.0f}"
              f"{agg['cpu_ops']:>9.0f}{max(agg['util']):>10.4f}")

    print(" dump-on-fault:")
    for reason, path in sorted(cluster.obs.dumped.items()):
        dump = load_flight(path)
        trace_path = path.replace(".npz", ".perfetto.json")
        flight_to_perfetto(dump, trace_path)
        print(f"  {reason}: {len(dump['tick'])} events -> {path}")
        print(f"   perfetto trace (ui.perfetto.dev) -> {trace_path}")
    assert cluster.obs.dumped, "MN crash must trigger a flight dump"


def profile_drill():
    """Causal-profiler drill: a planted zipf(0.99) fleet workload through
    an MN crash with the verb tracer AND the hot-key monitor armed, then
    the full profiling surface read back — the top-5 critical-path rows
    (op kind x protocol phase x retry cause, RTT-conservation-checked),
    the hot-key top-k table with the online zipf-θ estimate, and a
    Perfetto trace with the causal phase sub-spans nested under each op
    lane."""
    import tempfile

    import numpy as np

    from repro.obs import flight_to_perfetto
    from repro.obs.profile import format_report

    print("\n== profile drill (critical path + hot keys, zipf 0.99) ==")
    n_clients, n_keys, n_ops, theta = 8, 256, 1200, 0.99
    cluster = FuseeCluster(DMConfig(num_mns=4, replication=3,
                                    region_words=1 << 15, regions_per_mn=16,
                                    index_shards=4),
                           num_clients=n_clients, seed=5)
    cluster.attach_tracer(capacity=1 << 17)
    cluster.enable_hotspot()
    cluster.inject(FaultPlan().crash_mn(3, after_ops=300))
    fleet = cluster.fleet()
    stores = {c: cluster.store(c, max_inflight=0) for c in range(n_clients)}
    for k in range(n_keys):
        stores[k % n_clients].submit(Op.insert(k, [k]))
        if k % 32 == 31:
            fleet.run()
    fleet.run()
    # planted zipfian read/update mix (the hot head is keys 0, 1, 2, ...)
    wl = cluster.rng.stream("workload")
    ranks = np.arange(1, n_keys + 1, dtype=np.float64)
    p = ranks ** (-theta)
    p /= p.sum()
    keys = wl.choice(n_keys, size=n_ops, p=p)
    for i, k in enumerate(keys):
        st = stores[i % n_clients]
        op = Op.update(int(k), [i]) if i % 2 else Op.get(int(k))
        st.submit(op)
        if i % 64 == 63:
            fleet.run()
    fleet.run()

    prof = cluster.profile()
    print(" top-5 critical-path rows:")
    print("  " + format_report(prof, top=5).replace("\n", "\n  "))
    c = prof["conservation"]
    assert c["ok"], f"RTT conservation violated: {c}"

    hot = cluster.metrics()["hotspot"]
    print(f" hot-key monitor: θ~{hot['theta_milli'] / 1000:.2f} "
          f"(planted {theta}), regime={hot['regime']}, "
          f"{hot['keys_seen']} keys folded:")
    print(f"  {'key':>6}{'count':>8}{'err':>6}")
    for key, count, err in hot["top"][:10]:
        print(f"  {key:>6}{count:>8}{err:>6}")

    trace_path = tempfile.mktemp(prefix="fusee_profile_",
                                 suffix=".perfetto.json")
    flight_to_perfetto({"labels": cluster.obs.labels(),
                        **cluster.obs.flight_events(),
                        "dropped": cluster.obs.flight.dropped},
                       trace_path, spans=prof["spans"])
    print(f" perfetto trace with causal sub-spans -> {trace_path}")
    if "tick_phases" in prof:
        tp = prof["tick_phases"]
        print(f" fused tick phases: coord {tp['coord_build_frac']:.0%} "
              f"sweep {tp['sweep_frac']:.0%} "
              f"scatter {tp['scatter_frac']:.0%} "
              f"bookkeeping {tp['bookkeeping_frac']:.0%} "
              f"({tp['us_per_tick']:.0f}us/tick over {tp['ticks']} ticks)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip-train", action="store_true",
                    help="only run the KV-store drill (CI failure-path smoke)")
    ap.add_argument("--elastic", action="store_true",
                    help="also run the online MN scale-out drill")
    ap.add_argument("--scan", action="store_true",
                    help="also run the ordered-index crash-mid-split drill")
    ap.add_argument("--metrics", action="store_true",
                    help="also run the telemetry drill (latency percentiles, "
                         "per-MN load table, dump-on-fault + Perfetto export)")
    ap.add_argument("--profile", action="store_true",
                    help="also run the causal-profiler drill (critical-path "
                         "RTT attribution, hot-key top-k, Perfetto sub-spans)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if not args.skip_train:
        train_drill()
    store_drill()
    if args.elastic:
        elastic_drill()
    if args.scan:
        scan_drill()
    if args.metrics:
        metrics_drill()
    if args.profile:
        profile_drill()
