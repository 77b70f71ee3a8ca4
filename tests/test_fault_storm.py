"""Seeded fault-storm stress test (tier-1 + the CI seed matrix).

A randomized ``FaultPlan.storm`` — crash/recover clients and crash MNs at
random completed-op boundaries, all drawn from the run's ``SimRng`` —
fires while a fleet-driven insert workload runs.  Invariants:

* **no acknowledged write is ever lost** — every key whose insert
  resolved OK is readable (with its exact value) from a live client
  after the storm;
* **every future settles** — no hangs: each submitted op ends OK or
  CRASHED (crashed-client submits are rejected up front with the typed
  ``ClientCrashed`` and never enter the pipeline);
* **health converges** — empty pipelines everywhere, one lease epoch
  across live clients, every MN crash detected + Alg-3-recovered, and
  the whole plan fired.

Seeds come from ``FUSEE_STORM_SEEDS`` (comma-separated; CI runs a 3-seed
matrix).  Every assertion message carries the reproducing seed.
"""
import os

import pytest

# the storm matrix is the heavyweight part of tier-1: CI runs it (plus the
# property suite) in the dedicated sim-seeds / slow jobs
pytestmark = pytest.mark.slow

from repro.analysis.races import report
from repro.core import (CRASHED, OK, ClientCrashed, DMConfig, FaultPlan,
                        FuseeCluster, Op)

SEEDS = [int(s) for s in
         os.environ.get("FUSEE_STORM_SEEDS", "0,1").split(",")]

N_CLIENTS, N_MNS, REPL = 6, 5, 3
TOTAL_OPS = 160


def _run_storm(seed, **churn):
    cl = FuseeCluster(DMConfig(num_mns=N_MNS, replication=REPL,
                               region_words=1 << 15, regions_per_mn=16,
                               index_shards=churn.pop("index_shards", 1)),
                      num_clients=N_CLIENTS, seed=seed)
    cl.attach_tracer()                 # sanitizers run over every storm
    storm_kw = dict(clients=range(N_CLIENTS), mns=N_MNS, replication=REPL,
                    n_client_crashes=2, n_mn_crashes=2, first_op=10,
                    spacing=14, recover_delay=8)
    storm_kw.update(churn)             # churn overrides (e.g. n_mn_crashes)
    plan = FaultPlan.storm(cl.rng.stream("faults"), **storm_kw)
    injector = cl.inject(plan)
    fleet = cl.fleet()
    stores = {c: cl.store(c, max_inflight=0) for c in range(N_CLIENTS)}
    futs, rejected = [], 0
    submitted = 0
    while submitted < TOTAL_OPS:
        for c in range(N_CLIENTS):
            if submitted >= TOTAL_OPS:
                break
            k = submitted
            submitted += 1
            try:
                futs.append((k, c, stores[c].submit(Op.put(k, [k, c]))))
            except ClientCrashed:
                rejected += 1          # typed rejection: op never entered
        for _ in range(4):             # let faults fire mid-workload
            if cl.scheduler.has_work():
                fleet.tick()
    fleet.run()
    if cl.migrator.busy:               # drain membership churn (add/remove)
        cl.migrator.drive()
    return cl, plan, injector, futs, rejected


@pytest.mark.parametrize("seed", SEEDS)
def test_fault_storm_invariants(seed):
    msg = f"(reproduce with FUSEE_STORM_SEEDS={seed})"
    cl, plan, injector, futs, rejected = _run_storm(seed)

    # the storm actually happened, in full
    assert injector.done and len(injector.fired) == len(plan), msg
    crashes = [e for _, e in injector.fired if e.action == "crash_client"]
    mn_crashes = [e for _, e in injector.fired if e.action == "crash_mn"]
    assert crashes and mn_crashes, msg

    # every future settled: OK or typed-retriable CRASHED, nothing hung
    acked = {}
    for k, c, f in futs:
        assert f.done(), f"future for key {k} never settled {msg}"
        r = f.result()
        assert r.status in (OK, CRASHED), \
            f"key {k} ended {r.status} {msg}"
        if r.status == OK:
            acked[k] = [k, c]
    assert acked, msg

    # no acknowledged write is ever lost: every OK'd key is readable with
    # its exact value from a live client after recovery
    live = [c for c, cc in cl.clients.items() if not cc.crashed]
    assert live, msg
    reader = cl.store(live[0])
    for k, v in acked.items():
        got = reader.get(k)
        assert got == v, f"acked key {k} lost: read {got!r} {msg}"

    # health converges after the storm
    h = cl.health()
    assert all(c.inflight == 0 for c in h.clients), msg
    assert h.alive_mns == N_MNS - len(mn_crashes), msg
    assert h.mn_recoveries == len(mn_crashes), msg    # Alg-3 ran per crash
    assert h.client_recoveries == len(crashes), msg   # §5.3 ran per crash
    epochs = {c.epoch for c in h.clients if c.status == "live"}
    assert len(epochs) == 1, f"epoch split-brain {epochs} {msg}"
    assert h.crashed_ops == sum(c.crashed_ops for c in h.clients), msg

    # sanitizers: the verb trace is race-free and the heap audits clean
    findings = cl.race_findings()
    assert findings == [], report(findings, cl.pool._tracer) + msg
    rep = cl.heap_audit()
    assert rep.ok, f"{rep} {msg}"


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_fault_storm_is_seed_deterministic(seed):
    """The same storm seed reproduces the same fault schedule and the same
    op outcomes — the replay contract under fault injection."""
    def signature(run):
        cl, _plan, injector, futs, rejected = run
        return (tuple((t, e.action, e.target) for t, e in injector.fired),
                tuple((k, c, f.result().status) for k, c, f in futs),
                rejected, cl.scheduler.tick)
    assert signature(_run_storm(seed)) == signature(_run_storm(seed)), \
        f"(reproduce with FUSEE_STORM_SEEDS={seed})"


# ------------------------------------------------------- membership churn --
# one base MN crash (instead of two) leaves headroom for the
# crash-during-migration extra crash AND the drain of the added MN
CHURN = dict(index_shards=4, n_add_mns=1, remove_added=True,
             crash_during_migration=True, n_mn_crashes=1)


@pytest.mark.parametrize("seed", SEEDS)
def test_membership_churn_storm_invariants(seed):
    """Storm + membership churn: an MN joins mid-run (shard migrations
    ride the workload ticks), an original MN crashes WHILE the copies are
    in flight, and the added MN is drained + retired again — on top of
    the base client/MN crash storm.  Invariants: the full plan fires, no
    acknowledged write is lost across any cutover, every future settles,
    and the cluster converges with no open migration windows."""
    msg = f"(reproduce with FUSEE_STORM_SEEDS={seed})"
    cl, plan, injector, futs, rejected = _run_storm(seed, **CHURN)

    assert injector.done and len(injector.fired) == len(plan), msg
    actions = [e.action for _, e in injector.fired]
    assert "add_mn" in actions and "remove_mn" in actions, msg

    acked = {}
    for k, c, f in futs:
        assert f.done(), f"future for key {k} never settled {msg}"
        r = f.result()
        assert r.status in (OK, CRASHED), f"key {k} ended {r.status} {msg}"
        if r.status == OK:
            acked[k] = [k, c]
    assert acked, msg

    live = [c for c, cc in cl.clients.items() if not cc.crashed]
    reader = cl.store(live[0])
    for k, v in acked.items():
        got = reader.get(k)
        assert got == v, f"acked key {k} lost across cutover: {got!r} {msg}"

    h = cl.health()
    assert h.migrating_regions == 0 and not cl.migrator.busy, msg
    assert all(c.inflight == 0 for c in h.clients), msg
    epochs = {c.epoch for c in h.clients if c.status == "live"}
    assert len(epochs) == 1, f"epoch split-brain {epochs} {msg}"
    # the added MN either retired cleanly or crashed while draining
    added_mid = N_MNS
    assert (cl.pool.mns[added_mid].retired
            or not cl.pool.mns[added_mid].alive), msg

    # sanitizers: race-free trace, clean heap/epoch audit across cutovers
    findings = cl.race_findings()
    assert findings == [], report(findings, cl.pool._tracer) + msg
    rep = cl.heap_audit()
    assert rep.ok, f"{rep} {msg}"


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_membership_churn_storm_is_seed_deterministic(seed):
    """Migration runs replay bit-identically: same seed -> same fault +
    membership schedule, same op outcomes, same migration counters, and
    byte-identical primary index shards."""
    def signature(run):
        cl, _plan, injector, futs, rejected = run
        shards = []
        for g in sorted(cl.pool.index_regions):
            prim = cl.pool.mns[cl.pool.placement[g][0]]
            shards.append(prim.regions[g][:cl.pool.cfg.index_words]
                          .tobytes())
        return (tuple((t, e.action, e.target) for t, e in injector.fired),
                tuple((k, c, f.result().status) for k, c, f in futs),
                rejected, cl.scheduler.tick, cl.pool.epoch,
                tuple(sorted(cl.migrator.stats().items())), tuple(shards))
    assert signature(_run_storm(seed, **dict(CHURN))) == \
        signature(_run_storm(seed, **dict(CHURN))), \
        f"(reproduce with FUSEE_STORM_SEEDS={seed})"
