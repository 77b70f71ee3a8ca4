"""Quickstart: the FUSEE KV store end-to-end in 60 seconds.

One public API (``repro.core.api.KVStore``: pipelined submit/submit_batch
futures + blocking get/put/delete), two substrates:

1. the paper-faithful event-level store (SNAPSHOT + two-level alloc +
   embedded log) — bytes keys/values, batched ops, crash recovery;
2. the serving-side device pool: the same Op batches lowered onto jitted
   index epochs + the race_lookup Pallas kernel.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import DMConfig, FuseeCluster, Op
from repro.core.api import KVStore
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import DeviceBackend, PoolConfig


def main():
    enable_compile_cache()
    print("== 1. event-level FUSEE store (paper protocol, verb by verb) ==")
    cluster = FuseeCluster(DMConfig(num_mns=4, replication=3), num_clients=2)
    kv = cluster.store(0)
    kv2 = cluster.store(1)
    r = kv.put(b"user:42", b"hello fusee")
    print(f" PUT    user:42         -> {r.status}, {r.rtts} RTTs "
          f"(first op: +2 one-time block-grant/list-head RTTs; steady = 4)")
    r = kv2.submit(Op.get(b"user:42")).result()
    print(f" GET    user:42 (other) -> {r.status} value={r.value} "
          f"{r.rtts} RTTs")
    r = kv.update(b"user:42", b"v2")
    print(f" UPDATE user:42         -> {r.status}, rule={r.rule}, "
          f"{r.rtts} RTTs")
    r = kv.delete(b"user:42")
    print(f" DELETE user:42         -> {r.status}, {r.rtts} RTTs")

    print("\n pipelined batch: 16 PUTs in flight at once, then one fused GET")
    futs = kv.submit_batch([Op.put(f"k{i}".encode(), f"v{i}".encode())
                            for i in range(16)])
    print(f" batch PUT x16          -> "
          f"{sum(f.result().status == 'OK' for f in futs)}/16 OK")
    for i in range(16):
        kv.get(f"k{i}".encode())          # warm the adaptive index cache
    futs = kv.submit_batch([Op.get(f"k{i}".encode()) for i in range(16)])
    res = [f.result() for f in futs]
    st = kv.stats()
    print(f" batch GET x16          -> {sum(r.status == 'OK' for r in res)}"
          f"/16 OK in 1 RTT (race_lookup fast path, "
          f"{st['batch_fast_hits']} kernel hits)")

    print("\n ordered keydir: range scans over a second, ordered index")
    ocl = FuseeCluster(DMConfig(num_mns=4, replication=2,
                                ordered_index=True), num_clients=1)
    okv = ocl.store(0)
    for k in range(40):
        okv.insert(k, [k * 10])
    res = okv.scan(10, 5)
    print(f" SCAN(10, 5)            -> {[(k, v) for k, v in res]}")
    print(f" RANGE(30, 34)          -> {[k for k, _ in okv.range(30, 34)]} "
          f"(batched leaf sweeps; see README 'Ordered index & range scans')")

    print("\n crash client 0 mid-flight, recover from the embedded log:")
    for k in range(8):
        kv.put(100 + k, [k])
    cluster.crash_client(0)
    stats = cluster.recover_client(0, reassign_to_cid=1)
    print(f" recovery: used={stats.used_objects} "
          f"reclaimed={stats.reclaimed_objects} "
          f"redone={stats.redone_ops} (reconnect {stats.reconnect_ms}ms)")
    print(f" data survives: k=104 -> {cluster.store(1).get(104)}")
    print(f" health: {cluster.health().summary()}")
    print(" (examples/fault_drill.py drills the full membership/fault API:"
          " FaultPlan, CRASHED futures, add/remove_client)")

    print("\n== 2. serving pool (same API, batched, device-resident) ==")
    store = KVStore(DeviceBackend(PoolConfig(n_pages=1024, n_buckets=256,
                                             slots_per_bucket=8, replicas=3)))
    keys = list(range(1, 257))
    ins = [f.result() for f in
           store.submit_batch([Op.insert(k, b"page-payload") for k in keys])]
    got = [f.result() for f in
           store.submit_batch([Op.get(k) for k in keys])]
    stats = store.stats()
    print(f" batched INSERT x{len(keys)}: "
          f"success={np.mean([r.status == 'OK' for r in ins]):.2f} "
          f"in {stats['epochs']} SNAPSHOT epoch(s)")
    print(f" batched GET x{len(keys)}: "
          f"hits={np.mean([r.status == 'OK' for r in got]):.2f} "
          f"(race_lookup kernel), value[0]={got[0].value!r}")
    print(f" index replicas converged: "
          f"{store.backend.pool.check_replicas_converged()}")


if __name__ == "__main__":
    main()
