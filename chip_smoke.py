"""Bring-up smoke of the device store on one TPU, at the paper's YCSB scale.

    python chip_smoke.py [--seed N]

Builds ``KVStore(DeviceBackend(PoolConfig(...)))`` — the store's normal
entry point on the device substrate — with the paper's workload settings
(``configs/fusee_paper.py``: 100,000 keys, zipf 0.99, 1 KB values) and a
pool sized for them: 2^17 pages, 3 index replicas, 65536 buckets x 8 slots
(about 19% slot load).  INSERT takes the first empty slot of a key's first
bucket, then of its second, as the simulator does; at 32768 buckets (38%)
that placement leaves both buckets of 2 of the 100,000 keys full (seed 0),
and the store answers FULL.  It then runs ``benchmarks/store_phases.py``:
load, YCSB-C, YCSB-A, DELETE and SCAN in batches of 4096 ops, every answer
checked against a dict reference, replicas checked for convergence.

It refuses to run anywhere but on a TPU.  The lines before the last are
smoke readings, not metrics.  The last line is one JSON object naming the
device; the exit code is 0 only if every phase matched the reference.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BATCH = 4096


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is on "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    root = Path(__file__).resolve().parent
    sys.path[:0] = [str(root), str(root / "src")]
    from benchmarks.store_phases import run_store_phases
    from repro.configs.fusee_paper import FuseePaperConfig
    from repro.core.api import KVStore
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import DeviceBackend, PoolConfig

    print(f"smoke reading: compile cache {enable_compile_cache()}")
    print(f"smoke reading: device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}")
    paper = FuseePaperConfig()
    backend = DeviceBackend(PoolConfig(n_pages=2 ** 17, n_buckets=65536,
                                       slots_per_bucket=8, replicas=3,
                                       chunk_pages=64, n_shards=4),
                            seed=args.seed)
    pool = backend.pool
    sizes = {name: int(getattr(pool, name).nbytes)
             for name in ("index", "log", "chain", "free_bitmap")}
    print(f"smoke reading: device bytes {sizes} (values are held "
          f"host-side by DeviceBackend and are not in these bytes)")

    reports = run_store_phases(
        KVStore(backend), n_keys=paper.ycsb_keys,
        value_bytes=paper.kv_size_bytes, theta=paper.zipf_theta,
        batch=BATCH, seed=args.seed)

    stats = dev.memory_stats() or {}
    print(f"smoke reading: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"smoke reading: compiles after the load phase="
          f"{sum(r.compiles for r in reports[1:])}")
    if not all(r.ok for r in reports):
        bad = [r.name for r in reports if not r.ok]
        print(f"chip_smoke: phases failed: {bad}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
