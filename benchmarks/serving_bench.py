"""Serving-path benchmarks: FUSEE pool ops batched on-device, prefix-cache
effect in the engine, and the race_lookup kernel vs its oracle."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_host_mesh


def bench_pool_ops() -> List[Dict]:
    """Device pool through the unified store API (core/api.py): one
    ``submit_batch`` of INSERTs = alloc + page write + SNAPSHOT epochs;
    one batch of GETs = a single race_lookup probe."""
    from repro.core.api import KVStore, Op
    from repro.core.events import OK
    from repro.serving import DeviceBackend, PoolConfig
    rows = []
    store = KVStore(DeviceBackend(PoolConfig(n_pages=8192, n_buckets=2048,
                                             slots_per_bucket=8, replicas=3)))
    keys = list(range(1, 4001))
    t0 = time.perf_counter()
    res = [f.result() for f in
           store.submit_batch([Op.insert(k, None) for k in keys])]
    t_ins = time.perf_counter() - t0
    ok = np.array([r.status == OK for r in res])
    t0 = time.perf_counter()
    for _ in range(5):
        got = [f.result() for f in
               store.submit_batch([Op.get(k) for k in keys])]
    t_s = (time.perf_counter() - t0) / 5
    found = np.array([r.status == OK for r in got])
    stats = store.stats()
    rows.append({"bench": "serving_pool", "op": "insert_batch",
                 "n": len(keys), "wall_s": t_ins,
                 "success": float(ok.mean()),
                 "epochs": stats["epochs"]})
    rows.append({"bench": "serving_pool", "op": "search_batch",
                 "n": len(keys), "wall_s": t_s,
                 "hit": float(found.mean()),
                 "mops_host": len(keys) / t_s / 1e6})
    return rows


def bench_race_kernel() -> List[Dict]:
    from repro.kernels import race_lookup, race_lookup_ref
    rows = []
    nb, spb = 2048, 8
    rng = np.random.default_rng(0)
    index = jnp.asarray(rng.integers(0, 2**31 - 1, (nb, spb)), jnp.int32)
    keys = jnp.asarray(rng.integers(1, 2**31 - 1, 4096), jnp.int32)
    for name, fn in (("kernel_interpret",
                      lambda: race_lookup(keys, index)),
                     ("ref_jnp", lambda: race_lookup(keys, index,
                                                     use_kernel=False))):
        out = fn()
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(fn())
        rows.append({"bench": "race_lookup", "impl": name, "n_keys": 4096,
                     "us_per_call": (time.perf_counter() - t0) / 3 * 1e6})
    return rows


def bench_engine_prefix() -> List[Dict]:
    from repro.configs import base as C
    from repro.models import build
    from repro.serving import PoolConfig, Request, ServeEngine
    mesh = make_host_mesh((1, 1), ("data", "model"))
    r = C.reduced(C.get("llama3-8b"))
    m = build(r, mesh)
    params = m.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    shared = rng.integers(0, r.vocab, 128).astype(np.int32)
    rows = []
    for label, make_prompt in (
            ("shared_prefix", lambda i: np.concatenate(
                [shared, rng.integers(0, r.vocab, 16).astype(np.int32)])),
            ("disjoint", lambda i: rng.integers(0, r.vocab, 144)
             .astype(np.int32))):
        eng = ServeEngine(m, params, max_batch=4, max_len=256,
                          pool_cfg=PoolConfig(n_pages=1024, n_buckets=256,
                                              slots_per_bucket=8))
        for i in range(8):
            eng.submit(Request(rid=i, prompt=make_prompt(i), max_new=4))
        t0 = time.perf_counter()
        done = eng.run(max_ticks=200)
        rows.append({"bench": "engine", "workload": label,
                     "finished": len(done), "ticks": eng.steps,
                     "wall_s": time.perf_counter() - t0,
                     "prefix_hits": sum(q.prefix_hits for q in done),
                     "pool_epochs": eng.health()["epochs"]})
    return rows


def run() -> List[Dict]:
    return bench_pool_ops() + bench_race_kernel() + bench_engine_prefix()
