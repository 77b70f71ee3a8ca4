"""Serving example: continuous batching over the FUSEE-managed KV pool with
shared-prefix requests (the disaggregated prefix cache at work).

    PYTHONPATH=src python examples/serve_fusee.py
"""
import time

import jax
import numpy as np

from repro.configs import base as C
from repro.models import build
from repro.serving import PoolConfig, Request, ServeEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh


def main():
    enable_compile_cache()
    mesh = make_host_mesh((1, 1), ("data", "model"))
    cfg = C.reduced(C.get("llama3-8b"))
    model = build(cfg, mesh, use_kernels=True)   # Pallas attn (interpret)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, max_batch=4, max_len=256,
                      pool_cfg=PoolConfig(n_pages=2048, n_buckets=512,
                                          slots_per_bucket=8, replicas=3))
    rng = np.random.default_rng(0)
    system_prompt = rng.integers(0, cfg.vocab, 128).astype(np.int32)
    for i in range(8):
        user = rng.integers(0, cfg.vocab, 32).astype(np.int32)
        eng.submit(Request(rid=i, max_new=8,
                           prompt=np.concatenate([system_prompt, user])))
    t0 = time.perf_counter()
    done = eng.run(max_ticks=200)
    dt = time.perf_counter() - t0
    toks = sum(len(q.out) for q in done)
    print(f"served {len(done)} requests / {toks} tokens "
          f"in {dt:.1f}s ({eng.steps} ticks)")
    hits = sum(q.prefix_hits for q in done)
    print(f"prefix-cache: {hits} block hits across requests "
          f"(shared 128-token system prompt = 2 blocks)")
    print(f"pool: {eng.pool.metrics.snapshot()['counters']}  "
          f"replicas converged: "
          f"{eng.pool.check_replicas_converged()}")
    for q in done[:3]:
        print(f"  rid={q.rid} -> {q.out}")


if __name__ == "__main__":
    main()
