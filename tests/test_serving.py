"""Serving-layer tests: SNAPSHOT epoch vs numpy oracle + protocol
invariants (hypothesis over seeds), KV pool lifecycle, crash recovery,
engine integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:         # pragma: no cover - hypothesis-less environments
    from _hypo import given, settings, strategies as st

from repro.launch.mesh import make_host_mesh
from repro.serving import (PoolConfig, Request, ServeEngine,
                           snapshot_epoch, snapshot_epoch_np)
from repro.serving.kvpool import KVPool  # internal substrate (whitebox)


# ------------------------------------------------------- SNAPSHOT epoch ----
@settings(max_examples=60, deadline=None)
@given(trial=st.integers(0, 100_000), r=st.integers(1, 4),
       W=st.integers(1, 12), stale=st.booleans())
def test_snapshot_epoch_invariants(trial, r, W, stale):
    rng = np.random.default_rng(trial)
    M = 32
    base = (rng.integers(0, 3, M).astype(np.int32)) * 7
    index = np.tile(base, (r, 1))
    slot = rng.integers(-1, M, W).astype(np.int32)
    v_old = base[np.maximum(slot, 0)].astype(np.int32)
    if stale and W:
        v_old[0] += 1  # a writer with a stale read
    v_new = (rng.permutation(1000)[:W] + 10).astype(np.int32)
    res = snapshot_epoch(jnp.asarray(index), jnp.asarray(slot),
                         jnp.asarray(v_old), jnp.asarray(v_new),
                         jax.random.PRNGKey(trial))
    win = np.asarray(res.win)
    idx = np.asarray(res.index)
    for s in set(int(x) for x in slot if x >= 0):
        fresh = [w for w in range(W) if slot[w] == s and v_old[w] == base[s]]
        winners = [w for w in fresh if win[w]]
        # exactly one winner among fresh writers on a contested slot
        assert len(winners) == (1 if fresh else 0), (s, fresh, winners)
        if winners:
            # the winner's value is committed on EVERY replica
            assert (idx[:, s] == v_new[winners[0]]).all()
    # replicas converge on every touched slot
    touched = sorted(set(int(x) for x in slot if x >= 0))
    assert (idx[:, touched] == idx[0, touched]).all()
    # stale writers never win
    for w in range(W):
        if slot[w] >= 0 and v_old[w] != base[slot[w]]:
            assert not win[w]


@settings(max_examples=30, deadline=None)
@given(trial=st.integers(0, 10_000))
def test_snapshot_epoch_matches_numpy_oracle_semantics(trial):
    """The jnp epoch and the sequential numpy oracle must agree on the SET
    of possible outcomes: same single-winner slots; committed values drawn
    from the proposals.  (Arrival orders differ, so the specific winner may
    differ — the protocol guarantees agreement, not determinism.)"""
    rng = np.random.default_rng(trial)
    r, M, W = 3, 16, 6
    base = np.zeros(M, np.int32)
    index = np.tile(base, (r, 1))
    slot = rng.integers(0, 4, W).astype(np.int32)  # heavy contention
    v_old = np.zeros(W, np.int32)
    v_new = (rng.permutation(100)[:W] + 1).astype(np.int32)
    res = snapshot_epoch(jnp.asarray(index), jnp.asarray(slot),
                         jnp.asarray(v_old), jnp.asarray(v_new),
                         jax.random.PRNGKey(trial))
    order = [list(rng.permutation(W)) for _ in range(r)]
    idx_np, win_np, com_np, _ = snapshot_epoch_np(index, slot, v_old, v_new,
                                                  order)
    for s in set(int(x) for x in slot):
        writers = [w for w in range(W) if slot[w] == s]
        assert sum(bool(np.asarray(res.win)[w]) for w in writers) == 1
        assert sum(bool(win_np[w]) for w in writers) == 1
        # committed value is one of the proposals in both executions
        props = {int(v_new[w]) for w in writers}
        assert int(np.asarray(res.index)[0, s]) in props
        assert int(idx_np[0, s]) in props


# ----------------------------------------------------------- KV pool -------
@pytest.fixture
def pool():
    return KVPool(PoolConfig(n_pages=512, n_buckets=128, slots_per_bucket=4,
                             replicas=3))


def test_pool_insert_search_delete(pool):
    keys = np.arange(100, 200).astype(np.int32)
    pages = pool.alloc_pages(0, len(keys))
    assert (pages >= 0).all()
    pool.write_pages(0, pages, keys, opcode=1)
    ok = pool.insert_batch(0, keys, pages)
    assert ok.all()
    assert pool.check_replicas_converged()
    ptr, found = pool.search(keys)
    assert found.all()
    # key verification on pages makes pointers exact despite fp collisions
    assert (ptr == pages).all()
    okd = pool.delete_batch(0, keys[:50])
    assert okd.all()
    _, found2 = pool.search(keys)
    assert abs(found2.mean() - 0.5) < 0.05


def test_pool_two_level_allocation_amortizes_grants(pool):
    pages = pool.alloc_pages(1, 100)
    # 100 pages out of 64-page chunks -> only 2 coarse grants (ALLOC RPCs)
    assert pool.metrics.counter("pool.alloc.grants").value == 2
    assert len(set(pages.tolist())) == 100


def test_pool_elastic_add_shard(pool):
    """The serving twin of add_mn: a new grant shard joins the ring,
    ungranted chunks re-home onto it, granted chunks (live pages) stay
    owned, and allocation keeps working across the scale-out."""
    keys = np.arange(1, 65).astype(np.int32)
    pages = pool.alloc_pages(0, len(keys))
    pool.write_pages(0, pages, keys, opcode=1)
    assert pool.insert_batch(0, keys, pages).all()
    before = pool.grant.copy()
    new_shard = pool.add_shard()
    assert pool.cfg.n_shards == new_shard + 1
    assert (pool.grant == before).all()          # ownership never moves
    assert (pool.shard_of_chunk[pool.grant == 0] == new_shard).any()
    _, found = pool.search(keys)
    assert found.all()                           # live pages untouched
    p2 = pool.alloc_pages(7, 32)                 # allocation still works
    assert (p2 >= 0).all()


def test_pool_free_and_reclaim(pool):
    pages = pool.alloc_pages(0, 64)
    pool.write_pages(0, pages, np.arange(64).astype(np.int32) + 1, opcode=1)
    pool.free_pages(pages[:32])
    n = pool.reclaim(0)
    assert n >= 32
    # reclaimed pages are reusable
    p2 = pool.alloc_pages(0, 32)
    assert (p2 >= 0).all()


def test_pool_concurrent_writers_single_winner(pool):
    """Two clients INSERT the same keys -> exactly one wins per key and the
    index replicas converge (the SNAPSHOT guarantee at the pool level)."""
    keys = np.arange(500, 532).astype(np.int32)
    pg0 = pool.alloc_pages(0, len(keys))
    pg1 = pool.alloc_pages(1, len(keys))
    pool.write_pages(0, pg0, keys, opcode=1)
    pool.write_pages(1, pg1, keys, opcode=1)
    ok0 = pool.insert_batch(0, keys, pg0)
    ok1 = pool.insert_batch(1, keys, pg1)
    ptr, found = pool.search(keys)
    assert found.all()
    assert pool.check_replicas_converged()
    # each key points at exactly one of the two proposals
    assert ((ptr == pg0) | (ptr == pg1)).all()


def test_pool_crash_recovery_redoes_uncommitted(pool):
    keys = np.arange(300, 340).astype(np.int32)
    pages = pool.alloc_pages(1, len(keys))
    pool.write_pages(1, pages, keys, opcode=1)
    # crash BEFORE the index insert: pages written, log uncommitted
    pool.crash_client(1)
    st = pool.recover_client(1, reassign_to=2)
    assert st["used_pages"] == len(keys)
    assert st["redone"] == len(keys)
    _, found = pool.search(keys)
    assert found.all()
    # recovered pages re-owned by client 2
    assert (pool.grant == 2 + 1).sum() >= 1


def test_pool_recovery_idempotent(pool):
    keys = np.arange(700, 720).astype(np.int32)
    pages = pool.alloc_pages(3, len(keys))
    pool.write_pages(3, pages, keys, opcode=1)
    ok = pool.insert_batch(3, keys, pages)   # committed normally
    assert ok.all()
    st = pool.recover_client(3)
    assert st["redone"] == 0, "committed ops must never be redone"


# ------------------------------------------------------------ engine -------
def test_engine_serves_and_hits_prefix_cache():
    from repro.configs import base as C
    from repro.models import build
    mesh = make_host_mesh((1, 1), ("data", "model"))
    r = C.reduced(C.get("llama3-8b"))
    m = build(r, mesh, use_kernels=True)
    params = m.init(jax.random.key(0))
    eng = ServeEngine(m, params, max_batch=2, max_len=128,
                      pool_cfg=PoolConfig(n_pages=256, n_buckets=64,
                                          slots_per_bucket=4))
    rng = np.random.default_rng(0)
    shared = rng.integers(0, r.vocab, 64).astype(np.int32)
    for i in range(4):
        tail = rng.integers(0, r.vocab, 16).astype(np.int32)
        eng.submit(Request(rid=i, prompt=np.concatenate([shared, tail]),
                           max_new=4))
    done = eng.run(max_ticks=60)
    assert len(done) == 4
    assert all(len(q.out) == 4 for q in done)
    # later requests hit the shared 64-token prefix block
    assert sum(q.prefix_hits for q in done) >= 2
    assert eng.pool.check_replicas_converged()
    # ordered listing of live prefixes (the serving scan twin): sorted
    # block-hash keys, each backed by a live page in the device index
    listed = eng.list_prefixes(0, 64)
    assert listed, "prefix blocks were inserted, listing must see them"
    keys = [k for k, _p in listed]
    assert keys == sorted(set(keys))
    assert all(p >= 0 for _k, p in listed)
