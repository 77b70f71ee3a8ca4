"""The write path's jitted steps (``KVPool.write_pages`` and
``KVPool.insert_batch``) against the eager jnp code they replaced, kept
here as the oracle (with the RACE slot-match rule written out again: fp,
slot in use, and the page's key record equal to the key's): on the same
seed, every device array, the pool's PRNG key and the returned mask must
be bit-identical after every call.  Also: the steps compile once per
padded batch shape."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import PoolConfig
from repro.serving import slots_jax as SL
from repro.serving.kvpool import (KVPool, OP_INSERT, OP_UPDATE, _pad,
                                  _padded, as_key_rows)
from repro.serving.snapshot_jax import snapshot_epoch

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------ the eager oracle ------
def _eager_write_pages(pool, cid, pages, keys, opcode):
    keys = as_key_rows(keys)
    sl = pool._slab(cid)
    n = len(pages)
    nxt = np.array([sl.free[0] + 1 if sl.free else 0] * n, np.int32)
    for i in range(n - 1):
        nxt[i] = pages[i + 1] + 1
    prv = np.concatenate([[sl.last_alloc], pages[:-1] + 1]).astype(np.int32)
    if n:
        sl.last_alloc = int(pages[-1]) + 1
    W = _padded(n)
    entries = np.zeros((W, 4), np.int32)
    entries[:n, 1:] = np.stack([np.full(n, opcode), keys[:, 0], np.ones(n)],
                               1)
    links = np.zeros((W, 2), np.int32)
    links[:n] = np.stack([nxt, prv], 1)
    pg = jnp.asarray(_pad(np.asarray(pages, np.int32), W, pool.cfg.n_pages))
    pool.log = pool.log.at[pg].set(jnp.asarray(entries), mode="drop")
    pool.chain = pool.chain.at[pg].set(jnp.asarray(links), mode="drop")
    pool.page_keys = pool.page_keys.at[pg[:n]].set(jnp.asarray(keys[:, 1:]))


def _eager_insert_batch(pool, keys, pages):
    cfg = pool.cfg
    keys = as_key_rows(keys)
    n = len(keys)
    W = _padded(n)
    fold = jnp.asarray(_pad(keys[:, 0], W, 0))
    recs = jnp.zeros((W, keys.shape[1] - 1), jnp.int32).at[:n].set(
        keys[:, 1:])
    pages_j = jnp.asarray(_pad(np.asarray(pages, np.int32), W, 0))
    b1, b2 = SL.bucket_pair(fold, cfg.n_buckets)
    fp = SL.fingerprint(fold)
    v_new = SL.pack_slot(fp, pages_j)
    done = np.arange(W) >= n
    spb = cfg.slots_per_bucket
    for _ in range(2 * spb):
        idx0 = pool.index[0].reshape(cfg.n_buckets, spb)
        rows = jnp.concatenate([idx0[b1], idx0[b2]], axis=1)
        offs = jnp.concatenate([b1[:, None] * spb + jnp.arange(spb)[None],
                                b2[:, None] * spb + jnp.arange(spb)[None]],
                               axis=1)
        page_keys = pool.page_keys[SL.slot_ptr(rows)]       # (W, 2spb, 6)
        is_match = ((SL.slot_fp(rows) == fp[:, None]) & (rows != 0)
                    & jnp.all(page_keys == recs[:, None, :], axis=-1))
        is_empty = rows == 0
        cand = jnp.where(is_match.any(1), jnp.argmax(is_match, 1),
                         jnp.argmax(is_empty, 1))
        ok = is_match.any(1) | is_empty.any(1)
        slot = jnp.take_along_axis(offs, cand[:, None], 1)[:, 0]
        v_old = jnp.take_along_axis(rows, cand[:, None], 1)[:, 0]
        slot_i = jnp.where(jnp.asarray(~done) & ok, slot, -1)
        pool.key, k = jax.random.split(pool.key)
        res = snapshot_epoch(pool.index, slot_i, v_old, v_new, k)
        pool.index = res.index
        superseded = np.asarray(jnp.where(res.win & (v_old != 0),
                                          SL.slot_ptr(v_old), -1))
        win = np.asarray(res.win)
        wpg = jnp.where(res.win, pages_j, cfg.n_pages)
        pool.log = pool.log.at[wpg, 0].set(v_old | jnp.int32(1 << 30),
                                           mode="drop")
        pool.free_pages(superseded[superseded >= 0])
        done |= win
        if done.all():
            break
    return done[:n]


class Twin:
    """The pool under test and the oracle's pool, driven alike."""

    def __init__(self, cfg: PoolConfig, seed: int):
        self.new = KVPool(cfg, seed=seed)
        self.old = KVPool(cfg, seed=seed)

    def same(self):
        for name in ("index", "log", "page_keys", "chain", "free_bitmap",
                     "key"):
            np.testing.assert_array_equal(np.asarray(getattr(self.new, name)),
                                          np.asarray(getattr(self.old, name)),
                                          err_msg=name)

    def upsert(self, keys, opcode=OP_INSERT, cid=0) -> np.ndarray:
        keys = as_key_rows(keys)
        pages = self.new.alloc_pages(cid, len(keys))
        np.testing.assert_array_equal(self.old.alloc_pages(cid, len(keys)),
                                      pages)
        assert (pages >= 0).all()
        self.new.write_pages(cid, pages, keys, opcode)
        _eager_write_pages(self.old, cid, pages, keys, opcode)
        self.same()
        ok = self.new.insert_batch(cid, keys, pages, opcode)
        np.testing.assert_array_equal(ok, _eager_insert_batch(self.old, keys,
                                                              pages))
        self.same()
        return ok

    def reclaim(self, cid=0) -> int:
        n = self.new.reclaim(cid)
        assert self.old.reclaim(cid) == n
        self.same()
        return n

    def counter(self, name) -> int:
        return self.new.metrics.counter("pool." + name).value


def _same_bucket_and_fp(n_buckets, count):
    """``count`` keys with one fingerprint and one first bucket."""
    ks = np.arange(1, 100_001, dtype=np.int32)
    b1, _ = SL.bucket_pair(jnp.asarray(ks), n_buckets)
    code = np.asarray(SL.fingerprint(jnp.asarray(ks))) * n_buckets \
        + np.asarray(b1)
    _, group, size = np.unique(code, return_inverse=True, return_counts=True)
    assert size.max() >= count
    return ks[group == np.argmax(size)][:count]


SMALL = PoolConfig(n_pages=1024, n_buckets=16, slots_per_bucket=4,
                   replicas=3, chunk_pages=64, n_shards=4)
MID = PoolConfig(n_pages=4096, n_buckets=256, slots_per_bucket=8,
                 replicas=3, chunk_pages=64, n_shards=4)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_fresh_inserts_then_updates_that_supersede(seed):
    t = Twin(MID, seed & 0x7FFFFFFF)
    rng = np.random.default_rng(seed)
    keys = rng.choice(np.arange(1, 50_000, dtype=np.int32), 600,
                      replace=False)
    assert t.upsert(keys[:200]).all()
    assert t.upsert(keys[200:300]).all()
    freed = np.asarray(t.new.free_bitmap).sum()
    assert t.upsert(keys[:150], OP_UPDATE).all()
    # each UPDATE superseded its key's old page and freed it on the device
    assert np.asarray(t.new.free_bitmap).sum() == freed + 150
    assert t.upsert(keys[100:101], OP_UPDATE).all()        # a batch of 1
    assert t.reclaim() > 0
    assert t.upsert(rng.permutation(keys[:300])[:120], OP_UPDATE).all()
    assert t.upsert(keys[300:301]).all()                   # a batch of 1


@pytest.mark.parametrize("size", [256, 257])
def test_batches_either_side_of_a_padded_size(size):
    t = Twin(MID, seed=size)
    keys = np.arange(1, 2 * size + 1, dtype=np.int32) * 7
    t.upsert(keys[:size])
    t.upsert(keys[size // 2:size // 2 + size], OP_UPDATE)
    assert t.counter("upsert.keys") == 2 * size


def test_fingerprint_collisions_in_one_bucket():
    t = Twin(SMALL, seed=11)
    same = _same_bucket_and_fp(SMALL.n_buckets, 6)
    assert t.upsert(same).all()
    # every key holds its own slot though all share a fingerprint
    _, found = t.new.search(same)
    assert found.all()
    assert t.upsert(same[::-1], OP_UPDATE).all()
    assert t.upsert(same[2:3], OP_UPDATE).all()


def test_same_slot_contention_takes_more_than_one_attempt():
    t = Twin(SMALL, seed=5)
    same = _same_bucket_and_fp(SMALL.n_buckets, 5)
    t.upsert(same)
    assert t.counter("upsert.attempts") > t.counter("upsert.calls") == 1


def test_keys_with_one_fold_hold_slots_of_their_own():
    """``x`` and ``x + 2**31`` share a fold, so a bucket pair and a
    fingerprint: only their records tell them apart."""
    t = Twin(SMALL, seed=13)
    base = np.arange(1, 9, dtype=np.int64)
    both = np.concatenate([base, base + 2**31])
    rows = as_key_rows(both)
    assert (rows[:8, 0] == rows[8:, 0]).all()
    assert (rows[:8, 1:] != rows[8:, 1:]).any(1).all()
    assert t.upsert(both).all()
    ptr, found = t.new.search(both)
    assert found.all() and len(set(ptr.tolist())) == 16
    # each pair's second key meets its partner's page first
    assert t.counter("search.aliased") >= 1
    assert t.upsert(both[::-3], OP_UPDATE).all()
    ptr2, found2 = t.new.search(both)
    assert found2.all() and len(set(ptr2.tolist())) == 16
    assert (ptr2 != ptr).sum() == len(both[::-3])


def test_full_bucket_pairs_answer_not_ok():
    t = Twin(SMALL, seed=9)
    keys = np.arange(1, 151, dtype=np.int32)
    ok = t.upsert(keys)                 # 150 keys, 64 slots
    assert ok.any() and not ok.all()
    t.upsert(keys[::3], OP_UPDATE)


def test_steps_compile_once_per_padded_size():
    pool = KVPool(PoolConfig(n_pages=8192, n_buckets=2048, slots_per_bucket=8,
                             replicas=3), seed=1)
    fresh = iter(range(1, 10**6))

    def upsert(n, update_from=None):
        keys = (np.arange(update_from, update_from + n, dtype=np.int32)
                if update_from else
                np.array([next(fresh) for _ in range(n)], np.int32))
        pages = pool.alloc_pages(0, n)
        pool.write_pages(0, pages, keys, OP_UPDATE if update_from else
                         OP_INSERT)
        return pool.insert_batch(0, keys, pages)

    for n in (3, 300):                  # one call at each of 256 and 512
        assert upsert(n).all()
    compiles = [0]

    def on_event(name, _secs, **_kw):
        compiles[0] += 1 if name == COMPILE_EVENT else 0

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for n in (1, 100, 256, 257, 400, 512):
            assert upsert(n).all()
        assert upsert(200, update_from=1).all()
        assert upsert(450, update_from=100).all()
        assert compiles[0] == 0
        upsert(600)                     # a new padded size does compile
        assert compiles[0] > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
