"""Full key identity on the device store: keys whose 31-bit folds are equal
stay apart through ``KVStore`` over ``DeviceBackend``, checked against a
plain dict under seeded batches of INSERT, UPDATE, GET and DELETE.

The fold picks a key's bucket pair and fingerprint; only the key record
kept beside each page (``codec.key_record``) tells aliased keys apart.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec
from repro.core.api import KVStore, Op
from repro.core.events import NOT_FOUND, OK
from repro.serving import DeviceBackend, PoolConfig
from repro.serving import slots_jax as SL
from repro.serving.backend import _key32, encode_keys
from repro.serving.kvpool import OP_INSERT, as_key_rows

# YCSB's own keys ("user" + fnvhash64(record - 1), CoreWorkload with
# orderedinserts false) of record pairs among the first 1M records whose
# folds are equal: (54594, 60053), (58274, 101524), (68875, 105226),
# (85670, 139216), (172087, 181485)
YCSB_PAIRS = [
    ("user5751819879101565123", "user1851401732705620973"),
    ("user8556165338013714101", "user395005973130097549"),
    ("user5333989594979160023", "user3782381931935479284"),
    ("user6366752038442395533", "user7799771756601529833"),
    ("user1551041044270235617", "user340217519973083129"),
]
INT_PAIRS = [(x, x + 2**31) for x in (5, 77, 1234, 2**30 + 3)]
OTHERS = [5, b"\x05", "k", b"k", "", "é", 0, -1, 2**64 - 2,
          b"x" * codec.KEY_BYTES_MAX] + [f"key-{i}" for i in range(16)]
KEYS = [k for pair in YCSB_PAIRS + INT_PAIRS for k in pair] + OTHERS


def _store(seed=0, **cfg):
    pool_cfg = dict(n_pages=2048, n_buckets=64, slots_per_bucket=8,
                    replicas=3)
    pool_cfg.update(cfg)
    be = DeviceBackend(PoolConfig(**pool_cfg), seed=seed)
    return KVStore(be), be.pool


def _call(kv, ops):
    return [f.result() for f in kv.submit_batch(ops)]


def _same(key):
    """The key as the store holds it: a str is its utf-8 bytes, an int
    its 64-bit value."""
    if isinstance(key, str):
        return key.encode("utf-8")
    if isinstance(key, int):
        return ("int", key & (2**64 - 1))
    return bytes(key)


def _count(pool, name):
    return pool.metrics.counter("pool." + name).value


def test_the_pairs_alias_under_the_fold():
    for a, b in YCSB_PAIRS + INT_PAIRS:
        assert a != b and _key32(a) == _key32(b)
    recs, rows = encode_keys(KEYS)
    assert len({_same(k) for k in KEYS}) == len(set(recs))
    assert (rows[:, 0] == [_key32(k) for k in KEYS]).all()


def test_key_records_are_exact_and_tagged():
    assert codec.key_record(5) != codec.key_record(b"\x05")
    assert codec.key_record("é") == codec.key_record("é".encode())
    assert codec.key_record(-1) == codec.key_record(2**64 - 1)
    assert all(len(codec.key_record(k)) == 4 * codec.KEY_WORDS for k in KEYS)
    assert b"\x00" * 24 not in {codec.key_record(k) for k in KEYS}
    # a 1-D integer array given to the pool is a batch of integer keys
    ints = [0, 5, -1, 2**31 + 5, 2**63 - 1, -2**63]
    np.testing.assert_array_equal(as_key_rows(np.array(ints, np.int64)),
                                  encode_keys(ints)[1])


@pytest.mark.parametrize("key", [b"y" * 24, "z" * 24, "é" * 12,
                                 3.5, None])
def test_a_key_the_record_cannot_hold_raises_before_anything_runs(key):
    kv, pool = _store()
    ok_key = b"x" * codec.KEY_BYTES_MAX
    with pytest.raises(codec.CodecError) as e:
        kv.submit_batch([Op.put(ok_key, b"v"), Op.put(key, b"w")])
    if isinstance(key, (bytes, str)):
        assert str(codec.KEY_BYTES_MAX) in str(e.value)
    assert _count(pool, "upsert.calls") == 0
    assert kv.get(ok_key) is None
    assert kv.put(ok_key, b"v").status == OK
    assert kv.get(ok_key) == b"v"


def test_an_int_key_and_its_byte_are_two_keys():
    kv, _ = _store()
    _call(kv, [Op.put(5, b"int"), Op.put(b"\x05", b"byte")])
    assert kv.get(5) == b"int" and kv.get(b"\x05") == b"byte"
    assert kv.delete(5).status == OK
    assert kv.get(5) is None and kv.get(b"\x05") == b"byte"


@pytest.mark.parametrize("a,b", [YCSB_PAIRS[0], INT_PAIRS[1]])
def test_two_aliased_keys_in_one_update_batch(a, b):
    kv, pool = _store(seed=4)
    res = _call(kv, [Op.put(a, b"a1"), Op.put(b, b"b1"), Op.put(a, b"a2")])
    assert [r.status for r in res] == [OK] * 3
    assert [r.value for r in res] == [b"a2", b"b1", b"a2"]
    assert res[0].page == res[2].page != res[1].page
    assert _count(pool, "upsert.keys") == 2
    got = _call(kv, [Op.get(a), Op.get(b)])
    assert [r.value for r in got] == [b"a2", b"b1"]
    # whichever was placed second meets its partner's page first
    assert _count(pool, "search.aliased") >= 1
    assert _count(pool, "h2d_key_bytes") > 0
    assert kv.delete(b).status == OK
    assert kv.get(a) == b"a2" and kv.get(b) is None


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 9])
def test_random_batches_match_a_dict(seed):
    """Seeded single-kind batches over keys that alias in pairs; every
    answer and a final read of every key against a dict model."""
    rng = np.random.default_rng(seed)
    kv, pool = _store(seed=seed & 0x7FFFFFFF)
    model = {}
    for step in range(60):
        kind = rng.choice(["insert", "update", "get", "delete"],
                          p=[0.3, 0.25, 0.3, 0.15])
        n = int(rng.integers(1, 13))
        picks = [KEYS[i] for i in rng.integers(0, len(KEYS), n)]
        if kind == "delete":            # a key once: deletes of one key in
            seen, uniq = set(), []      # a batch are concurrent
            for k in picks:
                if _same(k) not in seen:
                    seen.add(_same(k))
                    uniq.append(k)
            picks = uniq
        if kind == "get":
            res = _call(kv, [Op.get(k) for k in picks])
            for k, r in zip(picks, res):
                want = model.get(_same(k))
                assert (r.status, r.value) == ((OK, want) if want else
                                               (NOT_FOUND, None)), (step, k)
        elif kind == "delete":
            res = _call(kv, [Op.delete(k) for k in picks])
            for k, r in zip(picks, res):
                had = model.pop(_same(k), None)
                assert r.status == (OK if had else NOT_FOUND), (step, k)
        else:
            vals = [b"%d-%d" % (step, i) for i in range(len(picks))]
            make = Op.insert if kind == "insert" else Op.update
            res = _call(kv, [make(k, v) for k, v in zip(picks, vals)])
            last = {_same(k): v for k, v in zip(picks, vals)}
            for k, r in zip(picks, res):
                assert (r.status, r.value) == (OK, last[_same(k)]), (step, k)
            model.update(last)
    res = _call(kv, [Op.get(k) for k in KEYS])
    for k, r in zip(KEYS, res):
        assert r.value == model.get(_same(k)), k
    assert pool.check_replicas_converged()


def test_collision_path_with_a_planted_fingerprint_collision():
    """Keys of one first bucket and one fingerprint, but other folds: a
    GET of the second is shadowed by the first's slot, and the collision
    path on the device finds it, or answers NOT_FOUND for an absent one."""
    kv, pool = _store(seed=2, n_buckets=16, slots_per_bucket=4)
    ks = np.arange(1, 20_001, dtype=np.int32)
    b1, _ = SL.bucket_pair(jnp.asarray(ks), 16)
    code = np.asarray(SL.fingerprint(jnp.asarray(ks))) * 16 + np.asarray(b1)
    _, group, size = np.unique(code, return_inverse=True, return_counts=True)
    same = [int(k) for k in ks[group == np.argmax(size)][:4]]
    assert len(same) == 4
    _call(kv, [Op.put(k, b"%d" % k) for k in same[:3]])
    before = _count(pool, "search.shadowed")
    res = _call(kv, [Op.get(k) for k in same])
    assert [r.value for r in res] == [b"%d" % k for k in same[:3]] + [None]
    assert _count(pool, "search.shadowed") - before >= 2
    assert _count(pool, "search.aliased") == 0     # no fold is shared


def test_recover_client_redo_keeps_the_record():
    """A crash between the page write and the index write: the redo
    indexes each page under its own key record, so keys that alias stay
    apart after recovery."""
    kv, pool = _store(seed=6)
    keys = [k for pair in YCSB_PAIRS[:3] + INT_PAIRS[:2] for k in pair]
    _, rows = encode_keys(keys)
    pages = pool.alloc_pages(1, len(keys))
    pool.write_pages(1, pages, rows, opcode=OP_INSERT)
    pool.crash_client(1)
    st = pool.recover_client(1, reassign_to=2)
    assert st["redone"] == len(keys)
    np.testing.assert_array_equal(np.asarray(pool.page_keys)[pages],
                                  rows[:, 1:])
    ptr, found = pool.search(rows)
    assert found.all()
    np.testing.assert_array_equal(ptr, pages)


def test_scan_lists_aliased_keys_by_fold():
    """Scans stay in fold order; both keys of an aliased pair are listed,
    each validated on the device under its own record."""
    kv, _ = _store(seed=8)
    (a, b), (c, d) = INT_PAIRS[:2]
    _call(kv, [Op.put(k, b"v%d" % i) for i, k in enumerate((a, b, c, d))])
    kv.delete(d)
    res = kv.submit(Op.scan(0, 10)).result()
    assert sorted(res.value) == [(a, b"v0"), (a, b"v1"), (c, b"v2")]
    rng = kv.submit(Op.range(a, c + 1)).result()
    assert sorted(rng.value) == sorted(res.value)
