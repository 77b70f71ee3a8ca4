"""The device store's own instrumentation: the ``kv.*`` profiler spans of
``KVPool``, ``DeviceBackend`` and ``KVFuture``, and the ``pool.*``
counters of ``KVPool.metrics``."""
import glob
import types

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.core.api import KVStore, Op
from repro.serving import DeviceBackend, PoolConfig, kvpool

RECORDS = 2000


def _store(seed=3):
    """A store loaded with ``RECORDS`` keys at about the cells' slot load
    (19%), large enough that a GET of every key meets a fingerprint
    collision."""
    be = DeviceBackend(PoolConfig(n_pages=4096, n_buckets=1024,
                                  slots_per_bucket=8), seed=seed)
    kv = KVStore(be)
    _call(kv, [Op.insert(k, b"v%d" % k) for k in range(1, RECORDS + 1)])
    return kv, be.pool


def _call(kv, ops):
    return [f.result() for f in kv.submit_batch(ops)]


def _counts(pool):
    return dict(pool.metrics.snapshot()["counters"])


def _diff(pool, before):
    return {k: v - before.get(k, 0) for k, v in _counts(pool).items()}


def _host_spans(trace_dir):
    """The ``kv.*`` events of the one host thread that has them, each with
    the name of the ``kv.*`` span directly around it (None at the top)."""
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in ln.events if e.name.startswith("kv.")]
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for ln in plane.lines]
    events, = [ev for ev in lines if ev]
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        out.append((name, stack[-1][0] if stack else None))
        stack.append((name, s, e))
    return out


def test_profile_shows_the_spans_nested_as_the_code_is(tmp_path):
    kv, pool = _store()
    every = list(range(1, RECORDS + 1))
    _call(kv, [Op.update(k, b"w") for k in every[:64]])    # warm shapes
    before = _counts(pool)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _call(kv, [Op.update(k, b"x") for k in every[:64]])
        _call(kv, [Op.get(k) for k in every])
        pool.reclaim(0)
    finally:
        jax.profiler.stop_trace()
    n = _diff(pool, before)
    spans = _host_spans(tmp_path)
    parents = {}
    for name, parent in spans:
        parents.setdefault(name, set()).add(parent)
    assert parents == {
        "kv.backend.keys": {None},
        "kv.backend.upsert": {None},
        "kv.pool.alloc": {"kv.backend.upsert"},
        "kv.pool.write": {"kv.backend.upsert"},
        "kv.pool.upsert": {"kv.backend.upsert"},
        "kv.pool.upsert.prepare": {"kv.pool.upsert"},
        "kv.pool.upsert.attempt": {"kv.pool.upsert"},
        "kv.pool.upsert.select": {"kv.pool.upsert.attempt"},
        "kv.pool.upsert.epoch": {"kv.pool.upsert.attempt"},
        "kv.pool.upsert.wait": {"kv.pool.upsert.attempt"},
        "kv.pool.upsert.commit": {"kv.pool.upsert.attempt"},
        "kv.codec.encode": {"kv.backend.upsert"},
        "kv.codec.decode": {None},
        "kv.backend.search": {None},
        "kv.pool.search": {"kv.backend.search"},
        "kv.pool.search.probe": {"kv.pool.search"},
        "kv.pool.search.check": {"kv.pool.search"},
        "kv.pool.search.wait": {"kv.pool.search"},
        "kv.pool.search.collision": {"kv.pool.search"},
        "kv.pool.search.collision.wait": {"kv.pool.search.collision"},
        "kv.pool.reclaim": {None},
        "kv.pool.reclaim.wait": {"kv.pool.reclaim"},
    }
    count = {k: sum(1 for name, _ in spans if name == k) for k in parents}
    # one span a call, or an attempt; the codec's, one an op
    assert count["kv.backend.upsert"] == count["kv.pool.upsert"] == 1
    assert count["kv.pool.upsert.attempt"] == n["pool.upsert.attempts"]
    assert count["kv.pool.upsert.wait"] == n["pool.upsert.attempts"]
    assert count["kv.pool.search"] == count["kv.pool.search.wait"] == 1
    assert count["kv.pool.search.check"] == 1
    assert count["kv.backend.keys"] == 2            # one a submit_batch
    assert count["kv.pool.search.collision"] == 1
    assert count["kv.codec.encode"] == 64
    assert count["kv.codec.decode"] == 64 + RECORDS


def _spy(monkeypatch, pool):
    """Count what the pool does, from outside it: SNAPSHOT epochs (the
    module-global ``snapshot_epoch``), searches, collision-path calls, and
    every ``np.asarray`` of a device array in ``kvpool``, with its bytes."""
    seen = {"epochs": 0, "searches": 0, "collisions": 0, "reclaims": 0,
            "reads": 0, "bytes": 0}

    def counted(obj, attr, key):
        fn = getattr(obj, attr)

        def call(*a, **k):
            seen[key] += 1
            return fn(*a, **k)
        monkeypatch.setattr(obj, attr, call)

    counted(kvpool, "snapshot_epoch", "epochs")
    counted(pool, "search", "searches")
    counted(pool, "_search_all_candidates", "collisions")
    counted(pool, "reclaim", "reclaims")

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            seen["reads"] += 1
            seen["bytes"] += a.nbytes
        return np.asarray(a, *args, **kw)
    monkeypatch.setattr(kvpool, "np", types.SimpleNamespace(
        **{**vars(np), "asarray": asarray}))
    return seen


def test_counters_match_what_the_code_does(monkeypatch):
    kv, pool = _store()
    every = list(range(1, RECORDS + 1))
    seen = _spy(monkeypatch, pool)
    before = _counts(pool)
    _call(kv, [Op.update(k, b"y") for k in every[::7]])
    _call(kv, [Op.get(k) for k in every])
    _call(kv, [Op.get(k) for k in every[:100]])
    pool.reclaim(0)
    n = _diff(pool, before)
    assert n["pool.upsert.calls"] == 1
    assert n["pool.upsert.keys"] == len(every[::7])
    assert n["pool.upsert.attempts"] == seen["epochs"] >= 1
    # the first attempt has every key pending, each later one fewer
    assert n["pool.upsert.writers"] >= n["pool.upsert.keys"]
    assert n["pool.search.calls"] == seen["searches"] == 2
    assert n["pool.search.keys"] == RECORDS + 100
    # a GET of every key meets a fingerprint collision
    assert n["pool.search.shadowed"] > 0
    assert seen["collisions"] >= 1
    # one device program to write the pages, one to prepare the batch,
    # then select, epoch and commit in each attempt
    assert n["pool.upsert.dispatches"] == 1 + 1 + 3 * seen["epochs"]
    # the reads the code makes, and no more: 1 an attempt, 1 a search, 1 a
    # collision-path call, 1 a reclaim
    assert n["pool.host_syncs"] == seen["reads"] == (
        seen["epochs"] + seen["searches"] + seen["collisions"]
        + seen["reclaims"])
    assert n["pool.d2h_bytes"] == seen["bytes"] > 0


def test_delete_counts_its_epoch_and_reads(monkeypatch):
    kv, pool = _store()
    seen = _spy(monkeypatch, pool)
    before = _counts(pool)
    _call(kv, [Op.delete(k) for k in range(1, 51)])
    n = _diff(pool, before)
    assert n["pool.delete.epochs"] == seen["epochs"] == 1
    assert n["pool.upsert.attempts"] == n["pool.upsert.dispatches"] == 0
    # the delete's own search, then the epoch's two reads
    assert n["pool.host_syncs"] == seen["reads"] == (
        seen["searches"] + seen["collisions"] + 2)
    assert n["pool.d2h_bytes"] == seen["bytes"]


def test_device_backend_stats_keep_their_keys():
    kv, pool = _store()
    st = kv.stats()
    assert {"backend", "cid", "inflight", "crashed", "pages_valued",
            "alloc_rpcs", "epochs", "search_batches"} <= set(st)
    c = _counts(pool)
    assert st["alloc_rpcs"] == c["pool.alloc.grants"] > 0
    assert st["epochs"] == (c["pool.upsert.attempts"]
                            + c["pool.delete.epochs"]) > 0
    assert st["search_batches"] == c["pool.search.calls"]
    _call(kv, [Op.get(1), Op.delete(2)])
    st2 = kv.stats()
    assert st2["search_batches"] == st["search_batches"] + 2
    assert st2["epochs"] == st["epochs"] + 1


def test_pool_keeps_no_stats_dict():
    _, pool = _store()
    assert not hasattr(pool, "stats")
    assert set(_counts(pool)) == {"pool." + k for k in kvpool.COUNTERS}
