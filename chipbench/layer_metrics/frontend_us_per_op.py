"""Host time of the front end per op: ``KVStore.submit_batch`` and every
``KVFuture.result()`` of the window's calls, less the time inside the
pool's methods and the codec (``core/api.py``, ``serving/backend.py``)."""

POOL = ("cb.pool.search", "cb.pool.alloc_pages", "cb.pool.reclaim",
        "cb.pool.write_pages", "cb.pool.insert_batch")
CODEC = ("cb.codec.encode_value", "cb.codec.decode_value")


def read(r):
    ops = sum(r.ops.values())
    if not ops:
        return None
    inner = sum(r.spans.seconds.get(n, 0.0) for n in POOL + CODEC)
    return (r.frontend_s - inner) / ops * 1e6
