"""Host-clock time per UPDATE batch in the pool: ``alloc_pages``,
``reclaim``, ``write_pages`` and ``insert_batch``, the last span ended
when the pool's device arrays are ready."""

NAMES = ("cb.pool.alloc_pages", "cb.pool.reclaim", "cb.pool.write_pages",
         "cb.pool.insert_batch")


def read(r):
    n = r.calls.get("update", 0)
    if not n or not r.spans.calls.get("cb.pool.insert_batch"):
        return None
    return sum(r.spans.seconds.get(k, 0.0) for k in NAMES) / n * 1e3
