"""Device programs launched per ``KVPool.insert_batch`` call of the window,
from the program's counters: ``pool.upsert.dispatches`` (one by
``write_pages``, one prepare and three an attempt by ``insert_batch``) over
``pool.upsert.calls``."""


def read(r):
    calls = r.counters.get("pool.upsert.calls")
    dispatches = r.counters.get("pool.upsert.dispatches")
    if not calls or dispatches is None:
        return None
    return dispatches / calls
