"""SNAPSHOT attempts per ``KVPool.insert_batch`` call of the window, from the
program's counters: ``pool.upsert.attempts`` over ``pool.upsert.calls``.
1.0 means every writer of every call settled at its first attempt."""


def read(r):
    calls = r.counters.get("pool.upsert.calls")
    attempts = r.counters.get("pool.upsert.attempts")
    if not calls or attempts is None:
        return None
    return attempts / calls
