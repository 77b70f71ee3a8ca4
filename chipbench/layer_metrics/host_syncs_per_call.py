"""Device-to-host reads of the pool per ``submit_batch`` call of the window:
the program's counter ``pool.host_syncs`` (one per array that
``KVPool._fetch`` reads) over the window's calls of every kind."""


def read(r):
    calls = sum(r.calls.values())
    syncs = r.counters.get("pool.host_syncs")
    if not calls or syncs is None:
        return None
    return syncs / calls
