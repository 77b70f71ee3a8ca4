"""Share of the window's searched keys whose first fingerprint match points
at another key's page, so that ``KVPool.search`` takes the collision path
for them: the program's counters ``pool.search.shadowed`` over
``pool.search.keys``, in percent."""


def read(r):
    keys = r.counters.get("pool.search.keys")
    shadowed = r.counters.get("pool.search.shadowed")
    if not keys or shadowed is None:
        return None
    return 100.0 * shadowed / keys
