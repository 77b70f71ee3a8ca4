"""Mean host-clock span of ``KVPool.search`` per GET batch, the collision
path included; it returns host arrays, so its span ends on the device."""


def read(r):
    n = r.spans.calls.get("cb.pool.search", 0)
    if not n:
        return None
    return r.spans.seconds["cb.pool.search"] / n * 1e3
