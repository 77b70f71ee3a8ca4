"""Bytes the pool reads from the device per op of the window: the program's
counter ``pool.d2h_bytes`` (every array that ``KVPool._fetch`` reads) over
the window's ops of every kind."""


def read(r):
    ops = sum(r.ops.values())
    nbytes = r.counters.get("pool.d2h_bytes")
    if not ops or nbytes is None:
        return None
    return nbytes / ops
