"""Host time spent waiting for the device on the GET path, per GET call of
the window: the totals of the program's spans ``kv.pool.search.wait`` (the
probe's answers) and ``kv.pool.search.collision.wait`` (the collision
path's reads, the log's key column among them), from the profiler trace."""

WAITS = ("kv.pool.search.wait", "kv.pool.search.collision.wait")


def read(r):
    n = r.calls.get("get", 0)
    if not n or not r.trace.span_calls.get(WAITS[0]):
        return None
    return sum(r.trace.span_total_s.get(k, 0.0) for k in WAITS) / n * 1e3
