"""Host time spent waiting for the device on the UPDATE path, per UPDATE
call of the window: the totals of the program's spans
``kv.pool.upsert.wait`` (each SNAPSHOT attempt's read of its winners) and
``kv.pool.reclaim.wait`` (the owner's read of the free bitmap), from the
profiler trace."""

WAITS = ("kv.pool.upsert.wait", "kv.pool.reclaim.wait")


def read(r):
    n = r.calls.get("update", 0)
    if not n or not r.trace.span_calls.get(WAITS[0]):
        return None
    return sum(r.trace.span_total_s.get(k, 0.0) for k in WAITS) / n * 1e3
