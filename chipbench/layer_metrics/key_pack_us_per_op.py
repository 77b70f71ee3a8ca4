"""Host time spent building the key rows of each batch per op of the
window: the total of the program's span ``kv.backend.keys`` (each key's
fold and exact record, in ``DeviceBackend.submit_many``), from the
profiler trace."""

SPAN = "kv.backend.keys"


def read(r):
    ops = sum(r.ops.values())
    if not ops or not r.trace.span_calls.get(SPAN):
        return None
    return r.trace.span_total_s.get(SPAN, 0.0) / ops * 1e6
