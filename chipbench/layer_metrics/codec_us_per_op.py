"""Host time in ``core/codec.py`` (``encode_value`` and ``decode_value``)
per op of the window."""

CODEC = ("cb.codec.encode_value", "cb.codec.decode_value")


def read(r):
    ops = sum(r.ops.values())
    if not ops or not any(r.spans.calls.get(n) for n in CODEC):
        return None
    return sum(r.spans.seconds.get(n, 0.0) for n in CODEC) / ops * 1e6
