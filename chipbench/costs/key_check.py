"""Bytes that the lookup's full-key check needs, whatever implements it.

Per searched key: its key record in (24 B), the record of the page its
probe pointed at (24 B), the page pointer in (4 B) and the answer's flags
out (1 B).  Per shadowed key (its probe pointed at another key's page),
the collision path: the slot words of both candidate buckets (2 x
slots_per_bucket words of 4 B), the record of each candidate's page (2 x
slots_per_bucket records), and its own record again.  The count depends on
the keys and the bucket width only: not on the pool's size, not on the
padding of the batch, and not on any copy that the present implementation
makes.  The check does no matrix work, so its roofline is the memory
bound.
"""
from __future__ import annotations

WORD = 4
RECORD = 24            # a key record: 6 words


def bytes_needed(n_keys: int, pool: dict, n_shadowed: int = 0) -> int:
    cand = 2 * pool["slots_per_bucket"]
    per_key = RECORD + RECORD + WORD + 1
    per_shadowed = cand * WORD + cand * RECORD + RECORD
    return n_keys * per_key + n_shadowed * per_shadowed
