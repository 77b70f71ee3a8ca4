"""Bytes that a batched RACE probe needs, whatever implements it.

Per key: the key in (4 B), the slot words of both candidate buckets
(2 x slots_per_bucket words of 4 B: the two RDMA_READs of the paper's
SEARCH), the page pointer out (4 B) and the found flag out (1 B).  The
count depends on the keys probed and the bucket width only: not on the
number of buckets, not on the padding of the batch, and not on any copy
of the index that the present implementation makes.  The probe does no
matrix work, so its roofline is the memory bound.
"""
from __future__ import annotations

WORD = 4


def bytes_needed(n_keys: int, pool: dict) -> int:
    per_key = WORD + 2 * pool["slots_per_bucket"] * WORD + WORD + 1
    return n_keys * per_key

