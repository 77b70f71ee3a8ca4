"""Bytes that one SNAPSHOT epoch needs, whatever implements it.

Per active writer: one read and one write of its slot on each of the r
index replicas (4 B words; the CAS on each backup and the commit of the
primary), its target slot, expected and proposed value in (3 x 4 B), and
its win flag (1 B), committed value (4 B) and rule (4 B) out.  The count
depends on the writers and r only: not on the number of slots M, which
the present implementation passes over several times per epoch.  An
epoch does no matrix work, so its roofline is the memory bound.
"""
from __future__ import annotations

WORD = 4


def bytes_needed(n_writers: int, pool: dict) -> int:
    r = pool["replicas"]
    per_writer = 2 * r * WORD + 3 * WORD + 1 + WORD + WORD
    return n_writers * per_writer

