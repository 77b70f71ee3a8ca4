"""Faults planted under the timed path, for the control and the tests.

``install(name)`` breaks the program in this process and returns the
function that mends it again; ``run.py`` never installs one.  Each fault
has to turn ``correct`` false:

* ``unreplicated`` (the control): every SNAPSHOT epoch leaves the backup
  replicas as they were, so an acknowledged write reaches the primary
  only.  It breaks the configuration's guarantee that every acknowledged
  write is present on all r replicas, while every answer stays right.
* ``stale_state``: every epoch returns the index it was given, unchanged,
  and still reports its writers as winners (a step that returns its state
  unchanged).
* ``half_batch``: ``insert_batch`` indexes the first half of its keys and
  reports the whole batch done (half of the batch left out).
* ``altered_answer``: ``search`` answers the first key it found in each
  batch with the page next to the right one (an answer altered where it
  is produced).

The exchange between chips has no fault here: every cell runs on one.
"""
from __future__ import annotations

from typing import Callable

import numpy as np

FAULTS = ("unreplicated", "stale_state", "half_batch", "altered_answer")


def install(name: str) -> Callable[[], None]:
    from repro.serving import kvpool

    if name in ("unreplicated", "stale_state"):
        orig = kvpool.snapshot_epoch

        def epoch(index, slot_idx, v_old, v_new, key):
            res = orig(index, slot_idx, v_old, v_new, key)
            if name == "stale_state":
                return res._replace(index=index)
            return res._replace(index=res.index.at[1:].set(index[1:]))

        target, attr, fn = kvpool, "snapshot_epoch", epoch
    elif name == "half_batch":
        orig = kvpool.KVPool.insert_batch

        def insert_batch(self, cid, keys, pages, opcode=kvpool.OP_INSERT):
            half = (len(keys) + 1) // 2
            orig(self, cid, keys[:half], pages[:half], opcode)
            return np.ones(len(keys), bool)

        target, attr, fn = kvpool.KVPool, "insert_batch", insert_batch
    elif name == "altered_answer":
        orig = kvpool.KVPool.search

        def search(self, keys):
            ptr, found = orig(self, keys)
            hit = np.flatnonzero(found)
            if len(hit):
                ptr = ptr.copy()
                ptr[hit[0]] ^= 1
            return ptr, found

        target, attr, fn = kvpool.KVPool, "search", search
    else:
        raise ValueError(f"no fault {name!r}; the faults are {FAULTS}")
    setattr(target, attr, fn)
    return lambda: setattr(target, attr, orig)
