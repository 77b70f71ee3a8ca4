"""The plain reference for ``correct``: a replay of every call against a map.

Nothing of the program is imported here.  The harness logs each call it
made through ``KVStore`` (the op kind, the record numbers, the index of
each written value in the run's value table) and what came back (each
op's status and value).  Once the window has closed, ``Replay`` applies
the calls in the order they were made to a map from record to value, and
counts every answer that differs from what the map says:

* a GET must answer OK with the value of the record's last acknowledged
  write (ops of one ``submit_batch`` are concurrent, and the harness never
  puts a GET and a write of one tick in one batch, so this is exact);
* an INSERT or UPDATE must answer OK with the value that the batch's last
  writer of that record wrote: duplicate keys in one batch are concurrent
  upserts, and the store resolves them to the last writer.  A FULL answer
  is counted apart, and its write is not applied.

``unconverged_slots`` compares the index replicas, copied to the host,
slot by slot: an acknowledged write has to be present on every replica.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

OK, NOT_FOUND, FULL = "OK", "NOT_FOUND", "FULL"
WRITES = ("insert", "update")


class Replay:
    def __init__(self, values: Sequence[bytes], records: int):
        self.values = values
        self.ref = np.full(records + 1, -1, np.int64)  # value index; -1 absent
        self.wrong = 0
        self.full = 0
        self.examples: List[str] = []

    def _miss(self, what: str):
        self.wrong += 1
        if len(self.examples) < 5:
            self.examples.append(what)

    def apply(self, kind: str, keys: np.ndarray, vidx: Optional[np.ndarray],
              statuses: Sequence[str], answers: Sequence) -> int:
        """Check one call's answers and apply its writes; returns the number
        of wrong answers in it."""
        before = self.wrong
        keys = keys.tolist()
        if kind == "get":
            for k, st, got in zip(keys, statuses, answers):
                want = int(self.ref[k])
                if want < 0:
                    if st != NOT_FOUND:
                        self._miss(f"GET {k}: {st}, want NOT_FOUND")
                elif st != OK or got != self.values[want]:
                    self._miss(f"GET {k}: {st}, value is not the last "
                               f"acknowledged write's")
            return self.wrong - before
        if kind not in WRITES:
            raise ValueError(f"the reference has no op kind {kind!r}")
        last = dict(zip(keys, vidx.tolist()))        # last writer wins
        acked = {}
        for k, st, got in zip(keys, statuses, answers):
            if st == FULL:
                self.full += 1
                acked.setdefault(k, False)
                continue
            acked[k] = True
            if st != OK or got != self.values[last[k]]:
                self._miss(f"{kind.upper()} {k}: {st}, value is not the "
                           f"batch's last writer's")
        for k, ok in acked.items():
            if ok:
                self.ref[k] = last[k]
        return self.wrong - before


def unconverged_slots(index: np.ndarray) -> int:
    """Slots whose word differs between the primary and any backup."""
    index = np.asarray(index)
    if index.shape[0] < 2:
        return 0
    return int((index[1:] != index[0]).any(axis=0).sum())
