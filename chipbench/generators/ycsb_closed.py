"""Closed-loop YCSB traffic: each tick is one op from every client.

A mix file names this generator and gives its parameters::

    {"generator": "ycsb_closed", "clients": 128,
     "ops": {"get": 0.5, "update": 0.5},
     "keys": {"distribution": "zipf", "theta": 0.99}}

Every tick carries the same number of ops of each kind: the shares times
the clients, rounded by largest remainder.  So every seed does the same
amount of work, and only the keys and values differ.  Keys are record
numbers ``1..records`` drawn from a zipf over ranks that a seeded
permutation maps to records.  Each UPDATE carries the index of its value
in the run's value table, drawn uniformly.

``stream`` separates the warm-up's draws from the window's, so that the
window's ticks depend only on the seed and not on how long warm-up ran.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench.zipf import ScrambledZipf

KINDS = ("get", "update")           # submitted in this order inside a tick

Tick = Dict[str, Tuple[np.ndarray, Optional[np.ndarray]]]


def op_counts(shares: Dict[str, float], clients: int) -> Dict[str, int]:
    """Ops of each kind per tick, summing to ``clients``."""
    unknown = set(shares) - set(KINDS)
    if unknown:
        raise ValueError(f"ycsb_closed has no op kind {sorted(unknown)}")
    exact = {k: shares.get(k, 0.0) * clients for k in KINDS}
    counts = {k: int(np.floor(v)) for k, v in exact.items()}
    by_remainder = sorted(KINDS, key=lambda k: counts[k] - exact[k])
    for k in by_remainder[:clients - sum(counts.values())]:
        counts[k] += 1
    return counts


class Ticks:
    def __init__(self, mix: dict, records: int, n_values: int, seed: int):
        keys = mix["keys"]
        if keys["distribution"] != "zipf":
            raise ValueError(f"ycsb_closed draws zipf keys, not "
                             f"{keys['distribution']!r}")
        self.clients = int(mix["clients"])
        self.counts = op_counts(mix["ops"], self.clients)
        self.kinds = [k for k in KINDS if self.counts[k]]
        self.records = records
        self.n_values = n_values
        self.seed = seed
        self.zipf = ScrambledZipf(records, float(keys["theta"]),
                                  np.random.default_rng([seed, 1]))

    def stream(self, name: str) -> "TickStream":
        return TickStream(self, {"warmup": 2, "window": 3}[name])


class TickStream:
    def __init__(self, ticks: Ticks, stream_id: int):
        self.t = ticks
        self.rng = np.random.default_rng([ticks.seed, stream_id])

    def next(self, n: int) -> List[Tick]:
        """The next ``n`` ticks of this stream."""
        t, rng = self.t, self.rng
        out: List[Tick] = [{} for _ in range(n)]
        for kind in t.kinds:
            c = t.counts[kind]
            keys = t.zipf.keys(rng, n * c).reshape(n, c)
            vidx = (rng.integers(0, t.n_values, size=(n, c))
                    if kind == "update" else None)
            for i in range(n):
                out[i][kind] = (keys[i], None if vidx is None else vidx[i])
        return out

    def distinct_keys(self, n: int) -> np.ndarray:
        """``n`` distinct record numbers, uniform (warm-up batch sizes)."""
        return self.rng.choice(self.t.records, size=n, replace=False) + 1
