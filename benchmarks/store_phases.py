"""YCSB phases over the device store, checked against a dict reference.

``run_store_phases`` drives ``KVStore(DeviceBackend(...))`` — the store's
main path on the device substrate — through five phases in fixed-size
batches, so that the device shapes repeat:

1. load: INSERT every key 1..n_keys;
2. YCSB-C: batches of zipf GETs;
3. YCSB-A: batches that alternate GET and UPDATE (a GET batch, then an
   UPDATE batch);
4. DELETE ``n_delete`` keys, then GET them and expect NOT_FOUND;
5. SCANs with zipf start keys and lengths up to ``MAX_SCAN``.

Every answer is compared with a plain dict reference.  Inside one UPDATE
batch, duplicate keys take the last writer's value, as the device backend
resolves them.  All data — keys order, zipf draws, value bytes — comes
from ``seed``.  ``chip_smoke.py`` runs it at the paper's YCSB scale on the
chip; the tests run it at a tiny size on the CPU.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List

import jax
import numpy as np

from repro.core.api import KVStore, Op
from repro.core.events import FULL, NOT_FOUND, OK

from .common import zipf_keys

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
MAX_SCAN = 100                    # YCSB-E's longest scan


@dataclass
class PhaseReport:
    name: str
    ops: int = 0
    mismatches: int = 0
    full: int = 0
    seconds: float = 0.0          # store calls only, ended on the device
    compiles: int = 0             # backend compiles during the store calls
    converged: bool = True        # index replicas agree after the phase
    examples: List[str] = field(default_factory=list)

    def miss(self, what: str):
        self.mismatches += 1
        if len(self.examples) < 5:
            self.examples.append(what)

    @property
    def ok(self) -> bool:
        return self.mismatches == 0 and self.full == 0 and self.converged


class _Timed:
    """Runs store batches, timing each until the pool's device arrays are
    ready and counting the backend compiles that happen inside."""

    def __init__(self, store: KVStore):
        self.store = store
        self.pool = store.backend.pool
        self.compiles = 0

    def _on_event(self, name, _secs, **_kw):
        if name == _COMPILE_EVENT:
            self.compiles += 1

    def run(self, rep: PhaseReport, ops: List[Op]):
        c0 = self.compiles
        t0 = time.perf_counter()
        results = [f.result() for f in self.store.submit_batch(ops)]
        p = self.pool
        jax.block_until_ready((p.index, p.log, p.chain, p.free_bitmap))
        rep.seconds += time.perf_counter() - t0
        rep.compiles += self.compiles - c0
        rep.ops += len(ops)
        rep.full += sum(r.status == FULL for r in results)
        return results


def run_store_phases(store: KVStore, *, n_keys: int, value_bytes: int,
                     theta: float, batch: int, read_batches: int = 8,
                     mixed_batches: int = 8, n_delete: int = 1024,
                     n_scans: int = 16, seed: int = 0,
                     log: Callable[[str], None] = print
                     ) -> List[PhaseReport]:
    """Run the five phases on ``store`` (a fresh device store); returns
    one report per phase.  A phase with mismatches, FULL answers or
    unconverged replicas has ``ok`` false."""
    rng = np.random.default_rng(seed)
    keys = np.arange(1, n_keys + 1)
    key_of_rank = rng.permutation(keys)        # scrambled zipf: hot keys spread
    ref: Dict[int, bytes] = {}
    timed = _Timed(store)
    reports: List[PhaseReport] = []

    def values(n: int) -> List[bytes]:
        buf = rng.bytes(n * value_bytes)
        return [buf[i * value_bytes:(i + 1) * value_bytes] for i in range(n)]

    def zipf(n: int) -> np.ndarray:
        return key_of_rank[zipf_keys(n_keys, theta, n, rng)]

    def check_gets(rep: PhaseReport, ks):
        ks = ks.tolist()
        for k, r in zip(ks, timed.run(rep, [Op.get(k) for k in ks])):
            want = ref.get(k)
            if want is None:
                if r.status != NOT_FOUND:
                    rep.miss(f"GET {k}: status {r.status}, want NOT_FOUND")
            elif r.status != OK or r.value != want:
                rep.miss(f"GET {k}: status {r.status}, value differs")

    def upsert(rep: PhaseReport, ks, kind: str):
        ks, vals = ks.tolist(), values(len(ks))
        make = Op.insert if kind == "insert" else Op.update
        res = timed.run(rep, [make(k, v) for k, v in zip(ks, vals)])
        for k, v in zip(ks, vals):
            ref[k] = v                         # in order: last writer wins
        for k, r in zip(ks, res):
            if r.status == FULL:
                continue                       # counted by the phase
            if r.status != OK or r.value != ref[k]:
                rep.miss(f"{kind.upper()} {k}: status {r.status}, "
                         f"value is not the last writer's")

    def finish(rep: PhaseReport):
        rep.converged = store.backend.pool.check_replicas_converged()
        reports.append(rep)
        log(f"smoke phase {rep.name}: ops={rep.ops} "
            f"mismatches={rep.mismatches} full={rep.full} "
            f"converged={rep.converged} seconds={rep.seconds!r} "
            f"compiles={rep.compiles}")
        for e in rep.examples:
            log(f"  mismatch: {e}")

    timed_listener = timed._on_event
    jax.monitoring.register_event_duration_secs_listener(timed_listener)
    try:
        rep = PhaseReport("load")
        order = rng.permutation(keys)
        for i in range(0, n_keys, batch):
            upsert(rep, order[i:i + batch], "insert")
        finish(rep)

        rep = PhaseReport("ycsb_c")
        for _ in range(read_batches):
            check_gets(rep, zipf(batch))
        finish(rep)

        rep = PhaseReport("ycsb_a")
        for b in range(mixed_batches):
            if b % 2 == 0:
                check_gets(rep, zipf(batch))
            else:
                upsert(rep, zipf(batch), "update")
        finish(rep)

        rep = PhaseReport("delete")
        dead = rng.choice(keys, size=n_delete, replace=False)
        res = timed.run(rep, [Op.delete(k) for k in dead.tolist()])
        for k, r in zip(dead.tolist(), res):
            del ref[k]
            if r.status != OK:
                rep.miss(f"DELETE {k}: status {r.status}")
        check_gets(rep, dead)
        finish(rep)

        rep = PhaseReport("scan")
        live = np.array(sorted(ref))
        starts = zipf(n_scans)
        counts = rng.integers(1, MAX_SCAN + 1, size=n_scans)
        ops = [Op.scan(int(s), int(c)) for s, c in zip(starts, counts)]
        for op, r in zip(ops, timed.run(rep, ops)):
            lo = int(np.searchsorted(live, op.key))
            want = [(k, ref[k]) for k in live[lo:lo + op.value].tolist()]
            got = r.value or []
            if r.status != OK or got != want:
                rep.miss(f"SCAN {op.key}+{op.value}: {len(got)} rows, "
                         f"want {len(want)}, or rows differ")
        finish(rep)
    finally:
        jax.monitoring.unregister_event_duration_listener(timed_listener)
    return reports
