"""Device backend for the unified store API (core/api.py).

Maps ``Op`` batches onto the jitted device-resident pool (kvpool.py):
GETs become one batched ``race_lookup`` probe, INSERT/UPDATEs one page
allocation + page write + SNAPSHOT epoch group, DELETEs one epoch — the
batch-native substrate the serving engine runs on.  Futures resolve
eagerly (device ops are synchronous host calls); the surface is identical
to the event-level ``SimBackend``, so the engine, benchmarks, and examples
speak one API for both substrates.

Each key goes to the pool whole: a row of its 31-bit fold, which picks
the bucket pair and the fingerprint, and its exact record
(``codec.key_record``: a ``bytes``/``str`` key of at most
``codec.KEY_BYTES_MAX`` bytes, or an ``int``), which the pool compares on
the device.  Keys whose folds are equal stay apart.  A longer byte key
raises ``CodecError`` at submit, before any op of the batch runs.
Values (optional, small) are retained host-side per page so ``get``
round-trips them.  The page id backing a key is reported on
``OpResult.page`` — the serving engine uses it for KV-cache page
accounting.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import codec
from repro.core.api import KVFuture, Op
from repro.core.events import FULL, NOT_FOUND, OK, OpResult
from repro.core.faults import ClientCrashed
from repro.kernels import leaf_probe_batch

from .kvpool import KVPool, OP_INSERT, OP_UPDATE, PoolConfig, fold_ints, \
    key_rows

_KEYED = ("search", "insert", "update", "delete")


def _folds(keys: Sequence) -> np.ndarray:
    return fold_ints(np.array([codec.encode_key(k) for k in keys],
                              np.uint64))


def _key32(key) -> int:
    return int(_folds([key])[0])


def encode_keys(keys: Sequence) -> tuple:
    """Each key's record (``codec.key_record``), and the pool's key rows."""
    recs = [codec.key_record(k) for k in keys]
    return recs, key_rows(_folds(keys), recs)


class DeviceBackend:
    """Batch-native backend over the device-resident FUSEE pool."""

    def __init__(self, cfg: Optional[PoolConfig] = None, *, cid: int = 0,
                 pool: Optional[KVPool] = None, seed: int = 0):
        self.pool = pool if pool is not None else KVPool(cfg or PoolConfig(),
                                                         seed=seed)
        self.cid = cid
        self.crashed = False                 # set by ServeEngine.crash_worker
        self._values: Dict[int, Any] = {}    # page -> encoded value words
        # ordered keydir twin (core/ordered.py on the sim substrate): the
        # key records this backend has upserted, each with its fold (the
        # scan order), kept as a *superset* of the live set — scans
        # validate every candidate against the device index (one batched
        # race_lookup probe), so spurious members are filtered exactly
        # like stale ordered entries in the simulator
        self._keydir: Dict[bytes, int] = {}

    # ------------------------------------------------------------- submit
    def submit_many(self, ops: Sequence[Op]) -> List[KVFuture]:
        if self.crashed:
            # same typed error as the event-level substrate: one failure
            # surface across both backends
            raise ClientCrashed(self.cid)
        with TraceAnnotation("kv.backend.keys"):
            # every keyed op's row, built once: a key the pool cannot hold
            # fails the batch before any of it runs
            recs, rows = encode_keys([op.key if op.kind in _KEYED else 0
                                      for op in ops])
        futs = [KVFuture(self) for _ in ops]
        # execute maximal same-kind runs as one device batch, preserving
        # cross-kind program order
        i = 0
        while i < len(ops):
            j = i
            while j < len(ops) and ops[j].kind == ops[i].kind:
                j += 1
            self._exec_group(ops[i].kind, list(range(i, j)), ops, futs,
                             recs[i:j], rows[i:j])
            i = j
        return futs

    def _exec_group(self, kind: str, idxs: List[int], ops, futs,
                    recs: List[bytes], rows: np.ndarray):
        if kind == "search":
            with TraceAnnotation("kv.backend.search"):
                self._search(idxs, ops, futs, rows)
        elif kind in ("insert", "update"):
            with TraceAnnotation("kv.backend.upsert"):
                self._upsert(kind, idxs, ops, futs, recs, rows)
        elif kind == "delete":
            with TraceAnnotation("kv.backend.delete"):
                ok = self.pool.delete_batch(self.cid, rows)
                for n, i in enumerate(idxs):
                    if ok[n]:
                        self._keydir.pop(recs[n], None)
                    futs[i]._resolve(OpResult(OK if ok[n] else NOT_FOUND))
        elif kind in ("scan", "range"):
            with TraceAnnotation("kv.backend.scan"):
                for i in idxs:
                    futs[i]._resolve(self._scan_one(ops[i]))
        elif kind == "reclaim":
            n = self.pool.reclaim(self.cid)
            for i in idxs:
                futs[i]._resolve(OpResult(OK, value=[n]))
        else:
            raise ValueError(kind)

    def _search(self, idxs: List[int], ops, futs, rows: np.ndarray):
        ptr, found = self.pool.search(rows)
        for n, i in enumerate(idxs):
            if found[n]:
                page = int(ptr[n])
                futs[i]._resolve(OpResult(OK, page=page,
                                          value=self._values.get(page)))
            else:
                futs[i]._resolve(OpResult(NOT_FOUND))

    def _upsert(self, kind: str, idxs: List[int], ops, futs,
                recs: List[bytes], rows: np.ndarray):
        # Duplicate keys within one batch are concurrent upserts of the
        # same key: exactly one page is written (last writer's value
        # wins) and every duplicate resolves to that one result — the
        # pool would otherwise supersede-and-free a page whose OK
        # future the caller still holds.  Keys are the same only where
        # their records are.
        last: Dict[bytes, int] = {}     # record -> position of its last op
        for n, rec in enumerate(recs):
            last[rec] = n
        uniq = sorted(last.values())
        keys = rows[uniq]
        pages = self.pool.alloc_pages(self.cid, len(uniq))
        if (pages < 0).any() and self.pool.reclaim(self.cid):
            # slab ran dry but bitmap-freed pages (superseded upserts,
            # released surplus) were reclaimable: retry the dead slots
            dead = pages < 0
            pages[dead] = self.pool.alloc_pages(self.cid, int(dead.sum()))
        live = pages >= 0
        if live.any():
            opcode = OP_INSERT if kind == "insert" else OP_UPDATE
            self.pool.write_pages(self.cid, pages[live], keys[live],
                                  opcode=opcode)
            ok = self.pool.insert_batch(self.cid, keys[live], pages[live],
                                        opcode=opcode)
        else:
            ok = np.zeros(0, bool)
        results: Dict[bytes, OpResult] = {}
        k = 0
        for m, n in enumerate(uniq):
            rec = recs[n]
            if not live[m]:
                results[rec] = OpResult(FULL, page=-1)
                continue
            page = int(pages[m])
            won = bool(ok[k]); k += 1
            with TraceAnnotation("kv.codec.encode"):
                self._values[page] = codec.encode_value(ops[idxs[n]].value)
            # keydir superset: even a lost upsert means the KEY is
            # live (another page won its slot); scans validate
            self._keydir[rec] = int(keys[m, 0])
            results[rec] = OpResult(OK if won else FULL, page=page,
                                    value=self._values[page])
        for n, i in enumerate(idxs):
            futs[i]._resolve(results[recs[n]])

    # ------------------------------------------------------ ordered scan
    def _scan_one(self, op: Op) -> OpResult:
        """SCAN/RANGE on the device substrate: locate the start position
        in the sorted keydir via the shared ``leaf_probe`` entry point,
        validate the candidate window against the device index with one
        batched ``race_lookup`` probe, and return ``[(key32, value),
        ...]`` in key order — the serving twin of core/ordered.py."""
        start = _key32(op.key)
        if op.kind == "scan":
            count, end = int(op.value), None
        else:
            count, end = None, _key32(op.value)
        if not self._keydir:
            return OpResult(OK, value=[])
        entries = sorted(self._keydir.items(), key=lambda e: (e[1], e[0]))
        folds = np.array([f for _, f in entries], np.uint64)
        # Pallas on TPU, numpy elsewhere
        pos = int(leaf_probe_batch(np.array([start], np.uint64), folds)[0])
        first = pos if (pos >= 0 and int(folds[pos]) >= start) else pos + 1
        if first < len(folds):      # the first of keys with an equal fold
            first = int(np.searchsorted(folds, folds[first]))
        last = (len(folds) if end is None else
                int(np.searchsorted(folds, np.uint64(end))))
        out: list = []
        i = first
        while i < last and (count is None or len(out) < count):
            window = entries[i:min(last, i + max(2 * (count or 64), 64))]
            ptr, found = self.pool.search(key_rows(
                [f for _, f in window], [r for r, _ in window]))
            for n, (_, fold) in enumerate(window):
                if found[n]:
                    out.append((fold, self._values.get(int(ptr[n]))))
                    if count is not None and len(out) >= count:
                        break
            i += len(window)
        return OpResult(OK, value=out)

    # --------------------------------------------------- page management
    def release_pages(self, pages: np.ndarray):
        """Free surplus pages (index no longer references them) back to the
        pool's free bitmap — the engine's retire path."""
        pages = np.asarray(pages, np.int32)
        if len(pages):
            self.pool.free_pages(pages)
            for p in pages.tolist():
                self._values.pop(int(p), None)

    # ------------------------------------------------------------- driving
    def drive(self, fut: KVFuture):     # futures resolve eagerly
        if not fut.done():              # pragma: no cover - defensive
            raise RuntimeError("device future left unresolved")

    def drain(self):
        pass

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        n = {k: self.pool.metrics.counter(f"pool.{k}").value
             for k in ("alloc.grants", "upsert.attempts", "delete.epochs",
                       "search.calls")}
        return {"backend": "device", "cid": self.cid, "inflight": 0,
                "crashed": self.crashed,
                "pages_valued": len(self._values),
                "alloc_rpcs": n["alloc.grants"],
                "epochs": n["upsert.attempts"] + n["delete.epochs"],
                "search_batches": n["search.calls"]}
