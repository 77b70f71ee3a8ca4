"""The FUSEE-managed disaggregated KV-cache pool (INTERNAL substrate).

This module is not a public KV surface: clients go through the unified
``core.api.KVStore`` over ``serving.backend.DeviceBackend``, which lowers
Op batches onto the pool below.  Whitebox tests import it directly.

This is the paper's technique as a first-class serving feature: the
*metadata* of a paged KV-cache prefix store — the RACE hash index mapping
``prefix_hash -> page``, and the memory-management information — lives in
replicated device arrays and is manipulated by serving workers (clients)
with CAS epochs, not by a metadata server.

Components, mapped 1:1 to the paper:

* RACE index (§4.2): ``(r, n_buckets, slots_per_bucket)`` int32 replicas;
  SEARCH = batched probe (the race_lookup Pallas kernel on replica 0 = the
  primary); INSERT/UPDATE/DELETE = SNAPSHOT epochs (snapshot_jax.py).
* Two-level memory management (§4.4): "memory nodes" (pool shards) grant
  coarse chunks of ``chunk_pages`` pages from a per-shard grant table
  (compute-light: a cursor bump, recorded per client); clients carve single
  pages out of their chunks with local free lists (slab).  Frees set bits
  in a per-chunk free bitmap (FAA analog); owners reclaim in batches.
* Embedded operation log (§4.5): every page carries a log record
  (old slot value, opcode, key, used/invalid bits) written together with
  the page payload; per-client allocation order forms the recovery chain
  (next/prev pointers pre-positioned from the deterministic free list).
* Recovery (§5.3): ``recover_client`` re-owns a crashed client's chunks
  from the grant table, walks its allocation-order log chain, reclaims
  incomplete pages, and redoes/commits in-flight index updates.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import race_lookup
from . import slots_jax as SL
from .snapshot_jax import snapshot_epoch

OP_INSERT, OP_UPDATE, OP_DELETE = 1, 2, 3


def _padded(n: int) -> int:
    """Device batch size for ``n`` ops: a power of two, at least the probe
    kernel's 256-key block.  Batch sizes that depend on the data (unique
    keys of an upsert batch, fingerprint collisions, scan windows) then
    compile a few shapes instead of one per size."""
    return max(256, 1 << max(n - 1, 0).bit_length())


def _pad(x: np.ndarray, size: int, fill) -> np.ndarray:
    out = np.full(size, fill, x.dtype)
    out[:len(x)] = x
    return out


@dataclass
class PoolConfig:
    n_pages: int = 4096          # pool pages per shard group
    n_buckets: int = 1024        # RACE combined buckets
    slots_per_bucket: int = 8
    replicas: int = 3            # index replication factor r
    chunk_pages: int = 64        # coarse grant unit (the "16MB block")
    n_shards: int = 4            # "memory nodes" granting chunks


@dataclass
class ClientSlab:
    """Client-side fine-grained allocator (one uniform size class)."""
    free: List[int] = field(default_factory=list)   # FIFO page free list
    chunks: List[int] = field(default_factory=list)
    last_alloc: int = 0


class KVPool:
    """Host-coordinated, device-resident FUSEE pool.

    Device state (jnp): index replicas, page log, free bitmap.
    Host state (np): grant table cursor per shard, per-client slabs —
    exactly the split the paper prescribes (coarse state at MNs, fine state
    at clients)."""

    def __init__(self, cfg: PoolConfig, seed: int = 0):
        self.cfg = cfg
        M = cfg.n_buckets * cfg.slots_per_bucket
        self.index = jnp.zeros((cfg.replicas, M), jnp.int32)
        # page log: [old_value, opcode, key, flags(used|invalid<<1)]
        self.log = jnp.zeros((cfg.n_pages, 4), jnp.int32)
        # next/prev allocation-order chain per page (+1; 0 = nil)
        self.chain = jnp.zeros((cfg.n_pages, 2), jnp.int32)
        self.free_bitmap = jnp.zeros((cfg.n_pages,), jnp.int8)
        # coarse level: grant table (page-chunk -> client+1), shard cursors
        n_chunks = cfg.n_pages // cfg.chunk_pages
        self.grant = np.zeros((n_chunks,), np.int32)
        self.shard_of_chunk = np.arange(n_chunks) % cfg.n_shards
        self.cursor = np.zeros((cfg.n_shards,), np.int32)
        self.slabs: Dict[int, ClientSlab] = {}
        self.key = jax.random.PRNGKey(seed)
        self.stats = {"alloc_rpcs": 0, "epochs": 0, "search_batches": 0}

    # ------------------------------------------------ two-level allocation --
    def _grant_chunk(self, cid: int) -> Optional[int]:
        """MN-side ALLOC (compute-light): grab the next free chunk on the
        client's home shard (round-robin over shards on exhaustion)."""
        cfg = self.cfg
        for probe in range(cfg.n_shards):
            sh = (cid + probe) % cfg.n_shards
            mine = np.where((self.shard_of_chunk == sh) & (self.grant == 0))[0]
            if len(mine):
                c = int(mine[0])
                self.grant[c] = cid + 1
                self.stats["alloc_rpcs"] += 1
                return c
        return None

    def add_shard(self) -> int:
        """Elastic grant-shard scale-out (the serving twin of
        ``FuseeCluster.add_mn``): a new "memory node" joins the grant
        ring; every still-ungranted chunk re-homes onto the grown ring
        round-robin.  Granted chunks (live pages) never move — at grant
        granularity the dual-write/copy window of the event-level
        migration engine is unnecessary, because chunk ownership, not
        page bytes, is the only sharded state here."""
        cfg = self.cfg
        self.cfg = cfg = dataclasses.replace(cfg, n_shards=cfg.n_shards + 1)
        self.cursor = np.concatenate([self.cursor, np.zeros(1, np.int32)])
        free = self.grant == 0
        self.shard_of_chunk[free] = \
            np.arange(int(free.sum())) % cfg.n_shards
        return cfg.n_shards - 1

    def _slab(self, cid: int) -> ClientSlab:
        return self.slabs.setdefault(cid, ClientSlab())

    def alloc_pages(self, cid: int, n: int) -> np.ndarray:
        """Client-side fine allocation of n pages (slab pop; grants chunks
        as needed).  Returns page ids (-1 = pool exhausted)."""
        sl = self._slab(cid)
        out = []
        for _ in range(n):
            if not sl.free:
                c = self._grant_chunk(cid)
                if c is None:
                    out.append(-1)
                    continue
                base = c * self.cfg.chunk_pages
                sl.free.extend(range(base, base + self.cfg.chunk_pages))
                sl.chunks.append(c)
            out.append(sl.free.pop(0))
        return np.array(out, np.int32)

    def write_pages(self, cid: int, pages: np.ndarray, keys: np.ndarray,
                    opcode: int):
        """Write page payload + embedded log entry in ONE device op (the
        paper's single-RDMA_WRITE log embedding).  Chain pointers come from
        the deterministic slab order (pre-positioned)."""
        sl = self._slab(cid)
        n = len(pages)
        nxt = np.array([sl.free[0] + 1 if sl.free else 0] * n, np.int32)
        for i in range(n - 1):
            nxt[i] = pages[i + 1] + 1
        prv = np.concatenate([[sl.last_alloc], pages[:-1] + 1]).astype(np.int32)
        if n:
            sl.last_alloc = int(pages[-1]) + 1
        W = _padded(n)
        entries = np.zeros((W, 4), np.int32)      # padding rows are dropped
        entries[:n, 1:] = np.stack([np.full(n, opcode), keys, np.ones(n)], 1)
        links = np.zeros((W, 2), np.int32)
        links[:n] = np.stack([nxt, prv], 1)
        pg = jnp.asarray(_pad(np.asarray(pages, np.int32), W,
                              self.cfg.n_pages))
        self.log = self.log.at[pg].set(jnp.asarray(entries), mode="drop")
        self.chain = self.chain.at[pg].set(jnp.asarray(links), mode="drop")

    def free_pages(self, pages: np.ndarray):
        """Any client: set free bits (the RDMA_FAA on the free bitmap)."""
        pages = np.asarray(pages, np.int32)
        pg = _pad(pages, _padded(len(pages)), self.cfg.n_pages)
        self.free_bitmap = self.free_bitmap.at[jnp.asarray(pg)].set(
            1, mode="drop")

    def reclaim(self, cid: int) -> int:
        """Owner-side batched reclaim of freed pages in own chunks (§4.4)."""
        sl = self._slab(cid)
        bm = np.asarray(self.free_bitmap)
        n = 0
        for c in sl.chunks:
            base = c * self.cfg.chunk_pages
            for p in range(base, base + self.cfg.chunk_pages):
                if bm[p]:
                    sl.free.append(p)
                    n += 1
        if n:
            idx = jnp.asarray([p for c in sl.chunks
                               for p in range(c * self.cfg.chunk_pages,
                                              (c + 1) * self.cfg.chunk_pages)])
            self.free_bitmap = self.free_bitmap.at[idx].set(0)
            self.log = self.log.at[idx, 3].set(0)
        return n

    # -------------------------------------------------------------- index --
    def search(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Batched SEARCH on the primary replica (race_lookup kernel), with
        the RACE data-access integrity check: the key stored on the pointed
        page must match, or the probe is a fingerprint collision -> miss."""
        cfg = self.cfg
        self.stats["search_batches"] += 1
        idx2d = self.index[0].reshape(cfg.n_buckets, cfg.slots_per_bucket)
        n = len(keys)
        kp = jnp.asarray(_pad(np.asarray(keys, np.int32), _padded(n), 0))
        ptr_d, found_d = race_lookup(kp, idx2d)
        page_keys = np.asarray(self.log[ptr_d, 2])[:n]
        ptr, found = np.asarray(ptr_d)[:n], np.asarray(found_d)[:n]
        verified = found & (page_keys == keys)
        shadowed = found & ~verified
        if shadowed.any():
            # collision path (read amplification): probe ALL candidate
            # slots of both buckets and verify keys, as RACE prescribes
            p2, f2 = self._search_all_candidates(keys[shadowed])
            ptr = ptr.copy()
            ptr[shadowed] = p2
            verified = verified.copy()
            verified[shadowed] = f2
        return np.where(verified, ptr, 0), verified

    def _search_all_candidates(self, keys: np.ndarray):
        cfg = self.cfg
        spb = cfg.slots_per_bucket
        n = len(keys)
        kj = jnp.asarray(_pad(np.asarray(keys, np.int32), _padded(n), 0))
        b1, b2, fp = self._slot_candidates(kj)
        idx0 = self.index[0].reshape(cfg.n_buckets, spb)
        rows = np.asarray(jnp.concatenate([idx0[b1], idx0[b2]], axis=1))[:n]
        fpv = np.asarray(fp)[:n]
        log_keys = np.asarray(self.log[:, 2])
        ptr = np.zeros(len(keys), np.int32)
        found = np.zeros(len(keys), bool)
        for i in range(len(keys)):
            for w in rows[i]:
                if w == 0:
                    continue
                if ((int(w) >> 24) & 0xFF) == fpv[i] and \
                        log_keys[int(w) & 0xFFFFFF] == keys[i]:
                    ptr[i] = int(w) & 0xFFFFFF
                    found[i] = True
                    break
        return ptr, found

    def _slot_candidates(self, keys: jnp.ndarray):
        cfg = self.cfg
        b1, b2 = SL.bucket_pair(keys, cfg.n_buckets)
        fp = SL.fingerprint(keys)
        return b1, b2, fp

    def insert_batch(self, cid: int, keys: np.ndarray, pages: np.ndarray,
                     opcode: int = OP_INSERT) -> np.ndarray:
        """INSERT/UPDATE a batch of prefix keys -> pages via one (or more)
        SNAPSHOT epochs.  Returns success mask."""
        cfg = self.cfg
        n = len(keys)
        W = _padded(n)
        keys_j = jnp.asarray(_pad(np.asarray(keys, np.int32), W, 0))
        pages_j = jnp.asarray(_pad(np.asarray(pages, np.int32), W, 0))
        b1, b2, fp = self._slot_candidates(keys_j)
        v_new = SL.pack_slot(fp, pages_j)
        done = np.arange(W) >= n                # padding writers never act
        spb = cfg.slots_per_bucket
        for attempt in range(2 * spb):
            # pick a target slot per key: existing fp-match else first empty
            idx0 = self.index[0].reshape(cfg.n_buckets, spb)
            rows = jnp.concatenate([idx0[b1], idx0[b2]], axis=1)  # (W, 2spb)
            offs = jnp.concatenate(
                [b1[:, None] * spb + jnp.arange(spb)[None],
                 b2[:, None] * spb + jnp.arange(spb)[None]], axis=1)
            # fp match alone is not enough: verify the page's key so a
            # colliding entry is never overwritten (RACE integrity check)
            page_keys = self.log[SL.slot_ptr(rows), 2]
            is_match = ((SL.slot_fp(rows) == fp[:, None])
                        & (rows != 0) & (page_keys == keys_j[:, None]))
            is_empty = rows == 0
            cand = jnp.where(is_match.any(1),
                             jnp.argmax(is_match, 1),
                             jnp.argmax(is_empty, 1))
            ok = is_match.any(1) | is_empty.any(1)
            slot = jnp.take_along_axis(offs, cand[:, None], 1)[:, 0]
            v_old = jnp.take_along_axis(rows, cand[:, None], 1)[:, 0]
            act = jnp.asarray(~done) & ok
            slot_i = jnp.where(act, slot, -1)
            self.key, k = jax.random.split(self.key)
            res = snapshot_epoch(self.index, slot_i, v_old, v_new, k)
            self.index = res.index
            self.stats["epochs"] += 1
            # commit logs of winners (old value into the embedded entry)
            wpg = jnp.where(res.win, pages_j, self.cfg.n_pages)
            self.log = self.log.at[wpg, 0].set(
                v_old | jnp.int32(1 << 30), mode="drop")
            # a winner that overwrote a same-key slot superseded that key's
            # old page: free it (any-client bitmap free, §4.4) so upserts
            # don't leak pool capacity
            superseded = np.asarray(
                jnp.where(res.win & (v_old != 0), SL.slot_ptr(v_old), -1))
            self.free_pages(superseded[superseded >= 0])
            done |= np.asarray(res.win)
            if done.all():
                break
        return done[:n]

    def delete_batch(self, cid: int, keys: np.ndarray) -> np.ndarray:
        """DELETE: SNAPSHOT-write slot -> 0 (plus temp log page, elided)."""
        cfg = self.cfg
        n = len(keys)
        W = _padded(n)
        keys_j = jnp.asarray(_pad(np.asarray(keys, np.int32), W, 0))
        _, found = self.search(keys)
        b1, b2, fp = self._slot_candidates(keys_j)
        spb = cfg.slots_per_bucket
        idx0 = self.index[0].reshape(cfg.n_buckets, spb)
        rows = jnp.concatenate([idx0[b1], idx0[b2]], axis=1)
        offs = jnp.concatenate(
            [b1[:, None] * spb + jnp.arange(spb)[None],
             b2[:, None] * spb + jnp.arange(spb)[None]], axis=1)
        page_keys = self.log[SL.slot_ptr(rows), 2]
        is_match = ((SL.slot_fp(rows) == fp[:, None]) & (rows != 0)
                    & (page_keys == keys_j[:, None]))
        slot = jnp.take_along_axis(offs, jnp.argmax(is_match, 1)[:, None],
                                   1)[:, 0]
        v_old = jnp.take_along_axis(rows, jnp.argmax(is_match, 1)[:, None],
                                    1)[:, 0]
        act = jnp.asarray(_pad(found, W, False)) & is_match.any(1)
        self.key, k = jax.random.split(self.key)
        res = snapshot_epoch(self.index, jnp.where(act, slot, -1), v_old,
                             jnp.zeros_like(v_old), k)
        self.index = res.index
        self.stats["epochs"] += 1
        # free the deleted pages (any-client free via bitmap)
        dead = np.asarray(jnp.where(res.win, SL.slot_ptr(v_old), -1))
        self.free_pages(dead[dead >= 0])
        return np.asarray(res.win)[:n]

    # ----------------------------------------------------------- recovery --
    def crash_client(self, cid: int):
        self.slabs.pop(cid, None)

    def recover_client(self, cid: int, reassign_to: Optional[int] = None
                       ) -> Dict[str, int]:
        """§5.3 for the serving pool: re-own chunks from the grant table,
        walk the embedded-log chain, reclaim unused pages, redo uncommitted
        winner index writes."""
        cfg = self.cfg
        stats = {"chunks": 0, "used_pages": 0, "reclaimed": 0, "redone": 0}
        chunks = np.where(self.grant == cid + 1)[0]
        stats["chunks"] = len(chunks)
        log = np.asarray(self.log)
        new_owner = reassign_to if reassign_to is not None else cid
        sl = self._slab(new_owner)
        for c in chunks:
            self.grant[c] = new_owner + 1
            if c not in sl.chunks:
                sl.chunks.append(int(c))
            base = c * cfg.chunk_pages
            for p in range(base, base + cfg.chunk_pages):
                used = log[p, 3] & 1
                if not used:
                    if p not in sl.free:
                        sl.free.append(p)
                    stats["reclaimed"] += 1
                    continue
                stats["used_pages"] += 1
                committed = bool(log[p, 0] & (1 << 30))
                if not committed and log[p, 1] in (OP_INSERT, OP_UPDATE):
                    # redo: re-run the index write for this page (§5.3 c1)
                    ok = self.insert_batch(new_owner,
                                           np.array([log[p, 2]], np.int32),
                                           np.array([p], np.int32),
                                           opcode=int(log[p, 1]))
                    stats["redone"] += int(ok[0])
        return stats

    # --------------------------------------------------------- invariants --
    def check_replicas_converged(self) -> bool:
        idx = np.asarray(self.index)
        return bool((idx[1:] == idx[0]).all()) if self.cfg.replicas > 1 else True
