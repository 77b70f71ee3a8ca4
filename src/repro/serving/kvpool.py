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
  (old slot value, opcode, key fold, used/invalid bits) and its key's
  exact record (``page_keys``), written together with the page payload;
  per-client allocation order forms the recovery chain (next/prev
  pointers pre-positioned from the deterministic free list).
* Keys (RACE's data-access integrity check): a key is a row of
  ``KEY_COLS`` int32 words, its 31-bit fold and then its exact record
  (``codec.key_record``).  The fold picks the bucket pair and the
  fingerprint; a slot is a key's only where ``slot_match`` finds the
  key's own record on the page it points at.  Every lookup, upsert,
  delete and recovery redo goes through that one rule on the device.
* Recovery (§5.3): ``recover_client`` re-owns a crashed client's chunks
  from the grant table, walks its allocation-order log chain, reclaims
  incomplete pages, and redoes/commits in-flight index updates.

Observability: every method on the device path opens a fixed-name
``jax.profiler.TraceAnnotation`` (``kv.pool.*``), so a profiler trace
splits each call into host dispatch and host waits on the device clock.
Every device->host read goes through ``_fetch``, which opens the
``<parent>.wait`` span and counts the reads and their bytes.  Counters
(``pool.*``) live in ``KVPool.metrics``, a ``repro.obs`` registry, and are
bumped from host-held values only: no span or counter reads the device.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import codec
from repro.kernels import race_lookup
from repro.obs.registry import Registry
from . import slots_jax as SL
from .snapshot_jax import snapshot_epoch

OP_INSERT, OP_UPDATE, OP_DELETE = 1, 2, 3
KEY_COLS = 1 + codec.KEY_WORDS          # a key row: fold, then record

# ``KVPool.metrics`` counters, each under ``pool.<name>``
COUNTERS = ("search.calls", "search.keys", "search.shadowed",
            "search.aliased", "upsert.calls", "upsert.keys",
            "upsert.attempts", "upsert.writers", "upsert.dispatches",
            "delete.epochs", "alloc.grants", "host_syncs", "d2h_bytes",
            "h2d_key_bytes")

# flag bits of the one word a lookup reads back per key, above the page
# pointer's 24 bits
_FOUND, _SHADOWED, _ALIASED = 1 << 24, 1 << 25, 1 << 26


def fold_ints(keys64) -> np.ndarray:
    """The 31-bit fold of each 64-bit protocol key (``codec.encode_key``):
    its halves xor-ed, 0 taken to 1.  It picks the bucket pair and the
    fingerprint, nothing else."""
    v = np.asarray(keys64).astype(np.int64).astype(np.uint64)
    f = ((v ^ (v >> np.uint64(32))) & np.uint64(0x7FFFFFFF)).astype(np.int32)
    return np.where(f == 0, 1, f).astype(np.int32)


def key_rows(folds, recs) -> np.ndarray:
    """The pool's key rows, (n, KEY_COLS) int32, of keys' folds and their
    records (``codec.key_record``, bytes)."""
    rows = np.empty((len(recs), KEY_COLS), np.int32)
    rows[:, 0] = folds
    rows[:, 1:] = np.frombuffer(b"".join(recs), "<i4").reshape(
        len(recs), codec.KEY_WORDS)
    return rows


def as_key_rows(keys) -> np.ndarray:
    """Key rows as the pool's methods take them: (n, KEY_COLS) rows as
    they are, or a 1-D integer array, a batch of integer keys."""
    keys = np.asarray(keys)
    if keys.ndim == 2:
        return keys.astype(np.int32, copy=False)
    return key_rows(fold_ints(keys),
                    [codec.key_record(int(k)) for k in keys.tolist()])


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


# The device steps: one jitted program each, compiled once per padded
# batch shape and shared by every pool of one shape.  Padding rows carry
# page id ``n_pages`` (dropped by the scatters) or never act.
@jax.jit
def slot_match(rows, fp, page_keys, recs):
    """The RACE slot-match rule, the one place it is written: slot words
    ``rows`` (W, C) of keys with fingerprints ``fp`` (W,) and records
    ``recs`` (W, KEY_WORDS) are the key's where the fingerprint matches,
    the slot is in use, and the page it points at holds the key's own
    record (a fingerprint or fold match alone is a collision)."""
    held = page_keys[SL.slot_ptr(rows)]                    # (W, C, words)
    return ((SL.slot_fp(rows) == fp[:, None]) & (rows != 0)
            & (held == recs[:, None, :]).all(-1))


def _candidates(index, keys, slots_per_bucket):
    """Offsets and words of the slots of both buckets of each key row,
    read from the primary, and each key's fingerprint."""
    n_buckets = index.shape[1] // slots_per_bucket
    b1, b2 = SL.bucket_pair(keys[:, 0], n_buckets)
    lane = jnp.arange(slots_per_bucket)[None]
    offs = jnp.concatenate([b1[:, None] * slots_per_bucket + lane,
                            b2[:, None] * slots_per_bucket + lane], axis=1)
    return offs, index[0, offs], SL.fingerprint(keys[:, 0])


def _aliased(rows, fp, log, keys, is_match):
    """Keys with a slot that the fold alone would take for theirs: its
    fingerprint matches and its page holds the key's fold, but another
    key's record."""
    same_fold = log[SL.slot_ptr(rows), 2] == keys[:, :1]
    return ((SL.slot_fp(rows) == fp[:, None]) & (rows != 0) & same_fold
            & ~is_match).any(1)


@jax.jit
def _write_step(log, chain, page_keys, pages, entries, links, recs):
    """Page payload's log entry, chain links and key record, one scatter
    each."""
    return (log.at[pages].set(entries, mode="drop"),
            chain.at[pages].set(links, mode="drop"),
            page_keys.at[pages].set(recs, mode="drop"))


@jax.jit
def _search_check(log, page_keys, keys, ptr, found):
    """The integrity check of each probed slot: one word a key, the page
    pointer of a verified key (0 otherwise) and the flags ``_FOUND``,
    ``_SHADOWED`` (an fp match on another key's page) and ``_ALIASED``
    (that page holds the key's fold)."""
    fp = SL.fingerprint(keys[:, 0])
    probed = jnp.where(found, SL.pack_slot(fp, ptr), 0)[:, None]
    verified = slot_match(probed, fp, page_keys, keys[:, 1:])[:, 0]
    shadowed = found & ~verified
    aliased = _aliased(probed, fp, log, keys, verified[:, None])
    return (jnp.where(verified, ptr | _FOUND, 0)
            | jnp.where(shadowed, _SHADOWED, 0)
            | jnp.where(aliased, _ALIASED, 0))


@partial(jax.jit, static_argnames=("slots_per_bucket",))
def _search_all_check(index, log, page_keys, keys, slots_per_bucket):
    """RACE's collision path: every candidate slot of both buckets of
    each key row checked by ``slot_match``; one word a key, as
    ``_search_check`` gives it (no ``_SHADOWED``)."""
    _, rows, fp = _candidates(index, keys, slots_per_bucket)
    is_match = slot_match(rows, fp, page_keys, keys[:, 1:])
    found = is_match.any(1)
    hit = jnp.take_along_axis(rows, jnp.argmax(is_match, 1)[:, None], 1)
    return (jnp.where(found, SL.slot_ptr(hit[:, 0]) | _FOUND, 0)
            | jnp.where(_aliased(rows, fp, log, keys, is_match), _ALIASED, 0))


@jax.jit
def _upsert_prepare(keys, pages):
    """Device copies of the padded key rows and pages, and the slot word
    each writer proposes."""
    return keys, pages, SL.pack_slot(SL.fingerprint(keys[:, 0]), pages)


@partial(jax.jit, static_argnames=("slots_per_bucket",))
def _upsert_select(index, page_keys, keys, pending, rng, slots_per_bucket):
    """Target slot per pending writer (its key's slot, else the first
    empty slot of the bucket pair; -1 = none) and the value it expects
    there, read from the primary; and the next split of the pool's key."""
    offs, rows, fp = _candidates(index, keys, slots_per_bucket)
    # a colliding entry is never overwritten (RACE integrity check)
    is_match = slot_match(rows, fp, page_keys, keys[:, 1:])
    is_empty = rows == 0
    cand = jnp.where(is_match.any(1), jnp.argmax(is_match, 1),
                     jnp.argmax(is_empty, 1))[:, None]
    ok = is_match.any(1) | is_empty.any(1)
    slot = jnp.take_along_axis(offs, cand, 1)[:, 0]
    v_old = jnp.take_along_axis(rows, cand, 1)[:, 0]
    rng, k = jax.random.split(rng)
    return jnp.where(pending & ok, slot, -1), v_old, rng, k


@jax.jit
def _upsert_commit(log, free_bitmap, win, v_old, pages):
    """Winners' log commit (old value into the embedded entry), and the
    free bit of each page a winner superseded (any-client bitmap free,
    §4.4, as ``KVPool.free_pages``) so upserts don't leak pool capacity."""
    n_pages = log.shape[0]
    log = log.at[jnp.where(win, pages, n_pages), 0].set(
        v_old | jnp.int32(1 << 30), mode="drop")
    gone = jnp.where(win & (v_old != 0), SL.slot_ptr(v_old), n_pages)
    return log, free_bitmap.at[gone].set(1, mode="drop")


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

    Device state (jnp): index replicas, page log, page key records,
    allocation chain, free bitmap.
    Host state (np): grant table cursor per shard, per-client slabs —
    exactly the split the paper prescribes (coarse state at MNs, fine state
    at clients)."""

    def __init__(self, cfg: PoolConfig, seed: int = 0):
        self.cfg = cfg
        M = cfg.n_buckets * cfg.slots_per_bucket
        self.index = jnp.zeros((cfg.replicas, M), jnp.int32)
        # page log: [old_value, opcode, key fold, flags(used|invalid<<1)]
        self.log = jnp.zeros((cfg.n_pages, 4), jnp.int32)
        # each page's key record (codec.key_record), written with its log
        self.page_keys = jnp.zeros((cfg.n_pages, codec.KEY_WORDS), jnp.int32)
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
        self.metrics = Registry()
        self._count = {k: self.metrics.counter("pool." + k) for k in COUNTERS}

    def _fetch(self, span: str, *arrays) -> List[np.ndarray]:
        """Read device arrays to the host, in order, inside the span
        ``<span>.wait``: the pool's one place that waits for the device."""
        with TraceAnnotation(span + ".wait"):
            out = [np.asarray(a) for a in arrays]
        self._count["host_syncs"].inc(len(out))
        self._count["d2h_bytes"].inc(sum(a.nbytes for a in out))
        return out

    def _keys_out(self, rows: np.ndarray, width: int) -> np.ndarray:
        """Key rows padded to ``width`` rows of zeros, to go to the
        device; counts their bytes (``pool.h2d_key_bytes``)."""
        out = np.zeros((width, KEY_COLS), np.int32)
        out[:len(rows)] = rows
        self._count["h2d_key_bytes"].inc(out.nbytes)
        return out

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
                self._count["alloc.grants"].inc()
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
        with TraceAnnotation("kv.pool.alloc"):
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
        """Write page payload + embedded log entry + key record in ONE
        device op (the paper's single-RDMA_WRITE log embedding).  Chain
        pointers come from the deterministic slab order (pre-positioned)."""
        with TraceAnnotation("kv.pool.write"):
            keys = as_key_rows(keys)
            sl = self._slab(cid)
            n = len(pages)
            nxt = np.array([sl.free[0] + 1 if sl.free else 0] * n, np.int32)
            for i in range(n - 1):
                nxt[i] = pages[i + 1] + 1
            prv = np.concatenate([[sl.last_alloc],
                                  pages[:-1] + 1]).astype(np.int32)
            if n:
                sl.last_alloc = int(pages[-1]) + 1
            W = _padded(n)
            entries = np.zeros((W, 4), np.int32)  # padding rows are dropped
            entries[:n, 1:] = np.stack([np.full(n, opcode), keys[:, 0],
                                        np.ones(n)], 1)
            links = np.zeros((W, 2), np.int32)
            links[:n] = np.stack([nxt, prv], 1)
            pg = _pad(np.asarray(pages, np.int32), W, self.cfg.n_pages)
            self.log, self.chain, self.page_keys = _write_step(
                self.log, self.chain, self.page_keys, pg, entries, links,
                self._keys_out(keys, W)[:, 1:])
            self._count["upsert.dispatches"].inc()

    def free_pages(self, pages: np.ndarray):
        """Any client: set free bits (the RDMA_FAA on the free bitmap)."""
        with TraceAnnotation("kv.pool.free"):
            pages = np.asarray(pages, np.int32)
            pg = _pad(pages, _padded(len(pages)), self.cfg.n_pages)
            self.free_bitmap = self.free_bitmap.at[jnp.asarray(pg)].set(
                1, mode="drop")

    def reclaim(self, cid: int) -> int:
        """Owner-side batched reclaim of freed pages in own chunks (§4.4)."""
        with TraceAnnotation("kv.pool.reclaim"):
            sl = self._slab(cid)
            bm, = self._fetch("kv.pool.reclaim", self.free_bitmap)
            n = 0
            for c in sl.chunks:
                base = c * self.cfg.chunk_pages
                for p in range(base, base + self.cfg.chunk_pages):
                    if bm[p]:
                        sl.free.append(p)
                        n += 1
            if n:
                cp = self.cfg.chunk_pages
                idx = jnp.asarray([p for c in sl.chunks
                                   for p in range(c * cp, (c + 1) * cp)])
                self.free_bitmap = self.free_bitmap.at[idx].set(0)
                self.log = self.log.at[idx, 3].set(0)
            return n

    # -------------------------------------------------------------- index --
    def search(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Batched SEARCH on the primary replica (race_lookup kernel), with
        the RACE data-access integrity check on the device: the page a
        probe points at must hold the key's own record, or the probe is a
        collision and the key's every candidate slot is checked.  Returns
        host (ptr, found), one each per key row."""
        with TraceAnnotation("kv.pool.search"):
            cfg = self.cfg
            keys = as_key_rows(keys)
            n = len(keys)
            self._count["search.calls"].inc()
            self._count["search.keys"].inc(n)
            with TraceAnnotation("kv.pool.search.probe"):
                idx2d = self.index[0].reshape(cfg.n_buckets,
                                              cfg.slots_per_bucket)
                kp = self._keys_out(keys, _padded(n))
                fold = np.ascontiguousarray(kp[:, 0])      # the probe's keys
                self._count["h2d_key_bytes"].inc(fold.nbytes)
                ptr_d, found_d = race_lookup(fold, idx2d)
            with TraceAnnotation("kv.pool.search.check"):
                word_d = _search_check(self.log, self.page_keys, kp, ptr_d,
                                       found_d)
            word, = self._fetch("kv.pool.search", word_d)
            word = word[:n]
            shadowed = (word & _SHADOWED) != 0
            n_shadowed = int(shadowed.sum())
            self._count["search.shadowed"].inc(n_shadowed)
            if n_shadowed:
                # collision path (read amplification): check ALL candidate
                # slots of both buckets, as RACE prescribes
                word = word.copy()
                word[shadowed] = (self._search_all_candidates(keys[shadowed])
                                  | (word[shadowed] & _ALIASED))
            self._count["search.aliased"].inc(
                int(((word & _ALIASED) != 0).sum()))
            return word & SL.MASK24, (word & _FOUND) != 0

    def _search_all_candidates(self, keys: np.ndarray) -> np.ndarray:
        """The collision path for key rows: one word a key, as
        ``_search_all_check`` gives it."""
        with TraceAnnotation("kv.pool.search.collision"):
            n = len(keys)
            word, = self._fetch(
                "kv.pool.search.collision",
                _search_all_check(self.index, self.log, self.page_keys,
                                  self._keys_out(keys, _padded(n)),
                                  slots_per_bucket=self.cfg.slots_per_bucket))
            return word[:n]

    def insert_batch(self, cid: int, keys: np.ndarray, pages: np.ndarray,
                     opcode: int = OP_INSERT) -> np.ndarray:
        """INSERT/UPDATE a batch of prefix keys -> pages via one (or more)
        SNAPSHOT epochs.  Returns success mask.

        Device work: one prepare program, then per attempt the select,
        ``snapshot_epoch`` and commit programs and one read, of the
        winners; pending writers are tracked on the host."""
        with TraceAnnotation("kv.pool.upsert"):
            cfg = self.cfg
            keys = as_key_rows(keys)
            n = len(keys)
            self._count["upsert.calls"].inc()
            self._count["upsert.keys"].inc(n)
            with TraceAnnotation("kv.pool.upsert.prepare"):
                W = _padded(n)
                keys_j, pages_j, v_new = _upsert_prepare(
                    self._keys_out(keys, W),
                    _pad(np.asarray(pages, np.int32), W, 0))
                self._count["upsert.dispatches"].inc()
                done = np.arange(W) >= n        # padding writers never act
            for _ in range(2 * cfg.slots_per_bucket):
                with TraceAnnotation("kv.pool.upsert.attempt"):
                    self._count["upsert.attempts"].inc()
                    self._count["upsert.writers"].inc(int((~done).sum()))
                    with TraceAnnotation("kv.pool.upsert.select"):
                        slot_i, v_old, self.key, k = _upsert_select(
                            self.index, self.page_keys, keys_j, ~done,
                            self.key, slots_per_bucket=cfg.slots_per_bucket)
                    with TraceAnnotation("kv.pool.upsert.epoch"):
                        res = snapshot_epoch(self.index, slot_i, v_old,
                                             v_new, k)
                        self.index = res.index
                    with TraceAnnotation("kv.pool.upsert.commit"):
                        self.log, self.free_bitmap = _upsert_commit(
                            self.log, self.free_bitmap, res.win, v_old,
                            pages_j)
                    self._count["upsert.dispatches"].inc(3)
                    win, = self._fetch("kv.pool.upsert", res.win)
                    done |= win
                    if done.all():
                        break
            return done[:n]

    def delete_batch(self, cid: int, keys: np.ndarray) -> np.ndarray:
        """DELETE: SNAPSHOT-write each found key's slot -> 0 (plus temp log
        page, elided)."""
        with TraceAnnotation("kv.pool.delete"):
            keys = as_key_rows(keys)
            n = len(keys)
            W = _padded(n)
            _, found = self.search(keys)
            # a found key's slot is its match: the select's empty-slot
            # fallback never acts
            slot_i, v_old, self.key, k = _upsert_select(
                self.index, self.page_keys, self._keys_out(keys, W),
                _pad(found, W, False), self.key,
                slots_per_bucket=self.cfg.slots_per_bucket)
            res = snapshot_epoch(self.index, slot_i, v_old,
                                 jnp.zeros_like(v_old), k)
            self.index = res.index
            self._count["delete.epochs"].inc()
            # free the deleted pages (any-client free via bitmap)
            dead, win = self._fetch(
                "kv.pool.delete",
                jnp.where(res.win, SL.slot_ptr(v_old), -1), res.win)
            self.free_pages(dead[dead >= 0])
            return win[:n]

    # ----------------------------------------------------------- recovery --
    def crash_client(self, cid: int):
        self.slabs.pop(cid, None)

    def recover_client(self, cid: int, reassign_to: Optional[int] = None
                       ) -> Dict[str, int]:
        """§5.3 for the serving pool: re-own chunks from the grant table,
        walk the embedded-log chain, reclaim unused pages, redo uncommitted
        winner index writes."""
        with TraceAnnotation("kv.pool.recover"):
            cfg = self.cfg
            stats = {"chunks": 0, "used_pages": 0, "reclaimed": 0, "redone": 0}
            chunks = np.where(self.grant == cid + 1)[0]
            stats["chunks"] = len(chunks)
            log, recs = self._fetch("kv.pool.recover", self.log,
                                    self.page_keys)
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
                        # with the page's own key: its fold and record
                        row = np.concatenate([log[p, 2:3], recs[p]])[None]
                        ok = self.insert_batch(new_owner, row,
                                               np.array([p], np.int32),
                                               opcode=int(log[p, 1]))
                        stats["redone"] += int(ok[0])
            return stats

    # --------------------------------------------------------- invariants --
    def check_replicas_converged(self) -> bool:
        idx = np.asarray(self.index)
        return bool((idx[1:] == idx[0]).all()) if self.cfg.replicas > 1 else True
