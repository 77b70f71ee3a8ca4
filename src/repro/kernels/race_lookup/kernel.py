"""Pallas TPU kernel for the batched RACE hash-index probe — the FUSEE
client SEARCH phase-1 (§4.2) as a serving hot-spot.

TPU adaptation of the one-sided-RDMA probe.  The index at a real size
(32768 buckets x 8 slots for 100K keys) does not fit fast memory, and the
TPU has no vector gather across sublanes, so the wrapper fetches each
key's two candidate bucket rows with one XLA gather in HBM (the RDMA_READ
of both buckets) and hands the kernel the rows transposed, keys on lanes:

    keys  (1, N)        int32
    rows  (2*spb, N)    int32   bucket-1 slots, then bucket-2 slots

The kernel hashes the fingerprint on the VPU and picks the first slot
whose fingerprint matches, bucket-1 slots before bucket-2 slots — integer
work only, so it is bit-exact with ``race_lookup_ref``.

Grid: (N / BLOCK_KEYS,), BLOCK_KEYS a multiple of 128 lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import MASK24, bucket_pair, fingerprint


def _lookup_kernel(keys_ref, rows_ref, ptr_ref, found_ref):
    fp = fingerprint(keys_ref[...])                   # (1, BK)
    rows = rows_ref[...]                              # (2*spb, BK)
    n_slots = rows.shape[0]
    match = ((rows >> 24) & 0xFF) == fp
    slot = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    first = jnp.min(jnp.where(match, slot, n_slots), axis=0, keepdims=True)
    found = first < n_slots
    picked = jnp.sum(jnp.where(slot == first, rows, 0), axis=0, keepdims=True)
    ptr_ref[...] = jnp.where(found, picked & MASK24, 0)
    found_ref[...] = found.astype(jnp.int32)


def race_lookup_fwd(keys, index, *, block_keys: int = 256,
                    interpret: bool = True):
    """keys: (N,) int32; index: (n_buckets, spb) int32 -> (ptr, found)."""
    N = keys.shape[0]
    nb, spb = index.shape
    block_keys = min(block_keys, N)
    assert N % block_keys == 0
    b1, b2 = bucket_pair(keys, nb)
    rows = jnp.concatenate([index[b1], index[b2]], axis=1).T   # (2*spb, N)
    key_spec = pl.BlockSpec((1, block_keys), lambda i: (0, i))
    ptr, found = pl.pallas_call(
        _lookup_kernel,
        grid=(N // block_keys,),
        in_specs=[key_spec,
                  pl.BlockSpec((2 * spb, block_keys), lambda i: (0, i))],
        out_specs=[key_spec, key_spec],
        out_shape=[jax.ShapeDtypeStruct((1, N), jnp.int32)] * 2,
        interpret=interpret,
    )(keys.reshape(1, N), rows)
    return ptr[0], found[0].astype(jnp.bool_)
