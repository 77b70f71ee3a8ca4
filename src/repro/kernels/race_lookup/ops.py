"""jit'd public wrapper for the RACE index-probe kernel, plus the
host-facing **batched entry point** used by the simulator's fleet mode.

``race_lookup`` is the jitted device API (jnp in / jnp out).
``race_lookup_batch`` is the fleet entry point: uint32 numpy in / numpy
out, pads the key batch to the kernel block size, and — because one fleet
tick probes on behalf of *every* client at once with constantly growing
shadow tables — routes through the Pallas kernel only where that is a
win (TPU); elsewhere it runs the exact numpy mirror of the kernel's
hash/probe sequence (one vectorized gather, no per-key work, no
per-shape recompiles).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.shadow import (MASK24, build_shadow,  # noqa: F401
                               hash32_np, race_lookup_np)

from ..platform import interpret_mode, on_tpu
from .kernel import race_lookup_fwd
from .ref import race_lookup_ref


@partial(jax.jit, static_argnames=("use_kernel", "block_keys"))
def race_lookup(keys, index, *, block_keys: int = 256, use_kernel: bool = True):
    """Batched RACE probe: keys (N,) int32, index (n_buckets, spb) int32
    -> (ptr (N,) int32, found (N,) bool)."""
    if not use_kernel:
        return race_lookup_ref(keys, index)
    return race_lookup_fwd(keys, index, block_keys=block_keys,
                           interpret=interpret_mode())


def race_lookup_batch(q: np.ndarray, table: np.ndarray, *,
                      block_keys: int = 256,
                      prefer_kernel: bool = None):
    """Fleet entry point: probe uint32 keys ``q`` (N,) against a uint32
    shadow table (nb, spb); returns (ptr (N,) uint32, found (N,) bool) as
    numpy arrays.  One invocation serves the whole batch — the caller
    (core/fleet.py, core/api.py) concatenates every client's keys for the
    tick before calling.

    ``prefer_kernel=None`` auto-selects: the Pallas kernel on TPU, the
    bit-identical numpy mirror elsewhere (interpret-mode Pallas would
    execute per-element and recompile per shape — exactly what a
    thousand-client tick cannot afford)."""
    q = np.ascontiguousarray(q, np.uint32)
    if prefer_kernel is None:
        prefer_kernel = on_tpu()
    if not prefer_kernel:
        return race_lookup_np(q, table)
    n = len(q)
    pad = -(-max(n, 1) // block_keys) * block_keys - n
    qp = jnp.asarray(np.concatenate([q, np.zeros(pad, np.uint32)])
                     .view(np.int32))
    ptr, found = race_lookup(qp, jnp.asarray(table.view(np.int32)),
                             block_keys=block_keys)
    return (np.asarray(ptr[:n]).view(np.uint32).astype(np.uint32),
            np.asarray(found[:n]))
