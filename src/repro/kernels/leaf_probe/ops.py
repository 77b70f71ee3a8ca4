"""jit'd public wrapper for the leaf-probe kernel, plus the host-facing
**batched entry point** used by the simulator, the fleet engine, and the
serving backend.

``leaf_probe`` is the jitted device API (jnp in / jnp out, pre-split
hi/lo halves).  ``leaf_probe_batch`` is the shared entry point: uint64
numpy in / numpy out, pads the key batch to the kernel block size, and
routes through the Pallas kernel only on TPU — elsewhere it runs the
bit-exact numpy mirror (``core.ordered.leaf_probe_np``, a uint64
searchsorted; interpret-mode Pallas would recompile per shape on every
fleet tick whose fence table grew).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ordered import leaf_probe_np  # noqa: F401  (re-export)

from ..platform import interpret_mode, on_tpu
from .kernel import BLOCK_M, leaf_probe_fwd
from .ref import leaf_probe_ref


@partial(jax.jit, static_argnames=("use_kernel", "block_keys"))
def leaf_probe(starts_hi, starts_lo, lows_hi, lows_lo, *,
               block_keys: int = 256, use_kernel: bool = True):
    """Batched leaf probe on pre-split uint32 halves -> (N,) int32."""
    if not use_kernel:
        return leaf_probe_ref(starts_hi, starts_lo, lows_hi, lows_lo)
    return leaf_probe_fwd(starts_hi, starts_lo, lows_hi, lows_lo,
                          block_keys=block_keys, interpret=interpret_mode())


def leaf_probe_batch(starts: np.ndarray, lows: np.ndarray, *,
                     block_keys: int = 256,
                     prefer_kernel: bool = None) -> np.ndarray:
    """Shared entry point: locate the rightmost ``lows`` entry <= each
    start key.  ``starts`` (N,) uint64, ``lows`` (M,) uint64 sorted
    ascending; returns (N,) int32 (-1 = every low exceeds the start).

    One invocation serves a whole fleet tick's scans — callers
    (core/fleet.py locate_wave, core/api.py, serving/backend.py)
    concatenate every client's start keys before calling."""
    starts = np.ascontiguousarray(starts, np.uint64)
    lows = np.ascontiguousarray(lows, np.uint64)
    if prefer_kernel is None:
        prefer_kernel = on_tpu()
    if not (prefer_kernel and len(lows)):
        return leaf_probe_np(starts, lows)
    n, m = len(starts), len(lows)
    pad = -(-max(n, 1) // block_keys) * block_keys - n
    sp = np.concatenate([starts, np.zeros(pad, np.uint64)])
    # the fence table grows a power of two at a time, so a growing keydir
    # compiles O(log M) shapes; the largest-key padding is clamped away
    lp = np.full(max(BLOCK_M, 1 << (m - 1).bit_length()),
                 np.uint64(2 ** 64 - 1))
    lp[:m] = lows
    idx = leaf_probe(*_halves(sp), *_halves(lp), block_keys=block_keys)
    return np.minimum(np.asarray(idx[:n], np.int32), m - 1)


def _halves(x: np.ndarray):
    return (jnp.asarray((x >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((x & np.uint64(0xFFFFFFFF)).astype(np.uint32)))
