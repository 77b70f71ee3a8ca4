"""Pallas TPU kernel for the batched ordered-index leaf probe — the
"leaf search" phase of SCAN (core/ordered.py) as a fleet-scale hot spot:
one invocation locates the covering leaf of EVERY client's scan start key
in a tick (fleet.locate_wave), or a scan's start in the device keydir.

Shape of the problem: the fence table (sorted lows) ranges from a few
thousand leaf fences to the whole keydir (100K keys at YCSB scale), so it
is streamed through fast memory in BLOCK_M tiles on a reduction grid axis,
and each start's count of lows <= start accumulates in its output block:

    starts (N, 1) on sublanes  x  lows (1, M) on lanes  ->  counts (N, 1)

64-bit keys on 32-bit lanes: inputs arrive pre-split into (hi, lo) uint32
halves; ``low <= start`` is the lexicographic pair compare.  The result
``count(lows <= start) - 1`` is a compare-and-reduce on the VPU — no
gather, no MXU.  The tail of the fence table is padded with the largest
key, which only a start equal to it counts; the count is clamped to M.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_M = 1024                  # fences per tile: (256, 1024) int32 compares


def _probe_kernel(shi_ref, slo_ref, lhi_ref, llo_ref, cnt_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    shi = shi_ref[...]                                # (BK, 1)
    slo = slo_ref[...]
    lhi = lhi_ref[...]                                # (1, BM)
    llo = llo_ref[...]
    le = (lhi < shi) | ((lhi == shi) & (llo <= slo))  # (BK, BM)
    cnt_ref[...] += jnp.sum(le.astype(jnp.int32), axis=1, keepdims=True)


def leaf_probe_fwd(starts_hi, starts_lo, lows_hi, lows_lo, *,
                   block_keys: int = 256, interpret: bool = True):
    """starts: (N,) uint32 halves; lows: (M,) uint32 halves (sorted as
    uint64) -> (N,) int32 rightmost-low-<=-start indices (-1 = none)."""
    N = starts_hi.shape[0]
    M = lows_hi.shape[0]
    block_keys = min(block_keys, N)
    assert N % block_keys == 0
    Mp = -(-M // BLOCK_M) * BLOCK_M
    top = jnp.uint32(0xFFFFFFFF)
    lhi = jnp.pad(lows_hi, (0, Mp - M), constant_values=top).reshape(1, Mp)
    llo = jnp.pad(lows_lo, (0, Mp - M), constant_values=top).reshape(1, Mp)
    start_spec = pl.BlockSpec((block_keys, 1), lambda i, j: (i, 0))
    low_spec = pl.BlockSpec((1, BLOCK_M), lambda i, j: (0, j))
    count = pl.pallas_call(
        _probe_kernel,
        grid=(N // block_keys, Mp // BLOCK_M),
        in_specs=[start_spec, start_spec, low_spec, low_spec],
        out_specs=start_spec,
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(starts_hi.reshape(N, 1), starts_lo.reshape(N, 1), lhi, llo)
    return jnp.minimum(count[:, 0], M) - 1
