"""jit'd public wrapper for the fleet-tick read-sweep kernel, plus the
host-facing batched entry point.

``fleet_read`` is the jitted device API (jnp in / jnp out, pre-split
hi/lo slab planes).  ``fleet_read_sweep`` is the shared entry point:
uint64 numpy slab in / numpy rows out; it routes through the Pallas
kernel only on TPU — elsewhere it runs the bit-exact numpy gather.  The
numpy ``DMPool.exec_fused_tick`` stays **authoritative** on CPU (it is
the simulator's replay-oracle-checked engine); this twin covers the
READ sweep — the tick's only pure gather — for device offload.  The
mutating sweeps (WRITE/CAS/FAA) update host slab state and stay on the
host.  The kernel does not compile for the TPU yet (its one-row
``(1, region_words)`` blocks break the sublane tiling), so on a TPU the
entry point raises unless the caller passes ``prefer_kernel=False``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..platform import interpret_mode, on_tpu
from .kernel import fleet_read_fwd
from .ref import fleet_read_ref


@partial(jax.jit, static_argnames=("n", "use_kernel"))
def fleet_read(slab_hi, slab_lo, cells, offs, *, n: int,
               use_kernel: bool = True):
    """Uniform-length read sweep on pre-split uint32 slab planes ->
    ((N, n), (N, n)) uint32."""
    if not use_kernel:
        return fleet_read_ref(slab_hi, slab_lo, cells, offs, n=n)
    return fleet_read_fwd(slab_hi, slab_lo, cells, offs, n=n,
                          interpret=interpret_mode())


def fleet_read_sweep(slab: np.ndarray, region_words: int,
                     cells: np.ndarray, offs: np.ndarray, n: int, *,
                     prefer_kernel: bool = None) -> np.ndarray:
    """Gather ``n`` contiguous uint64 words per verb from the flat slab.

    ``slab`` is the DMPool's flat uint64 buffer (``pool.slab.buf``),
    viewed as ``(n_cells, region_words)``; ``cells``/``offs`` are the
    per-verb cell indices and in-region word offsets (``n`` uniform —
    callers group verbs by length).  Returns (N, n) uint64 rows."""
    cells = np.ascontiguousarray(cells, np.int64)
    offs = np.ascontiguousarray(offs, np.int64)
    slab2d = slab.reshape(-1, region_words)
    if prefer_kernel is None:
        prefer_kernel = on_tpu()
    if not (prefer_kernel and len(cells)):
        return slab2d[cells[:, None], offs[:, None] + np.arange(n)]
    hi = jnp.asarray((slab2d >> np.uint64(32)).astype(np.uint32))
    lo = jnp.asarray((slab2d & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    rhi, rlo = fleet_read(hi, lo, jnp.asarray(cells, jnp.int32),
                          jnp.asarray(offs, jnp.int32), n=n)
    return (np.asarray(rhi, np.uint64) << np.uint64(32)) \
        | np.asarray(rlo, np.uint64)
