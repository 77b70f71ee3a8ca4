"""Pallas TPU kernels for the perf-critical compute layers, each with a
pure-jnp oracle (ref.py) and a jit'd wrapper (ops.py):

* flash_attention — train/prefill attention (online softmax, GQA-aware)
* paged_attention — decode attention over the FUSEE block pool
* race_lookup     — batched RACE hash-index probe (FUSEE SEARCH phase 1)
* leaf_probe      — batched ordered-index leaf search (SCAN locate phase)
* fleet_tick      — fused-tick READ sweep (paged slab gather via scalar
                    prefetch; the numpy exec_fused_tick stays the CPU
                    authority)

``platform.py`` decides how they run: compiled to Mosaic on the TPU,
``interpret=True`` on the CPU, an error anywhere else.  Correctness is
swept over shapes/dtypes in tests/test_kernels.py; tests/test_tpu_compile.py
compiles race_lookup and leaf_probe for a described v5e.  fleet_tick does
not compile for the TPU yet.
"""
from .flash_attention import flash_attention, flash_attention_ref  # noqa
from .paged_attention import paged_attention, paged_attention_ref  # noqa
from .race_lookup import race_lookup, race_lookup_batch, race_lookup_ref  # noqa
from .leaf_probe import leaf_probe, leaf_probe_batch, leaf_probe_ref  # noqa
from .fleet_tick import fleet_read, fleet_read_sweep, fleet_read_ref  # noqa
