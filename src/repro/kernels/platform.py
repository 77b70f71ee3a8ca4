"""The one place that decides how Pallas kernels run in this process.

On the TPU they compile to Mosaic.  On the CPU they run in interpret mode
(tests, small CPU rehearsals).  Any other platform raises: a kernel never
falls back to the interpreter, or to numpy, without the caller choosing so.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """``interpret=`` for ``pl.pallas_call`` on this process's platform."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")
