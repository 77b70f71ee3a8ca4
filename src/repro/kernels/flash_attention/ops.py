"""jit'd public wrapper for the flash-attention kernel.

On CPU (this container) the kernel executes in interpret mode; on TPU it
compiles to a fused Mosaic kernel.  ``use_kernel=False`` falls back to the
pure-jnp twin used by the dry-run lowering.
"""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret_mode
from .kernel import flash_attention_fwd
from .ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                   "use_kernel"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_kv: int = 512, use_kernel: bool = True):
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret_mode())
