"""jit'd public wrapper for the paged decode-attention kernel."""
from __future__ import annotations

from functools import partial

import jax

from ..platform import interpret_mode
from .kernel import paged_attention_fwd
from .ref import paged_attention_ref


@partial(jax.jit, static_argnames=("use_kernel",))
def paged_attention(q, kc, vc, valid_len, *, use_kernel: bool = True):
    """q: (B, H, hd); kc/vc: (nb, tb, B, KV, hd) -> (B, H, hd)."""
    if not use_kernel:
        return paged_attention_ref(q, kc, vc, valid_len)
    return paged_attention_fwd(q, kc, vc, valid_len,
                               interpret=interpret_mode())
