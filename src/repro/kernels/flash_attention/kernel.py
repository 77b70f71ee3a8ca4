"""Pallas TPU flash-attention kernel (forward).

Grid: (batch * q_heads, Sq / BLOCK_Q).  Each program streams the KV
sequence in BLOCK_KV tiles through VMEM, carrying the online-softmax state
(m, l, acc) in VMEM scratch.  GQA is handled in the BlockSpec index maps: a
query head h reads kv head h // (H // KV) — no repeated KV in HBM.

Block shapes are MXU-aligned: BLOCK_Q x head_dim and BLOCK_KV x head_dim
tiles keep the two matmuls (q @ k^T and p @ v) on 128-multiple dims.
Causal masking is computed from absolute positions (program ids), and fully
-masked KV tiles are skipped via ``when`` predication on the tile index.

VMEM working set (defaults BQ=256, BK=512, hd=128, bf16):
    q 64KB + k/v 256KB + acc/m/l fp32 ~160KB + panel 512KB  <  ~1.2MB  OK
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_q, block_kv,
                causal, seq_kv):
    qi = pl.program_id(1)                       # q-tile index
    nkv = seq_kv // block_kv

    q = q_ref[...].astype(jnp.float32) * scale  # (BQ, hd)

    def body(kv_i, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(kv_i * block_kv, block_kv), :].astype(jnp.float32)
        v = v_ref[pl.ds(kv_i * block_kv, block_kv), :].astype(jnp.float32)
        s = q @ k.T                              # (BQ, BKV) on the MXU
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            k_pos = kv_i * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    if causal:
        # skip KV tiles strictly above the diagonal
        last = jnp.minimum(nkv, (qi + 1) * block_q // block_kv
                           + (1 if block_q % block_kv else 0) + 1)
        upper = jnp.minimum(last, nkv)
    else:
        upper = nkv
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, block_q: int = 256,
                        block_kv: int = 512, interpret: bool = True):
    """q: (B, H, Sq, hd); k/v: (B, KV, Skv, hd) -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0
    scale = hd ** -0.5
    grid = (B * H, Sq // block_q)

    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_kv=block_kv, causal=causal, seq_kv=Skv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bh, qi: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((None, None, Skv, hd),
                         lambda bh, qi: (bh // H, (bh % H) // G, 0, 0)),
            pl.BlockSpec((None, None, Skv, hd),
                         lambda bh, qi: (bh // H, (bh % H) // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bh, qi: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        interpret=interpret,
    )(q, k, v)
