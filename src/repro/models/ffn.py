"""Feed-forward layers: dense SwiGLU and expert-parallel MoE.

MoE design (TPU-native adaptation, see DESIGN.md §2): routing is computed
redundantly on every model shard (router weights are tiny and replicated),
experts are sharded over the ``expert`` rule axis ('model'), and tokens stay
resident on their data shard.  Each (data, model) device scatters its local
tokens into the capacity buffers of *its own* experts, runs the expert FFNs,
scatters results back, and a single ``psum`` over the model axis merges the
per-expert partial outputs — the same collective cost as a Megatron TP FFN
(one all-reduce of (tokens, d_model)), with **zero all-to-alls**.  This is
the DeepSeek-EP-style redundant-routing layout; it sidesteps GShard's
(tokens, experts, capacity) dispatch einsum, which cannot be materialized at
384 experts x 1M tokens.

Expert weights are additionally FSDP-sharded over 'data' and all-gathered
just-in-time inside the shard_map (manual ZeRO-3; the transpose rule makes
the backward a reduce-scatter of the weight grads).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ParamBuilder


def make_dense_ffn_params(pb: ParamBuilder, d_model: int, d_ff: int):
    return {
        "w_gate": pb.param((d_model, d_ff), ("fsdp", "mlp")),
        "w_up": pb.param((d_model, d_ff), ("fsdp", "mlp")),
        "w_down": pb.param((d_ff, d_model), ("mlp", "fsdp")),
    }


def dense_ffn(p, x):
    dt = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                      p["w_down"].astype(dt))


def make_moe_params(pb: ParamBuilder, d_model: int, n_experts: int,
                    d_ff_expert: int):
    return {
        "router": pb.param((d_model, n_experts), (None, None), scale=1.0),
        "experts": {
            "w_gate": pb.param((n_experts, d_model, d_ff_expert),
                               ("expert", "expert_din", "expert_dff")),
            "w_up": pb.param((n_experts, d_model, d_ff_expert),
                             ("expert", "expert_din", "expert_dff")),
            "w_down": pb.param((n_experts, d_ff_expert, d_model),
                               ("expert", "expert_dff", "expert_din")),
        },
    }


def _axes_tuple(ax):
    if ax is None:
        return ()
    return (ax,) if isinstance(ax, str) else tuple(a for a in ax)


class MoEContext:
    """Mesh-resolved shard_map specs for the MoE layer (built once per model)."""

    def __init__(self, mesh, rules, n_experts: int, top_k: int,
                 capacity_factor: float):
        self.mesh = mesh
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        exp_axes = tuple(a for a in _axes_tuple(rules.expert) if a in sizes)
        din_axes = tuple(a for a in _axes_tuple(rules.expert_din)
                         if a in sizes)
        dff_axes = tuple(a for a in _axes_tuple(rules.expert_dff)
                         if a in sizes)
        batch_axes = tuple(a for a in _axes_tuple(rules.batch) if a in sizes)
        ep = math.prod(sizes[a] for a in exp_axes) if exp_axes else 1
        if n_experts % max(ep, 1):
            exp_axes, ep = (), 1  # fallback: replicate experts
        self.exp_axes, self.batch_axes = exp_axes, batch_axes
        self.fsdp_axes = din_axes        # 'gather' layout: D sharded (FSDP)
        self.dff_axes = dff_axes         # 'split' layout: F sharded
        self.split_layout = bool(dff_axes)
        self.ep = ep
        e_ax = exp_axes if exp_axes else None
        self.x_spec = P(batch_axes if batch_axes else None, None, None)
        self.w_spec = P(e_ax, din_axes if din_axes else None,
                        dff_axes if dff_axes else None)
        self.wd_spec = P(e_ax, dff_axes if dff_axes else None,
                         din_axes if din_axes else None)
        self.r_spec = P(None, None)
        # expert shards each contribute partial sums for their experts only;
        # the psum over the expert axes merges them.  Axes that are neither
        # batch nor expert see fully replicated compute (no psum, or the
        # output would be multiplied by the axis size).
        self.reduce_axes = exp_axes


def moe_ffn(ctx: MoEContext, p, x):
    """x: (B, S, D) sharded per ctx.x_spec -> (B, S, D)."""

    def local(router, wg, wu, wd, xl):
        Bl, Sl, D = xl.shape
        T = Bl * Sl
        E, k = ctx.n_experts, ctx.top_k
        e_loc = E // ctx.ep
        cap = max(1, int(math.ceil(T * k / E * ctx.capacity_factor)))
        xt = xl.reshape(T, D)
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
        gate, idx = jax.lax.top_k(probs, k)                      # (T, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        # position of each (token, slot) within its expert's capacity buffer
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # (T, k, E)
        flat = onehot.reshape(T * k, E)
        pos = jnp.cumsum(flat, axis=0) - 1                       # (T*k, E)
        pos = jnp.sum(pos * flat, axis=-1).reshape(T, k)         # (T, k)
        keep = pos < cap

        # this shard owns experts [lo, lo+e_loc)
        if ctx.exp_axes:
            ep_idx = jax.lax.axis_index(ctx.exp_axes[0])
            for a in ctx.exp_axes[1:]:
                ep_idx = ep_idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        else:
            ep_idx = 0
        lo = ep_idx * e_loc
        mine = (idx >= lo) & (idx < lo + e_loc) & keep
        le = jnp.where(mine, idx - lo, e_loc)                    # e_loc = drop row
        lp = jnp.where(mine, pos, cap)

        # scatter tokens into (e_loc, cap, D) capacity buffers (+1 drop row)
        buf = jnp.zeros((e_loc + 1, cap + 1, D), xt.dtype)
        buf = buf.at[le.reshape(-1), lp.reshape(-1)].add(
            jnp.repeat(xt, k, axis=0))
        buf = buf[:e_loc, :cap]

        if ctx.split_layout:
            # 'split' layout (decode): weights stay put (F sharded over the
            # dff axes, which coincide with the batch/data axes at decode);
            # the *tokens* travel instead: gather every data shard's tiny
            # capacity buffers, compute the F-shard partial for all of them,
            # psum the down-proj partials, and keep the local slice.  Wire
            # bytes are O(experts x cap x D) activations — MBs — instead of
            # the gather layout's per-step expert-weight all-gathers (GBs).
            my = 0
            for a in ctx.dff_axes:
                my = my * jax.lax.axis_size(a) + jax.lax.axis_index(a)
                buf = jax.lax.all_gather(buf, a, axis=1, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype))
            u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
            y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                           wd.astype(xt.dtype))
            for a in ctx.dff_axes:
                y = jax.lax.psum(y, a)
            y = jax.lax.dynamic_slice_in_dim(y, my * cap, cap, axis=1)
        else:
            # 'gather' layout (train): JIT all-gather of FSDP-sharded expert
            # weights (manual ZeRO-3) — right when tokens >> weights.
            for a in ctx.fsdp_axes:
                wg = jax.lax.all_gather(wg, a, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, a, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, a, axis=2, tiled=True)
            g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(xt.dtype))
            u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(xt.dtype))
            y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                           wd.astype(xt.dtype))                  # (e_loc,cap,D)

        # gather back with gate weights; drop-row trick keeps shapes static
        y = jnp.pad(y, ((0, 1), (0, 1), (0, 0)))
        picked = y[le, lp]                                       # (T, k, D)
        out = jnp.einsum("tkd,tk->td", picked,
                         gate.astype(picked.dtype) * mine.astype(picked.dtype))
        for a in ctx.reduce_axes:
            out = jax.lax.psum(out, a)
        return out.reshape(Bl, Sl, D)

    fn = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(ctx.r_spec, ctx.w_spec, ctx.w_spec, ctx.wd_spec, ctx.x_spec),
        out_specs=ctx.x_spec,
        check_vma=False,
    )
    e = p["experts"]
    return fn(p["router"], e["w_gate"], e["w_up"], e["w_down"], x)
