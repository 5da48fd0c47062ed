"""Expert-parallel MoE via shard_map (the §Perf fix for GSPMD dispatch).

Problem (baseline, see EXPERIMENTS.md §Perf): the scatter-based capacity
dispatch in `layers.moe_fwd` makes GSPMD materialize and **all-reduce the
whole [E·C, d] dispatch buffer over the data axis** (deepseek train_4k:
8.4 TB all-reduce + 4.4 TB all-to-all per device per step).

Insight: activations are *batch-sharded only* — every model-axis rank
already holds its data-shard's full token slab. So expert dispatch needs no
token movement at all: each (data, model) device gathers, from its local
tokens, the ones routed to ITS experts (experts are sharded over 'model'),
runs its expert FFNs, scatters partial outputs back to local token slots,
and a single `psum` over 'model' combines expert contributions — the same
collective shape as ordinary tensor parallelism (2(g-1)/g · t_loc · d
bytes/layer instead of the buffer-sized all-reduce).

Capacity becomes per-(data-shard × expert): C_loc = t_loc·k/E·cf — dropping
decisions are local, which is how real EP systems behave under skew.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as SH
from .config import ModelConfig
from .layers import _act, mlp_fwd

Params = dict


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def moe_fwd_ep(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Drop-in for layers.moe_fwd when a mesh with a 'model' axis is active
    and the expert count divides it. Falls back to the caller otherwise."""
    mesh = SH._CTX.mesh
    if mesh is None or "model" not in mesh.shape \
            or cfg.moe_num_experts % mesh.shape["model"] != 0:
        from .layers import moe_fwd
        return moe_fwd(p, x, cfg)

    dp = _dp_axes(mesh)
    ep = mesh.shape["model"]
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    e_loc = e // ep

    x_spec = P(dp, None, None)           # batch-sharded, replicated on model
    router_spec = P(None, None)
    # expert weights stay ZeRO-3 sharded at rest (expert -> model, d -> data)
    # and are all-gathered over 'data' just-in-time inside the block.
    wi_spec = P("model", "data", None)
    wo_spec = P("model", "data", None)
    # shared experts: TP-sharded on ff inside the block; their partial output
    # joins the experts' psum, so the layer pays ONE all-reduce total and no
    # duplicate compute.
    shared = p.get("shared") if cfg.moe_shared_experts else None
    if shared is not None:
        sh_in_spec = P(None, "model")
        sh_out_spec = P("model", None)
        sh_args = (shared["wi_gate"], shared["wi_up"], shared["wo"])
    else:  # replicated placeholders so the block signature is static
        sh_in_spec = sh_out_spec = P(None, None)
        z = jnp.zeros((1, 1), x.dtype)
        sh_args = (z, z, z)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(x_spec, router_spec, wi_spec, wi_spec, wo_spec,
                  sh_in_spec, sh_in_spec, sh_out_spec),
        out_specs=x_spec, check_vma=False)
    def ep_block(x_loc, router, wi_gate, wi_up, wo, sh_gate, sh_up, sh_wo):
        if "data" in mesh.shape and mesh.shape["data"] > 1:
            wi_gate = lax.all_gather(wi_gate, "data", axis=1, tiled=True)
            wi_up = lax.all_gather(wi_up, "data", axis=1, tiled=True)
            wo = lax.all_gather(wo, "data", axis=1, tiled=True)
        bl, sl, _ = x_loc.shape
        t_loc = bl * sl
        cap = max(1, int(math.ceil(t_loc * k / e * cfg.capacity_factor)))
        xt = x_loc.reshape(t_loc, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = lax.top_k(probs, k)                       # [t_loc, k]
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        my_first = lax.axis_index("model") * e_loc
        # rank-within-(local)expert via sort over the local assignment list
        e_flat = idx.reshape(t_loc * k)
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = jnp.take(e_flat, order)
        counts = jax.ops.segment_sum(jnp.ones_like(e_sorted, jnp.int32),
                                     e_sorted, num_segments=e)
        starts = jnp.cumsum(counts) - counts
        rank_sorted = jnp.arange(t_loc * k, dtype=jnp.int32) - jnp.take(starts, e_sorted)
        rank = jnp.zeros((t_loc * k,), jnp.int32).at[order].set(rank_sorted)

        local_e = e_flat - my_first
        mine = (local_e >= 0) & (local_e < e_loc) & (rank < cap)
        dest = jnp.where(mine, local_e * cap + rank, e_loc * cap)
        tok_of = jnp.arange(t_loc * k, dtype=jnp.int32) // k

        buf = jnp.zeros((e_loc * cap + 1, d), xt.dtype)
        buf = buf.at[dest].add(jnp.take(xt, tok_of, axis=0))
        buf = buf[:-1].reshape(e_loc, cap, d)

        h = _act(cfg)(jnp.einsum("ecd,edf->ecf", buf, wi_gate))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wi_up)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo)

        flat_out = jnp.concatenate(
            [out_buf.reshape(e_loc * cap, d), jnp.zeros((1, d), out_buf.dtype)],
            axis=0)
        y_assign = jnp.take(flat_out, dest, axis=0)
        y = jnp.sum(y_assign.reshape(t_loc, k, d)
                    * gates.astype(y_assign.dtype)[..., None], axis=1)
        y = y.astype(x_loc.dtype)
        if shared is not None:
            # ff-sharded shared expert: partial [t, d] joins the same psum
            hs = _act(cfg)(jnp.einsum("td,df->tf", xt, sh_gate))
            hs = hs * jnp.einsum("td,df->tf", xt, sh_up)
            y = y + jnp.einsum("tf,fd->td", hs, sh_wo)
        # ONE all-reduce combines routed-expert and shared contributions;
        # wire format stays in the compute dtype (fp32 promotion from the
        # gates would double the bytes)
        y = lax.psum(y, "model")
        return y.reshape(bl, sl, d)

    return ep_block(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"],
                    *sh_args)
