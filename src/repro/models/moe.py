"""Mixture-of-Experts FFN with capacity-based sorted dispatch (EP-shardable).

Dispatch is the sort-and-segment pattern (no [T,E,C] one-hot tensors):
assignments are argsorted by expert, ranked within expert, capacity-dropped,
scattered into an [E, C, d] buffer, run through a grouped SwiGLU einsum
(the leading E axis shards over the `model`/EP mesh axis → the all-to-alls
GSPMD inserts around the scatter/gather ARE the MoE dispatch collectives),
and combined back with router gates.

EBG hook (beyond-paper, DESIGN.md §4): `expert_permutation` from
repro.core.placement reorders expert ids before sharding so that hot
(co-activated) experts land on different devices — the paper's balance
objective applied to the token→expert routing graph.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import pspec
from repro.models.config import ModelConfig


def moe_ffn(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # [B, S, d]
    *,
    expert_perm: Optional[jax.Array] = None,
) -> jax.Array:
    m = cfg.moe
    capacity_factor = m.capacity_factor
    B, S, d = x.shape
    T = B * S
    E, k = m.num_experts, m.top_k
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf, p["router"].astype(x.dtype)).astype(jnp.float32)
    gate_logits, expert_idx = jax.lax.top_k(logits, k)  # [T, k]
    gates = jax.nn.softmax(gate_logits, axis=-1)
    if expert_perm is not None:  # EBG placement: reorder expert ids
        expert_idx = expert_perm[expert_idx]

    cap = int(T * k / E * capacity_factor)
    cap = max(8, -(-cap // 8) * 8)

    flat_e = expert_idx.reshape(-1)  # [T*k]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank = jnp.arange(T * k) - starts[sorted_e]
    keep = rank < cap
    token_of = order // k

    safe_rank = jnp.where(keep, rank, 0)
    buf = jnp.zeros((E, cap, d), x.dtype)
    buf = buf.at[sorted_e, safe_rank].add(
        jnp.where(keep[:, None], xf[token_of], 0).astype(x.dtype)
    )
    buf = pspec.constrain(buf, "tp", None, None)  # EP: experts over model axis

    # Grouped expert SwiGLU — leading E axis is the EP shard axis.
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["w_in"]
    )
    out = jnp.einsum("ecf,efd->ecd", h, p["w_out"])

    contrib = out[sorted_e, safe_rank]  # [T*k, d]
    gate_sorted = gates.reshape(-1)[order]
    contrib = jnp.where(keep[:, None], contrib * gate_sorted[:, None].astype(x.dtype), 0)
    y = jnp.zeros((T, d), x.dtype).at[token_of].add(contrib)
    y = pspec.constrain(y, "dp", None)
    return y.reshape(B, S, d)


def _moe_body(cfg: ModelConfig, xb, router, wg, wi, wo, *, tp_axis: str):
    """Per-EP-shard MoE: tokens are model-replicated, so each shard gathers
    ITS experts' tokens locally (no dispatch collective at all) and the
    combine is one psum of [T_loc, d] partial outputs — ~E·C·d/(T·d) times
    fewer bytes than GSPMD's full-buffer all-reduce."""
    m = cfg.moe
    E, k = m.num_experts, m.top_k
    E_loc = wg.shape[0]
    j = jax.lax.axis_index(tp_axis)
    Tl, d = xb.shape

    logits = jnp.einsum("td,de->te", xb, router.astype(xb.dtype)).astype(jnp.float32)
    gate_logits, expert_idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(gate_logits, axis=-1)

    cap = int(Tl * k / E * m.capacity_factor)
    cap = max(8, -(-cap // 8) * 8)

    flat_e = expert_idx.reshape(-1) - j * E_loc  # local expert ids
    mine = (flat_e >= 0) & (flat_e < E_loc)
    sort_key = jnp.where(mine, flat_e, E_loc)  # foreign → dump bucket
    order = jnp.argsort(sort_key)
    sorted_e = sort_key[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E_loc), side="left")
    safe_e = jnp.clip(sorted_e, 0, E_loc - 1)
    rank = jnp.arange(Tl * k) - starts[safe_e]
    keep = (sorted_e < E_loc) & (rank >= 0) & (rank < cap)
    token_of = order // k
    safe_rank = jnp.where(keep, rank, 0)

    buf = jnp.zeros((E_loc, cap, d), xb.dtype)
    buf = buf.at[safe_e, safe_rank].add(
        jnp.where(keep[:, None], xb[token_of], 0).astype(xb.dtype)
    )
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf, wi
    )
    out = jnp.einsum("ecf,efd->ecd", h, wo)

    contrib = out[safe_e, safe_rank]
    gate_sorted = gates.reshape(-1)[order]
    contrib = jnp.where(keep[:, None], contrib * gate_sorted[:, None].astype(xb.dtype), 0)
    y = jnp.zeros((Tl, d), xb.dtype).at[token_of].add(contrib)
    return jax.lax.psum(y, tp_axis)


def moe_ffn_ep(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """shard_map EP dispatch (plan `ep`); falls back to moe_ffn off-mesh."""
    from jax.sharding import PartitionSpec as P

    ctx = pspec.ep_shard_map()
    if ctx is None:
        return moe_ffn(cfg, p, x)
    mesh, dp, tp = ctx
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    body = lambda xb, router, wg, wi, wo: _moe_body(
        cfg, xb, router, wg, wi, wo, tp_axis=tp
    )
    y = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None), P(None, None), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=P(dp, None),
        check_vma=False,
    )(xf, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    return y.reshape(B, S, d)


def aux_load_balance_loss(logits: jax.Array, expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E[fraction routed] x E[router prob]."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], num_experts, dtype=jnp.float32), axis=0
    )
    return num_experts * jnp.sum(frac * probs.mean(axis=0))


def init_moe(cfg: ModelConfig, key, dtype) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff, m.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(ks[0], (d, E)) * d ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (E, d, f)) * d ** -0.5).astype(dtype),
        "w_in": (jax.random.normal(ks[2], (E, d, f)) * d ** -0.5).astype(dtype),
        "w_out": (jax.random.normal(ks[3], (E, f, d)) * f ** -0.5).astype(dtype),
    }
