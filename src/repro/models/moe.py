"""Mixture-of-Experts FFN: top-k routing + capacity-based grouped matmul.

TPU adaptation notes:
  * Dispatch is per batch row (tokens only move within their own row), so the
    gather stays local to each data shard.
  * Dispatch indices are materialised as (B, E, C) and sharded E over the
    `model` axis (expert parallelism): each chip gathers only its experts'
    tokens, runs a grouped matmul against its expert shard, and the combine
    scatter-add is reduced over the model axis by GSPMD (one per-layer
    all-reduce, same as the TP attention output reduction).
  * FLOPs are honest: E*C = S*top_k*cf, so compiled compute is
    ~capacity_factor x the active-param ideal (no dense-all-experts waste).
  * The Pallas `gmm` kernel (kernels/gmm.py) is the sorted-token
    megablox-style form of this matmul; no model path calls it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


def init_moe_params(key, cfg, dtype):
    m = cfg.moe
    d, ff, E = cfg.d_model, m.expert_d_ff, m.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "moe_wg": dense_init(ks[1], (E, d, ff), dtype),
        "moe_wu": dense_init(ks[2], (E, d, ff), dtype),
        "moe_wo": dense_init(ks[3], (E, ff, d), dtype),
    }


def capacity(cfg, seq_len: int) -> int:
    m = cfg.moe
    c = int(seq_len * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to lanes


def route_topk(router_logits, top_k):
    """router_logits: (..., E) -> (weights (..., k), idx (..., k) int32)."""
    gates = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(gates, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32)


def build_dispatch(idx, w, n_experts: int, cap: int):
    """Per-row dispatch tables.

    idx, w: (S, k).  Returns (slot_token (E, C) int32 token ids,
    slot_weight (E, C) f32, token->slot validity folded into slot_weight).
    Overflowing tokens (beyond capacity) are dropped (capacity-factor path).
    """
    S, k = idx.shape
    flat_expert = idx.reshape(-1)                       # (S*k,)
    flat_token = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)
    flat_w = w.reshape(-1).astype(jnp.float32)

    # position of each (token, expert) pair within its expert's queue
    order = jnp.argsort(flat_expert, stable=True)       # group by expert
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_w = flat_w[order]
    # rank within group = position - first position of the group
    positions = jnp.arange(S * k, dtype=jnp.int32)
    seg_start = jnp.full((n_experts,), S * k, jnp.int32).at[sorted_expert].min(
        positions, mode="drop")
    rank = positions - seg_start[sorted_expert]

    keep = rank < cap
    slot = sorted_expert * cap + jnp.where(keep, rank, cap * n_experts)
    slot_token = jnp.full((n_experts * cap + 1,), 0, jnp.int32).at[slot].set(
        sorted_token, mode="drop")
    slot_w = jnp.zeros((n_experts * cap + 1,), jnp.float32).at[slot].set(
        jnp.where(keep, sorted_w, 0.0), mode="drop")
    return (slot_token[:-1].reshape(n_experts, cap),
            slot_w[:-1].reshape(n_experts, cap))


def moe_ffn(cfg, p, x, ctx=None):
    """x: (B, S, d) -> (B, S, d)."""
    m = cfg.moe
    B, S, d = x.shape
    E, cap = m.n_experts, capacity(cfg, S)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    # NOTE: constraining logits to (dp,None,None) here was tried and REFUTED
    # (kimi train collective 54.4->58.8 s): the batch-gather it removes is
    # cheaper than the extra reshards it forces around top_k/dispatch.
    # See EXPERIMENTS.md §Perf iteration A3.
    w, idx = route_topk(logits, m.top_k)                  # (B,S,k)

    slot_token, slot_w = jax.vmap(
        lambda i, ww: build_dispatch(i, ww, E, cap))(idx, w)   # (B,E,C)
    if ctx is not None:
        dp = ctx.dp_axes or None
        slot_token = ctx.constrain(slot_token, P(dp, ctx.ep_axis, None))
        slot_w = ctx.constrain(slot_w, P(dp, ctx.ep_axis, None))

    # gather tokens into expert slots: (B, E, C, d)
    xe = jnp.take_along_axis(
        x[:, None, :, :],                                  # (B,1,S,d)
        slot_token[..., None].astype(jnp.int32),           # (B,E,C,1)
        axis=2)
    if ctx is not None:
        xe = ctx.constrain(xe, P(ctx.dp_axes or None, ctx.ep_axis, None, None))

    h = jnp.einsum("becd,edf->becf", xe, p["moe_wg"])
    u = jnp.einsum("becd,edf->becf", xe, p["moe_wu"])
    h = jax.nn.silu(h) * u
    ye = jnp.einsum("becf,efd->becd", h, p["moe_wo"])      # (B,E,C,d)
    ye = ye * slot_w[..., None].astype(ye.dtype)

    # combine: scatter-add back to token positions (B, S, d).  Keep the
    # cross-expert reduction payload in bf16: the psum over the model axis
    # otherwise travels in f32 (measured 51 GB/chip on moonshot train_4k).
    ye = ye.astype(x.dtype)
    def combine_row(y_row, tok_row):
        flat_y = y_row.reshape(E * cap, d)
        flat_t = tok_row.reshape(E * cap)
        return jnp.zeros((S, d), flat_y.dtype).at[flat_t].add(flat_y)
    y = jax.vmap(combine_row)(ye, slot_token)
    if ctx is not None:
        y = ctx.act_btd(y)
    return y.astype(x.dtype)


def moe_ffn_single(cfg, p, x, ctx=None):
    """Decode-time MoE for (B, 1, d) — reuse the dispatch path with the batch
    acting as the token row: (B, 1, d) -> (1, B, d).  Weight reads amortise
    over the whole decode batch (a batched-serving essential for MoE)."""
    B = x.shape[0]
    y = moe_ffn(cfg, p, x.reshape(1, B, -1), ctx=None)
    return y.reshape(B, 1, -1)
