"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.digest import MIX_MULT, MIX_SEED


def weighted_agg_ref(stacked: jnp.ndarray, scores: jnp.ndarray) -> jnp.ndarray:
    """Eq. 1: (n, P), (n,) -> (P,) score-weighted average, f32 accumulation."""
    s = scores.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(s), 1e-12)
    return (jnp.einsum("np,n->p", stacked.astype(jnp.float32), s)
            / denom).astype(stacked.dtype)


def model_distance_ref(local: jnp.ndarray, global_: jnp.ndarray) -> jnp.ndarray:
    """Eq. 4: (n, P), (P,) -> (n,) L2 distances, f32 accumulation."""
    d = local.astype(jnp.float32) - global_.astype(jnp.float32)[None]
    return jnp.sqrt(jnp.sum(d * d, axis=-1))


def flash_attention_ref(q, k, v, causal: bool = True):
    """(B, S, H, dh), (B, S, Hkv, dh) x2 -> (B, S, H, dh), GQA via repeat."""
    B, S, H, dh = q.shape
    n_rep = H // k.shape[2]
    k = jnp.repeat(k, n_rep, axis=2)
    v = jnp.repeat(v, n_rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def gmm_ref(xe: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Expert-grouped matmul: (E, C, d) x (E, d, f) -> (E, C, f)."""
    return jnp.einsum("ecd,edf->ecf", xe.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(xe.dtype)


def rollup_digest_ref(buf_u32: jnp.ndarray) -> jnp.ndarray:
    """Chunked XOR-mix fold over a u32 buffer -> scalar u32."""
    mixed = jnp.bitwise_xor(buf_u32, buf_u32 >> 16) * jnp.uint32(MIX_MULT)
    out = jnp.uint32(MIX_SEED)
    return out ^ jax.lax.reduce(mixed, jnp.uint32(0), jnp.bitwise_xor, (0,))
