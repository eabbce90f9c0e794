"""Pallas TPU kernel: reputation-weighted aggregation (paper Eq. 1).

    out[p] = sum_n s[n] * w[n, p] / sum_n s[n]

The aggregation hot-spot of the paper's DON/aggregator role: n trainers'
model shards are folded in one pass.  Tiling: the parameter axis is split
into lane-aligned tiles resident in VMEM; the (small) trainer axis stays
whole so the weighted reduction is a single (1, n) x (n, Pt) MXU matvec per
tile with f32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128


def _kernel(s_ref, w_ref, denom_ref, o_ref):
    s = s_ref[...].astype(jnp.float32)           # (1, n)
    w = w_ref[...].astype(jnp.float32)           # (n, Pt)
    acc = jax.lax.dot_general(s, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (1, Pt)
    o_ref[...] = (acc / denom_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def weighted_agg(stacked: jnp.ndarray, scores: jnp.ndarray,
                 block_p: int = 4096, interpret: bool = False) -> jnp.ndarray:
    """stacked: (n, P) trainer weights; scores: (n,) -> (P,)."""
    n, P = stacked.shape
    pad = (-P) % block_p
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Pp = P + pad
    s2 = scores.astype(jnp.float32).reshape(1, n)
    denom = jnp.maximum(jnp.sum(s2), 1e-12).reshape(1, 1)

    out = pl.pallas_call(
        _kernel,
        grid=(Pp // block_p,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Pp), stacked.dtype),
        interpret=interpret,
        name="weighted_agg",
    )(s2, stacked, denom)
    return out[0, :P]
