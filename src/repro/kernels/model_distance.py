"""Pallas TPU kernel: per-trainer model distance (paper Eq. 4).

    D[i] = || w_local[i, :] - w_global[:] ||_2

Fused subtract-square-reduce over parameter tiles; per-trainer partial sums
accumulate in the output block across the (arbitrary-order) parameter grid
axis, initialised at the first step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(l_ref, g_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d = l_ref[...].astype(jnp.float32) - g_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.sum(d * d, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def model_distance(local: jnp.ndarray, global_: jnp.ndarray,
                   block_p: int = 4096, interpret: bool = False):
    """local: (n, P); global_: (P,) -> (n,) L2 distances."""
    n, P = local.shape
    pad = (-P) % block_p
    if pad:
        local = jnp.pad(local, ((0, 0), (0, pad)))
        global_ = jnp.pad(global_, (0, pad))
    Pp = P + pad
    g2 = global_.reshape(1, Pp)

    sq = pl.pallas_call(
        _kernel,
        grid=(Pp // block_p,),
        in_specs=[
            pl.BlockSpec((n, block_p), lambda i: (0, i)),
            pl.BlockSpec((1, block_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        name="model_distance",
    )(local, g2)
    return jnp.sqrt(sq[:, 0])
