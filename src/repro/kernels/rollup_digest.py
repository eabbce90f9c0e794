"""Pallas TPU kernel: rollup validity digest (chunked XOR-mix fold).

The 'prove' stand-in of the rollup commit (see core/rollup.py): a
deterministic integrity digest over the merged update buffer, computed
in-line with aggregation so the commit adds no extra HBM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.digest import MIX_MULT, MIX_SEED

LANES = 128
SUBLANES = 8


def mix(x):
    """``kernels.digest.mix`` on u32 device arrays (and in kernels)."""
    return jnp.bitwise_xor(x, x >> 16) * jnp.uint32(MIX_MULT)


def seed(fold):
    """A xor fold of mixed words -> its digest."""
    return jnp.uint32(MIX_SEED) ^ fold


def _fold_lane_tiles(x_ref):
    """xor of the ``(8, 128)`` lane tiles of a ``(8, W)`` block ->
    ``(8, 128)``.  Static lane-aligned slices only: Mosaic has no
    ``reduce`` lowering for xor, and whole-tile xors stay on the VPU."""
    acc = mix(x_ref[:, pl.ds(0, LANES)])
    for j in range(1, x_ref.shape[1] // LANES):
        acc = acc ^ mix(x_ref[:, pl.ds(j * LANES, LANES)])
    return acc


def row_fold_call(rows2d: jnp.ndarray, *, name: str, interpret: bool,
                  block_w: int | None = None) -> jnp.ndarray:
    """Per-row xor-mix digests of a ``(R, W)`` u32 grid: ``(R,)`` u32.

    THE Pallas layout every ledger fold shares (chunk digests, batch
    seals, dirty chunks): one row per digest on the sublane axis, its
    words along the lanes.  Rows pad to a multiple of 8 (zero rows,
    sliced off) and each grid step folds 8 rows into one ``(8, 128)``
    output tile, so every block is ``(8, 128)``-aligned.  ``block_w``
    (a multiple of 128 dividing ``W``) tiles long rows over a second
    grid axis that accumulates into the resident output tile, bounding
    fast memory by the block and not by the longest row."""
    r, w = rows2d.shape
    assert w % LANES == 0, "row width must be lane-aligned"
    bw = w if block_w is None else block_w
    assert w % bw == 0 and bw % LANES == 0
    rp = -(-r // SUBLANES) * SUBLANES
    if rp != r:
        rows2d = jnp.pad(rows2d, ((0, rp - r), (0, 0)))

    def kernel(x_ref, o_ref):
        part = _fold_lane_tiles(x_ref)
        if bw == w:
            o_ref[...] = part
            return

        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] = o_ref[...] ^ part

    out = pl.pallas_call(
        kernel,
        grid=(rp // SUBLANES, w // bw),
        in_specs=[pl.BlockSpec((SUBLANES, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rp, LANES), jnp.uint32),
        interpret=interpret,
        name=name,
    )(rows2d)
    # the last 128-lane fold + seed runs in XLA on the small partials
    return seed(jax.lax.reduce(out, jnp.uint32(0), jnp.bitwise_xor,
                               (1,)))[:r]


def _digest_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)   # seed applied in the wrapper

    # fold the block's (8, 128) row tiles into the resident out tile
    acc = o_ref[...]
    for j in range(x_ref.shape[0] // SUBLANES):
        acc = acc ^ mix(x_ref[pl.ds(j * SUBLANES, SUBLANES), :])
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def rollup_digest(buf: jnp.ndarray, block_p: int = 16384,
                  interpret: bool = False) -> jnp.ndarray:
    """buf: (P,) float32/uint32 buffer -> scalar u32 digest.  ``block_p``
    must hold whole ``(8, 128)`` tiles (% 1024 == 0)."""
    assert block_p % (SUBLANES * LANES) == 0, "block must be tile-aligned"
    if buf.dtype != jnp.uint32:
        buf = jax.lax.bitcast_convert_type(buf.astype(jnp.float32), jnp.uint32)
    P = buf.shape[0]
    # an empty buffer folds one zero block: the bare seed, as the mirror
    pad = (-P) % block_p if P else block_p
    if pad:
        buf = jnp.pad(buf, (0, pad))
    rows = (P + pad) // LANES
    block_r = block_p // LANES

    out = pl.pallas_call(
        _digest_kernel,
        grid=(rows // block_r,),
        in_specs=[pl.BlockSpec((block_r, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.uint32),
        interpret=interpret,
        name="rollup_digest",
    )(buf.reshape(rows, LANES))
    # final (8, 128) fold + seed in XLA (tiny); seeding here keeps the
    # lane-broadcast in the kernel from cancelling it (even lane count)
    return seed(jax.lax.reduce(out.reshape(-1), jnp.uint32(0),
                               jnp.bitwise_xor, (0,)))


@functools.partial(jax.jit, static_argnames=("chunk_p", "interpret"))
def rollup_chunk_digests(buf: jnp.ndarray, chunk_p: int = 2048,
                         interpret: bool = False) -> jnp.ndarray:
    """Per-chunk digests for the chunked state commitment (core/state.py).

    buf: (P,) float32/uint32 buffer -> (ceil(P/chunk_p),) u32, one xor-mix
    fold per ``chunk_p``-word chunk (zero-padded tail; zero words fold
    away).  ``kernels.digest.chunk_fold_digests`` is the bit-exact NumPy
    mirror, pinned by tests/test_state.py.  chunk_p must be lane-aligned
    (% 128) so each chunk maps to whole VPU rows.
    """
    assert chunk_p % LANES == 0, "chunk must be lane-aligned"
    if buf.dtype != jnp.uint32:
        buf = jax.lax.bitcast_convert_type(buf.astype(jnp.float32), jnp.uint32)
    P = buf.shape[0]
    assert P > 0, "empty buffer has no chunks"
    pad = (-P) % chunk_p
    if pad:
        buf = jnp.pad(buf, (0, pad))
    return row_fold_call(buf.reshape(-1, chunk_p), name="chunk_digests",
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("width",))
def rollup_aggregate_digests(digests: jnp.ndarray,
                             width: int) -> jnp.ndarray:
    """Recursive proof aggregation: (n,) u32 digests -> (ceil(n/width),)
    u32 aggregate digests.

    The prover pipeline's aggregation stage (core/prover.py) applies the
    SAME xor-mix fold the batch digests were built with, one level up:
    batch tx words -> batch digest -> session proof -> aggregate proof.
    The digest vector is tiny (one word per proof), so this is a plain
    jitted VPU fold rather than a pallas_call; ``kernels.digest.
    chunk_fold_digests(digests, chunk=width)`` is the bit-exact NumPy
    mirror (pinned by tests/test_prover.py).  Zero padding folds away
    (zero words mix to zero), matching the chunk kernel's padded tail.
    """
    d = jnp.asarray(digests, jnp.uint32)
    pad = (-d.shape[0]) % width
    if pad:
        d = jnp.pad(d, (0, pad))
    return seed(jax.lax.reduce(mix(d.reshape(-1, width)), jnp.uint32(0),
                               jnp.bitwise_xor, (1,)))
