"""Shard-lane seal kernel: K shards' segmented xor-fold digests at once.

The fused fabric loop (core/fused.py over core/shards.ShardedRollup)
precomputes every shard lane's seal structure, then needs each lane's
per-batch tx roots and per-window update digests — K independent
``batch_seal``-style segmented folds.  This module is the dedicated
multi-lane kernel (kernels/factory.py op ``"shard_seal"``): the K lanes
become the rows of one ``(K, W)`` SoA word grid, and ONE call folds
every lane's segments:

  * ``digest.shard_seal_np`` — the bit-exact NumPy mirror (per-row
    ``reduceat``, THE semantics);
  * ``shard_seal_jax``       — one jitted program: a 2-D prefix-xor
    ``associative_scan`` over the row axis-1, segment digests by prefix
    difference;
  * ``shard_seal_shard_map`` — the same fold ``shard_map``-ped over a
    1-D ``"shard"`` mesh axis (launch/mesh.make_shard_mesh +
    sharding/specs.shard_lane_spec): each device owns a contiguous row
    block of lanes, the SoA starts grid is donated (it shares the
    output's byte layout, so XLA folds in place), and rows pad to the
    mesh size with empty lanes.  This is the shape real parallel shard
    execution takes — per-lane work with no cross-lane traffic until
    the fabric root merge (modeled by core/interconnect.py).

Call contract (shared by all impls, pinned bit-exact by
tests/test_shard_lanes.py on the CI ``kernel-parity`` + ``shard-mesh``
matrices):

    shard_seal(words, starts, n_seg, n_words) -> (K, B) uint32

  * ``words``   (K, W) u32 — row ``k``'s word buffer in its first
    ``n_words[k]`` columns, zero-padded after (zero words mix to zero
    and fold away — the ``batch_seal_pallas`` padding contract);
  * ``starts``  (K, B) int — row ``k``'s segment starts in its first
    ``n_seg[k]`` columns, strictly increasing and ``< n_words[k]``
    (segments are non-empty); padded columns MUST hold ``n_words[k]``;
  * output row ``k``: the segment digests in the first ``n_seg[k]``
    columns; every padded column holds ``MIX_SEED`` (the fold of an
    empty segment).  Real segments reproduce
    ``engine.xor_fold_digest_segments`` bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rollup_digest import mix, seed


def _pow2(n: int, floor: int) -> int:
    """Smallest power of two >= max(n, floor) — jit-cache bucketing."""
    return 1 << max(n - 1, floor - 1, 1).bit_length()


# -- one jitted 2-D prefix-xor program ----------------------------------------
def _lane_fold(words, starts):
    """(K, W) u32 x (K, B) i32 -> (K, B) u32 — prefix-xor per row,
    segment digests by prefix difference.  Padded starts (== n_words)
    yield MIX_SEED because their lead and last prefixes coincide."""
    prefix = jax.lax.associative_scan(jnp.bitwise_xor, mix(words), axis=1)
    w = words.shape[1]
    # starts arrive as u32 (same element type as the output, so the
    # donated grid aliases it); index via i32 views — shapes are tiny
    ends = jnp.concatenate(
        [starts[:, 1:], jnp.full((starts.shape[0], 1), w, starts.dtype)],
        axis=1).astype(jnp.int32)
    s32 = starts.astype(jnp.int32)
    last = jnp.where(ends > 0, jnp.take_along_axis(
        prefix, jnp.maximum(ends - 1, 0), axis=1), jnp.uint32(0))
    lead = jnp.where(s32 > 0, jnp.take_along_axis(
        prefix, jnp.maximum(s32 - 1, 0), axis=1), jnp.uint32(0))
    return seed(last ^ lead)


# donate the starts grid: it is (K, B) i32 — the same byte layout as
# the (K, B) u32 output, so XLA reuses it in place (the larger word
# grid can never alias the output and is left alone)
@functools.partial(jax.jit, donate_argnums=(1,))
def _lane_fold_jit(words, starts):
    return _lane_fold(words, starts)


def _padded(words, starts, n_words):
    """Bucket (K, W)/(K, B) to power-of-two shapes, preserving the call
    contract: words pad with zeros, starts pad with each row's n_words."""
    K, W = words.shape
    B = starts.shape[1]
    Wp, Bp = _pow2(W, 128), _pow2(B, 8)
    wp = np.zeros((K, Wp), np.uint32)
    wp[:, :W] = words
    sp = np.repeat(np.asarray(n_words, np.uint32)[:, None], Bp, axis=1)
    sp[:, :B] = starts
    return wp, sp


def shard_seal_jax(words: np.ndarray, starts: np.ndarray,
                   n_seg: np.ndarray, n_words: np.ndarray) -> np.ndarray:
    """One compiled program for all K lanes (shapes bucketed to powers
    of two so the jit cache holds one entry per bucket; the starts grid
    is donated — it is consumed)."""
    B = starts.shape[1]
    wp, sp = _padded(np.asarray(words, np.uint32),
                     np.asarray(starts), n_words)
    out = _lane_fold_jit(jnp.asarray(wp), jnp.asarray(sp))
    return np.asarray(out)[:, :B]


# -- the same fold over a 1-D "shard" mesh ------------------------------------
@functools.lru_cache(maxsize=None)
def lane_fold_mapped(mesh):
    """shard_map the fold over the mesh's "shard" axis: each device owns
    a contiguous block of lane rows; no cross-device collectives — the
    fabric-root merge is the only cross-lane step, and it happens on the
    host (with its wire cost modeled by core/interconnect.py)."""
    from repro.sharding.specs import shard_lane_spec
    spec = shard_lane_spec()
    fn = jax.shard_map(_lane_fold, mesh=mesh,
                       in_specs=(spec, spec), out_specs=spec)
    return jax.jit(fn, donate_argnums=(1,))


def shard_seal_shard_map(words: np.ndarray, starts: np.ndarray,
                         n_seg: np.ndarray, n_words: np.ndarray, *,
                         mesh=None) -> np.ndarray:
    """Mesh-mapped impl: lane rows pad to a multiple of the mesh size
    with empty lanes (n_words=0 -> a row of MIX_SEED, sliced off)."""
    from repro.launch.mesh import make_shard_mesh
    if mesh is None:
        mesh = make_shard_mesh()
    d = int(np.prod(list(mesh.shape.values())))
    K, B = starts.shape
    wp, sp = _padded(np.asarray(words, np.uint32),
                     np.asarray(starts), n_words)
    kp = -(-K // d) * d
    if kp != K:
        wp = np.concatenate([wp, np.zeros((kp - K, wp.shape[1]),
                                          np.uint32)])
        sp = np.concatenate([sp, np.zeros((kp - K, sp.shape[1]),
                                          sp.dtype)])
    out = lane_fold_mapped(mesh)(jnp.asarray(wp), jnp.asarray(sp))
    return np.asarray(out)[:K, :B]
