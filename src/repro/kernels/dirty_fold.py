"""Dirty-chunk refold kernel: per-chunk xor-mix digests of SELECTED chunks.

The chunked state commitment (core/state.py) folds the whole u32 word
buffer every window — O(state) even when a window touched a handful of
account rows.  ``StateArrays`` now caches the per-chunk digest vector and
only the chunks covering dirty rows are refolded before the sha256 seal;
this module is that refold: given the (patched) word buffer and the ids of
the dirty chunks, return one xor-mix digest per dirty chunk.

``dirty_fold_np`` is the bit-exact NumPy mirror — by construction it is
``core.state.chunk_fold_digests(words, chunk)[chunk_ids]``, so the
incremental root is pinned against the full refold (tests/test_state.py)
and every impl here is pinned against the mirror (tests/test_kernels.py).
All arithmetic is u32 (mix + xor), so bit-exactness cannot depend on
JAX_ENABLE_X64 — no pair encoding needed.

Registered with ``kernels.factory`` under op ``"dirty_fold"``:

  * ``numpy``  — reshape + reduce over the selected rows (CPU default:
    a window dirties few chunks, and dispatch overhead beats XLA there);
  * ``jax``    — ONE jitted gather-fold (shapes bucketed to powers of two
    so the jit cache holds one entry per bucket);
  * ``pallas`` — grid over dirty chunks, each step folds 8 lane-aligned
    chunk rows (TPU default; ``interpret=True`` off-TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rollup_digest import row_fold_call

MIX_MULT = np.uint32(0x85EBCA6B)
MIX_SEED = np.uint32(0x9E3779B9)


def _padded(words: np.ndarray, chunk: int) -> np.ndarray:
    w = np.ascontiguousarray(words, dtype=np.uint32)
    pad = (-w.size) % chunk
    if pad:
        w = np.concatenate([w, np.zeros(pad, np.uint32)])
    return w


def _bucket(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))


# -- NumPy mirror (THE reference semantics) ---------------------------------

def dirty_fold_np(words: np.ndarray, chunk_ids: np.ndarray,
                  chunk: int) -> np.ndarray:
    """Digests of the selected chunks: (P,) u32 words + (D,) chunk ids ->
    (D,) u32, where row d is ``MIX_SEED ^ xor-fold(mix(chunk chunk_ids[d]))``
    — exactly ``chunk_fold_digests(words, chunk)[chunk_ids]`` without
    folding the untouched chunks.  Zero padding folds away (zero words mix
    to zero), matching the full fold's padded tail."""
    ids = np.asarray(chunk_ids, np.int64)
    if ids.size == 0:
        return np.zeros(0, np.uint32)
    rows = _padded(words, chunk).reshape(-1, chunk)[ids]
    mixed = (rows ^ (rows >> np.uint32(16))) * MIX_MULT
    return MIX_SEED ^ np.bitwise_xor.reduce(mixed, axis=1)


# -- jax impl: one jitted gather-fold ---------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def _gather_fold(words2d, ids, chunk: int):
    rows = words2d[ids]                              # (Db, chunk) gather
    mixed = (rows ^ (rows >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
    return jnp.uint32(0x9E3779B9) ^ jax.lax.reduce(
        mixed, jnp.uint32(0), jnp.bitwise_xor, (1,))


def _bucket_ids(ids: np.ndarray) -> np.ndarray:
    """Pad the dirty-id vector to its pow2 bucket (pad ids point at chunk
    0 — their folds are computed and dropped)."""
    db = _bucket(ids.size)
    out = np.zeros(db, np.int64)
    out[: ids.size] = ids
    return out


def dirty_fold_jax(words: np.ndarray, chunk_ids: np.ndarray,
                   chunk: int) -> np.ndarray:
    """XLA impl: one jitted gather + row fold; both the chunk-count and
    the dirty-count axes are bucketed to powers of two so the jit cache
    holds one entry per bucket, not one per state size."""
    ids = np.asarray(chunk_ids, np.int64)
    if ids.size == 0:
        return np.zeros(0, np.uint32)
    w = _padded(words, chunk)
    n_chunks = w.size // chunk
    cb = _bucket(n_chunks, floor=1)
    if cb > n_chunks:                   # zero rows fold to MIX_SEED, unused
        w = np.concatenate([w, np.zeros((cb - n_chunks) * chunk, np.uint32)])
    out = _gather_fold(jnp.asarray(w.reshape(-1, chunk)),
                       jnp.asarray(_bucket_ids(ids)), chunk)
    return np.asarray(out, np.uint32)[: ids.size]


# -- Pallas impl: grid over dirty chunks ------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def _fold_pallas_call(rows2d, interpret: bool):
    return row_fold_call(rows2d, name="dirty_fold", interpret=interpret)


def dirty_fold_pallas(words: np.ndarray, chunk_ids: np.ndarray, chunk: int,
                      *, interpret: bool | None = None) -> np.ndarray:
    """Pallas impl: the device gathers the dirty chunk rows, then each
    grid step folds 8 of them (``rollup_digest.row_fold_call``; the
    pow2 id bucket is >= 8, so the rows are tile-aligned).  ``chunk``
    must be lane-aligned (% 128 == 0) — ``STATE_CHUNK_WORDS`` is."""
    assert chunk % 128 == 0, "chunk must be lane-aligned"
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    ids = np.asarray(chunk_ids, np.int64)
    if ids.size == 0:
        return np.zeros(0, np.uint32)
    w = _padded(words, chunk)
    ids_b = _bucket_ids(ids)
    rows = jnp.asarray(w.reshape(-1, chunk))[jnp.asarray(ids_b)]
    out = _fold_pallas_call(rows, bool(interpret))
    return np.asarray(out, np.uint32)[: ids.size]
