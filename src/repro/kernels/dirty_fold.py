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
  * ``jax``    — ONE jitted patch-and-fold program (below);
  * ``pallas`` — the same program, its fold a grid over dirty chunks,
    each step folding 8 lane-aligned chunk rows (TPU default;
    ``interpret=True`` off-TPU).

The device impls keep the buffer on the chip.  Each commit cache owns a
``Resident`` holder; a call hands it in with the word indices its row
patch touched, and the program scatters just those words into the
resident copy (donated, so in place), gathers the dirty chunks and
folds them.  The whole buffer crosses to the device only when the
holder has no copy of its size: once per cache, counted in
``kernel.uploads.dirty_fold``.  A call without a holder uploads the
whole buffer for itself.  The impls count what they stage in
``kernel.h2d_bytes.dirty_fold``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.rollup_digest import LANES, row_fold_call

#: counters: full-buffer uploads into a holder, and every byte staged
UPLOADS = "kernel.uploads.dirty_fold"
H2D = "kernel.h2d_bytes.dirty_fold"

MIX_MULT = np.uint32(0x85EBCA6B)
MIX_SEED = np.uint32(0x9E3779B9)


def _padded(words: np.ndarray, chunk: int) -> np.ndarray:
    w = np.ascontiguousarray(words, dtype=np.uint32)
    pad = (-w.size) % chunk
    if pad:
        w = np.concatenate([w, np.zeros(pad, np.uint32)])
    return w


def _bucket(n: int) -> int:
    return max(8, 1 << max(0, (int(n) - 1).bit_length()))


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


# -- device impls: one patch-and-fold program over a resident buffer ------

class Resident:
    """The device copy of one commit cache's word buffer.

    ``lanes`` is the chunk-padded buffer as ``(n_words // 128, 128)`` u32
    rows on the device, or ``None`` before the first upload: lane rows,
    because a flat word index scatters into them in place, and a chunk
    is ``chunk // 128`` consecutive rows.  The host buffer stays the
    source of truth (the mirror and the sha256 seal read it); this copy
    changes only by the scatters ``dirty_fold`` makes of the words each
    call names as touched.  Each commit cache owns one, and it dies with
    the cache."""

    __slots__ = ("lanes",)

    def __init__(self):
        self.lanes = None


def upload(words: np.ndarray, chunk: int,
           resident: Resident | None = None) -> jax.Array:
    """Copy the whole chunk-padded buffer to the device as lane rows.
    Given a holder, the copy becomes its resident buffer and counts one
    ``kernel.uploads.dirty_fold`` and its bytes in
    ``kernel.h2d_bytes.dirty_fold``."""
    lanes = jnp.asarray(_padded(words, chunk).reshape(-1, LANES))
    if resident is not None:
        resident.lanes = lanes
        obs.count(UPLOADS)
        obs.count(H2D, lanes.nbytes)
    return lanes


@functools.partial(jax.jit, static_argnames=("chunk", "fold", "interpret"),
                   donate_argnums=(0,))
def _patch_fold(lanes, idx, vals, ids, chunk: int, fold: str,
                interpret: bool):
    """Scatter ``vals`` into the resident lane rows at the flat word
    indices ``idx`` (in place: the buffer is donated), then fold the
    chunks ``ids``: ``(new buffer, (len(ids),) digests)``.  ``idx`` is
    strictly increasing; its padding lies past the buffer and is
    dropped."""
    lanes = lanes.at[idx // LANES, idx % LANES].set(
        vals, mode="drop", indices_are_sorted=True, unique_indices=True)
    picked = lanes.reshape(-1, chunk // LANES, LANES)[ids]  # (Db, .., 128)
    if fold == "pallas":
        return lanes, row_fold_call(picked.reshape(-1, chunk),
                                    name="dirty_fold", interpret=interpret)
    mixed = (picked ^ (picked >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
    return lanes, jnp.uint32(0x9E3779B9) ^ jax.lax.reduce(
        mixed, jnp.uint32(0), jnp.bitwise_xor, (1, 2))


def _resident_fold(words, chunk_ids, chunk, resident, touched, fold,
                   interpret=False) -> np.ndarray:
    """Bring ``resident`` up to date with ``words`` and fold ``chunk_ids``
    on the device.  The whole buffer moves only when the holder has none
    of this size; otherwise only the ``touched`` words (their indices
    and values) and the ids are staged.  Both counts are bucketed to
    powers of two, so the jit cache holds one entry per bucket."""
    assert chunk % LANES == 0, "chunk must be lane-aligned"
    ids = np.asarray(chunk_ids, np.int64)
    touched = (np.zeros(0, np.int64) if touched is None
               else np.asarray(touched, np.int64))
    if ids.size == 0 and touched.size == 0:
        return np.zeros(0, np.uint32)
    if resident is None:
        resident = Resident()
    end = -(-len(words) // chunk) * chunk
    if resident.lanes is None or resident.lanes.size != end:
        upload(words, chunk, resident)
    tb = _bucket(touched.size)
    idx = np.arange(end, end + tb, dtype=np.int32)   # pads: past the end
    idx[: touched.size] = touched
    vals = np.zeros(tb, np.uint32)
    vals[: touched.size] = words[touched]
    ids_b = _bucket_ids(ids)
    obs.count(H2D, idx.nbytes + vals.nbytes + ids_b.nbytes)
    # the buffer is donated: a call that fails leaves no holder copy, so
    # the next call uploads afresh rather than reading a deleted array
    lanes, resident.lanes = resident.lanes, None
    resident.lanes, out = _patch_fold(lanes, idx, vals, ids_b, chunk=chunk,
                                      fold=fold, interpret=bool(interpret))
    return np.asarray(out, np.uint32)[: ids.size]


def _bucket_ids(ids: np.ndarray) -> np.ndarray:
    """Pad the dirty-id vector to its pow2 bucket (pad ids point at chunk
    0 — their folds are computed and dropped)."""
    db = _bucket(ids.size)
    out = np.zeros(db, np.int32)
    out[: ids.size] = ids
    return out


def dirty_fold_jax(words: np.ndarray, chunk_ids: np.ndarray, chunk: int,
                   *, resident: Resident | None = None,
                   touched: np.ndarray | None = None) -> np.ndarray:
    """XLA impl: one jitted program scatters the touched words into the
    resident buffer, gathers the dirty chunks and folds them.  Without a
    holder the whole buffer is uploaded for this call alone.  ``chunk``
    must be lane-aligned (% 128 == 0) — ``STATE_CHUNK_WORDS`` is."""
    return _resident_fold(words, chunk_ids, chunk, resident, touched, "jax")


def dirty_fold_pallas(words: np.ndarray, chunk_ids: np.ndarray, chunk: int,
                      *, resident: Resident | None = None,
                      touched: np.ndarray | None = None,
                      interpret: bool | None = None) -> np.ndarray:
    """Pallas impl: the same program as ``dirty_fold_jax``, with the
    gathered chunks folded 8 per grid step (``rollup_digest.row_fold_call``;
    the pow2 id bucket is >= 8, so the rows are tile-aligned)."""
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    return _resident_fold(words, chunk_ids, chunk, resident, touched,
                          "pallas", interpret)
