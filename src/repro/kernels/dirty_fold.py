"""Dirty-chunk refold kernel: per-chunk xor-mix digests of SELECTED chunks.

The chunked state commitment (core/state.py) caches each commit cache's
per-chunk digest vector and refolds only the chunks covering rows a
window touched, before the sha256 seal.  This module is that refold on
the device (kernels/factory.py op ``"dirty_fold"``), and the full fold
that seeds a commit cache (``fold_all``).

The NumPy mirror is ``digest.dirty_fold_np``: by construction it is
``digest.chunk_fold_digests(words, chunk)[chunk_ids]``, so the
incremental root is pinned against the full refold (tests/test_state.py)
and the device impl against the mirror (tests/test_kernels.py).  All
arithmetic is u32 (mix + xor), so bit-exactness cannot depend on
JAX_ENABLE_X64.

The device impl (``dirty_fold_pallas``, the TPU's choice) keeps the
buffer on the chip.  Each commit cache owns a ``Resident`` holder; a
call hands it in with the word indices its row patch touched, and one
program scatters just those words into the resident copy (donated, so
in place), gathers the dirty chunks and folds them, 8 lane-aligned
chunk rows per grid step.  The whole buffer crosses to the device only
when the holder has no copy of its size: once per cache, by
``fold_all``, counted in ``kernel.uploads.dirty_fold``.  A call without
a holder uploads the whole buffer for itself.  The impl counts what it
stages in ``kernel.h2d_bytes.dirty_fold``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels.digest import chunk_fold_digests
from repro.kernels.factory import resolve_impl
from repro.kernels.rollup_digest import (LANES, rollup_chunk_digests,
                                         row_fold_call)

#: counters: full-buffer uploads into a holder, and every byte staged
UPLOADS = "kernel.uploads.dirty_fold"
H2D = "kernel.h2d_bytes.dirty_fold"


def _padded(words: np.ndarray, chunk: int) -> np.ndarray:
    w = np.ascontiguousarray(words, dtype=np.uint32)
    pad = (-w.size) % chunk
    if pad:
        w = np.concatenate([w, np.zeros(pad, np.uint32)])
    return w


def _bucket(n: int) -> int:
    return max(8, 1 << max(0, (int(n) - 1).bit_length()))


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


def fold_all(words: np.ndarray, chunk: int,
             resident: Resident | None = None) -> np.ndarray:
    """Every chunk's digest (a writable vector), on the side the factory
    picks for ``dirty_fold``, so both halves of the commitment run on
    the same side.  On the device the buffer's upload seeds
    ``resident``, so the refolds that follow stage only the words they
    touch.  A commit cache's set-up fold, outside the kernel spans."""
    if resolve_impl("dirty_fold") == "numpy" or not len(words):
        return chunk_fold_digests(words, chunk)
    from repro.kernels.ops import _interpret
    return np.array(rollup_chunk_digests(
        upload(words, chunk, resident).reshape(-1), chunk_p=chunk,
        interpret=_interpret()))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   donate_argnums=(0,))
def _patch_fold(lanes, idx, vals, ids, chunk: int, interpret: bool):
    """Scatter ``vals`` into the resident lane rows at the flat word
    indices ``idx`` (in place: the buffer is donated), then fold the
    chunks ``ids``: ``(new buffer, (len(ids),) digests)``.  ``idx`` is
    strictly increasing; its padding lies past the buffer and is
    dropped.  The pow2 id bucket is >= 8, so the rows are tile-aligned."""
    lanes = lanes.at[idx // LANES, idx % LANES].set(
        vals, mode="drop", indices_are_sorted=True, unique_indices=True)
    picked = lanes.reshape(-1, chunk // LANES, LANES)[ids]  # (Db, .., 128)
    return lanes, row_fold_call(picked.reshape(-1, chunk),
                                name="dirty_fold", interpret=interpret)


def _bucket_ids(ids: np.ndarray) -> np.ndarray:
    """Pad the dirty-id vector to its pow2 bucket (pad ids point at chunk
    0 — their folds are computed and dropped)."""
    db = _bucket(ids.size)
    out = np.zeros(db, np.int32)
    out[: ids.size] = ids
    return out


def dirty_fold_pallas(words: np.ndarray, chunk_ids: np.ndarray, chunk: int,
                      *, resident: Resident | None = None,
                      touched: np.ndarray | None = None,
                      interpret: bool | None = None) -> np.ndarray:
    """Bring ``resident`` up to date with ``words`` and fold ``chunk_ids``
    on the device.  The whole buffer moves only when the holder has none
    of this size; otherwise only the ``touched`` words (their indices
    and values) and the ids are staged.  Both counts are bucketed to
    powers of two, so the jit cache holds one entry per bucket.
    ``chunk`` must be lane-aligned (% 128 == 0): ``STATE_CHUNK_WORDS``
    is."""
    assert chunk % LANES == 0, "chunk must be lane-aligned"
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
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
                                      interpret=bool(interpret))
    return np.asarray(out, np.uint32)[: ids.size]
