"""The ledger's xor-mix digest format and its NumPy mirrors.

Every ledger digest is one fold: each u32 word is mixed,
``mix(w) = (w ^ (w >> 16)) * MIX_MULT`` (mod 2^32), the mixed words are
xor-folded, and the fold is xored with ``MIX_SEED``.  A batch's tx root,
the merged update digest, a state chunk's digest and a proof aggregate
all take this form over different word ranges.  Zero words mix to zero
and fold away, so zero padding never changes a digest; the device impls
pad freely for that reason.

The mirrors below are the semantics of record.  The kernel factory
registers them as the ``"numpy"`` impl of each digest op, and every
device impl under ``kernels/`` is pinned bit-exact against them
(tests/test_kernels.py, tests/test_state.py, tests/test_shard_lanes.py).
This module imports NumPy only; the device code takes its constants from
here.
"""
from __future__ import annotations

import numpy as np

MIX_MULT = np.uint32(0x85EBCA6B)
MIX_SEED = np.uint32(0x9E3779B9)


def mix(words: np.ndarray) -> np.ndarray:
    """The per-word mix, as u32."""
    w = np.ascontiguousarray(words, dtype=np.uint32)
    return (w ^ (w >> np.uint32(16))) * MIX_MULT


def xor_fold_digest(words: np.ndarray) -> int:
    """One digest over the whole buffer (op ``rollup_digest``); an empty
    buffer folds to the bare seed."""
    if np.size(words) == 0:
        return int(MIX_SEED)
    return int(MIX_SEED ^ np.bitwise_xor.reduce(mix(words)))


def batch_seal_np(words: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """One digest per ``[starts[i], starts[i+1])`` word segment (op
    ``batch_seal``).  Segments must be non-empty (seal batches always
    are)."""
    return MIX_SEED ^ np.bitwise_xor.reduceat(mix(words), starts)


def chunk_fold_digests(words: np.ndarray, chunk: int) -> np.ndarray:
    """Per-chunk digests: (P,) u32 -> (ceil(P/chunk),) u32, the tail
    chunk zero-padded.  An empty buffer has one chunk, the bare seed."""
    w = np.ascontiguousarray(words, dtype=np.uint32)
    if w.size == 0:
        return np.array([MIX_SEED], np.uint32)
    pad = (-w.size) % chunk
    if pad:
        w = np.concatenate([w, np.zeros(pad, np.uint32)])
    return MIX_SEED ^ np.bitwise_xor.reduce(mix(w).reshape(-1, chunk),
                                            axis=1)


def dirty_fold_np(words: np.ndarray, chunk_ids: np.ndarray, chunk: int,
                  *, resident=None, touched=None) -> np.ndarray:
    """Digests of the selected chunks (op ``dirty_fold``): exactly
    ``chunk_fold_digests(words, chunk)[chunk_ids]``, without folding the
    others.

    ``resident`` and ``touched`` are the device impls' arguments (a
    commit cache's device copy of ``words`` and the word indices patched
    since the last fold).  The mirror folds the host buffer, which has
    moved past any device copy, so it drops the holder's copy: the next
    device call uploads afresh."""
    if resident is not None:
        resident.lanes = None
    ids = np.asarray(chunk_ids, np.int64)
    if ids.size == 0:
        return np.zeros(0, np.uint32)
    w = np.ascontiguousarray(words, dtype=np.uint32)
    pad = (-w.size) % chunk
    if pad:
        w = np.concatenate([w, np.zeros(pad, np.uint32)])
    rows = w.reshape(-1, chunk)[ids]
    return MIX_SEED ^ np.bitwise_xor.reduce(mix(rows), axis=1)


def shard_seal_np(words: np.ndarray, starts: np.ndarray,
                  n_seg: np.ndarray, n_words: np.ndarray) -> np.ndarray:
    """``batch_seal_np`` per row of a (K, W) lane grid (op
    ``shard_seal``): row ``k`` folds its ``n_seg[k]`` segments over its
    ``n_words[k]`` live words; padded output cells hold ``MIX_SEED``."""
    words = np.asarray(words, np.uint32)
    starts = np.asarray(starts, np.int64)
    K, B = starts.shape
    out = np.full((K, B), MIX_SEED, np.uint32)
    for k in range(K):
        ns, nw = int(n_seg[k]), int(n_words[k])
        if ns:
            out[k, :ns] = batch_seal_np(words[k, :nw], starts[k, :ns])
    return out
