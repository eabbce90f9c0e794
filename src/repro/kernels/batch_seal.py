"""Batch-seal kernel: per-batch xor-mix digests over a sealed tx stream.

``VectorRollup.seal`` folds the lane-sorted word buffer into one digest
per batch: ``[starts[i], starts[i+1])`` word segments through the
xor-mix of ``kernels/digest.py``.  This module is the device impl of
that fold (kernels/factory.py op ``"batch_seal"``; its NumPy mirror is
``digest.batch_seal_np``): the segments are scattered into a
zero-padded (n_batches, width) tile (zero words mix to zero and fold
away), then one Pallas grid pass folds 8 batch rows per step
(``rollup_digest.row_fold_call``, the layout every ledger fold shares).
Pinned bit-exact against the mirror by tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rollup_digest import LANES, row_fold_call


#: longest row block (u32 words) one batch_seal grid step loads; longer
#: batches tile over the kernel's second grid axis, so fast memory is
#: bounded by this and not by the longest batch
SEAL_BLOCK_W = 8192


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def _seal_pallas_call(tiles, *, block_w: int, interpret: bool):
    return row_fold_call(tiles, name="batch_seal", block_w=block_w,
                         interpret=interpret)


def batch_seal_pallas(words: np.ndarray, starts: np.ndarray, *,
                      interpret: bool | None = None) -> np.ndarray:
    """Pallas impl: scatter segments into a zero-padded row per batch
    (zero words fold away) and fold 8 batch rows per grid step."""
    if interpret is None:
        from repro.kernels.ops import _interpret
        interpret = _interpret()
    w = np.ascontiguousarray(words, np.uint32)
    starts = np.asarray(starts, np.int64)
    nb = len(starts)
    lens = np.diff(np.concatenate([starts, [len(w)]]))
    # rows and row width bucketed to powers of two: one compiled program
    # per bucket, not per batch count or longest batch (extra zero rows
    # are folded and dropped)
    width = _pow2(int(lens.max()), LANES)
    block_w = min(width, SEAL_BLOCK_W)
    tiles = np.zeros((_pow2(nb, 8), width), np.uint32)
    seg = np.repeat(np.arange(nb), lens)
    tiles[seg, np.arange(len(w)) - starts[seg]] = w
    out = _seal_pallas_call(jnp.asarray(tiles), block_w=block_w,
                            interpret=bool(interpret))
    return np.asarray(out)[:nb]


def _pow2(n: int, floor: int) -> int:
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))
