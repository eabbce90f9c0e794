"""Work a kernel call needs, from its shapes: the yardstick for rooflines.

Bytes are the least the algorithm must move through HBM for the call
(its inputs read once, its outputs written once), with no padding,
tiling or re-reads counted: a roofline share built on them is a lower
bound on how close the kernel runs to the chip's bandwidth.
"""
from __future__ import annotations

U32 = 4
CHUNK_WORDS = 2048
TX_WORDS = 4

def seal_bytes(n_tx: int, n_segments: int) -> int:
    """One segmented seal fold (``batch_seal`` or one lane of
    ``shard_seal``): every tx word read, one u32 digest per segment."""
    return (n_tx * TX_WORDS + n_segments) * U32


def dirty_fold_bytes(n_chunks: int, chunk_words: int = CHUNK_WORDS) -> int:
    """Refold of ``n_chunks`` state chunks: their words in, one u32 each
    out."""
    return n_chunks * (chunk_words + 1) * U32


def block_pack_bytes(n_blocks: int, n_mempool: int) -> int:
    """FIFO packing of ``n_blocks`` blocks over an ``n_mempool``-entry
    mempool: per block two binary searches over (hi, lo) u32 key pairs
    (``bit_length + 1`` probes each), the block's time pair, visible
    count and previous gas pair in, its stop index out."""
    probes = max(1, int(n_mempool).bit_length() + 1)
    return n_blocks * (2 * probes * 2 * U32 + 6 * U32)
