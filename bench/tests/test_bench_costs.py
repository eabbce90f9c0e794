"""Byte counts of the ledger kernels, against hand counts."""
import numpy as np

from harness import costs
from harness.registry import Registry


def test_seal_bytes():
    # 45 txs x 4 u32 words read, 3 batch digests written
    assert costs.seal_bytes(45, 3) == (45 * 4 + 3) * 4


def test_dirty_fold_bytes():
    # 10 chunks x 2048 words in, 10 digests out, 4 bytes each
    assert costs.dirty_fold_bytes(10) == 10 * 2049 * 4


def test_block_pack_bytes():
    # mempool of 1000 -> 11 probes per search; 2 searches x 11 probes x
    # one (hi, lo) pair, plus 6 words of per-block inputs and output
    assert costs.block_pack_bytes(1, 1000) == 2 * 11 * 8 + 24
    assert costs.block_pack_bytes(3, 1000) == 3 * (2 * 11 * 8 + 24)


def test_dirty_chunks_hand_count():
    ref = Registry().module("reference", "ledger")
    n = 4096                              # rows; 2-word fields = 4 chunks
    # row 0: chunk 0 of every field block (6 blocks: 5 two-word, one
    # one-word).  Field offsets in words: 0, 8192, 16384 (rep, 1 word),
    # 20480, 28672, 36864 -> chunks 0, 4, 8, 10, 14, 18.
    assert ref.dirty_chunks(np.array([0]), n) == 6
    # row 1024 starts the second chunk of each 2-word block, and stays in
    # the first chunk of the 1-word block
    assert ref.dirty_chunks(np.array([0, 1024]), n) == 6 + 5
    assert ref.dirty_chunks(np.array([5, 5, 5]), n) == 6
