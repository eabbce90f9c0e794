"""Compile the main path's Pallas kernels for a TPU v5e, with no chip attached.

Interpret mode (tests/test_kernels.py) checks what a kernel computes, not
whether Mosaic accepts it: unaligned blocks, in-kernel ``reduce`` and
oversized blocks only fail in the TPU compiler.  These tests hand the
installed compiler a described ``v5e:2x2`` topology's first chip and the
shapes ``chip_smoke.py`` produces:

  * the ledger kernels at its ledger phase (3000 tx/s x 20 s = ~60k txs
    in 20-tx batches over 100,000 accounts, 11 u32 words per account);
  * the resident ``dirty_fold`` patch-and-fold program at the benchmark
    cell's window (2^20 accounts: 5,632 chunks of 2,048 u32 words held
    as 128-lane rows, 16,384 touched words, 4,096 dirty chunk ids, each
    count's pow2 bucket).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a module that decided at import
whether its tests exist would give pytest-xdist workers different tests.
Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

STATE_WORDS = 544 * 2048          # 100k accounts x 11 words, chunk-padded


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _case(name):
    """(kernel callable, [(shape, dtype), ...]) for one compile case."""
    from repro.kernels.batch_seal import SEAL_BLOCK_W, _seal_pallas_call
    from repro.kernels.dirty_fold import _patch_fold
    from repro.kernels.rollup_digest import (rollup_chunk_digests,
                                             rollup_digest, row_fold_call)
    u32, i32 = jnp.uint32, jnp.int32
    return {
        # merged-buffer digest over the whole state word buffer
        "rollup_digest": (rollup_digest, [((STATE_WORDS,), u32)]),
        # full state commitment fold
        "rollup_chunk_digests": (
            lambda b: rollup_chunk_digests(b, chunk_p=2048),
            [((STATE_WORDS,), u32)]),
        # per-batch tx roots: ~3000 batches of 80 words
        "batch_seal": (
            lambda t: _seal_pallas_call(t, block_w=128, interpret=False),
            [((3016, 128), u32)]),
        # per-window update digests: ~3000 txs x 4 words per window row,
        # longer than one block, so the row tiles over the second grid axis
        "batch_seal_long_rows": (
            lambda t: _seal_pallas_call(t, block_w=SEAL_BLOCK_W,
                                        interpret=False),
            [((24, 2 * SEAL_BLOCK_W), u32)]),
        # dirty chunks of a window at 100k accounts (pow2 id bucket)
        "dirty_fold": (lambda r: row_fold_call(r, name="dirty_fold",
                                               interpret=False),
                       [((1024, 2048), u32)]),
        # the benchmark cell's window: scatter ~11k touched words (pow2
        # bucket) into the resident 2^20-account buffer, fold ~3,700
        # dirty chunks (bucket 4,096)
        "dirty_fold_resident": (
            lambda b, i, v, d: _patch_fold(b, i, v, d, chunk=2048,
                                           interpret=False),
            [((5632 * 16, 128), u32), ((16384,), i32), ((16384,), u32),
             ((4096,), i32)]),
    }[name]


@pytest.mark.parametrize("name", [
    "rollup_digest", "rollup_chunk_digests", "batch_seal",
    "batch_seal_long_rows", "dirty_fold", "dirty_fold_resident",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
