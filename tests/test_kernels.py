"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles,
the ledger ops' device impls pinned bit-exact to their NumPy mirrors, and
the kernel factory's selection."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm import gmm
from repro.kernels.rollup_digest import rollup_digest

RNG = np.random.default_rng(42)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,S,H,Hkv,dh,dt", [
    (2, 256, 4, 2, 64, jnp.float32),
    (1, 512, 8, 8, 32, jnp.float32),
    (2, 256, 8, 2, 64, jnp.bfloat16),
    (1, 128, 4, 1, 128, jnp.float32),      # MQA
])
def test_flash_attention_sweep(B, S, H, Hkv, dh, dt):
    q = jnp.asarray(RNG.normal(size=(B, S, H, dh)), dt)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, dh)), dt)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, dh)), dt)
    got = flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    want = ops.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dt))


def test_flash_attention_non_causal():
    q = jnp.asarray(RNG.normal(size=(1, 256, 2, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 256, 2, 32)), jnp.float32)
    got = flash_attention(q, k, v, causal=False, block_q=128, block_k=128,
                          interpret=True)
    want = ops.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("E,C,d,f,dt", [
    (8, 96, 64, 200, jnp.float32),
    (4, 128, 128, 512, jnp.bfloat16),
    (1, 8, 32, 64, jnp.float32),
])
def test_gmm_sweep(E, C, d, f, dt):
    xe = jnp.asarray(RNG.normal(size=(E, C, d)), dt)
    w = jnp.asarray(RNG.normal(size=(E, d, f)), dt)
    got = gmm(xe, w, block_c=32, block_f=64, interpret=True)
    want = ops.gmm_ref(xe, w)
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), **_tol(dt))


@pytest.mark.parametrize("P", [128, 10000, 65536])
def test_rollup_digest_sweep(P):
    buf = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    got = rollup_digest(buf, block_p=2048, interpret=True)
    want = ops.rollup_digest_ref(
        jax.lax.bitcast_convert_type(buf, jnp.uint32))
    assert got == want


def test_rollup_digest_detects_tampering():
    buf = jnp.asarray(RNG.normal(size=(4096,)), jnp.float32)
    d0 = rollup_digest(buf, interpret=True)
    d1 = rollup_digest(buf.at[1234].add(1e-6), interpret=True)
    assert d0 != d1


# -- ledger hot-path kernels: device impls pinned BIT-EXACT to the mirrors ----
# (these are integer/bit-pattern kernels — no tolerance, any backend, any
# JAX_ENABLE_X64 setting; CI runs this module on the {x64 on, x64 off}
# matrix with JAX_PLATFORMS=cpu pinned)

def _pack_stream(n_txs, n_blocks, seed, gas_limit):
    """Random mempool + block grid in produce_block's representation."""
    g = np.random.default_rng(seed)
    submit = np.cumsum(g.exponential(0.02, n_txs))
    tmax = np.maximum.accumulate(submit)
    gcum = np.cumsum(g.integers(21_000, 120_000, n_txs).astype(np.int64))
    times = np.cumsum(g.uniform(0.05, 1.5, n_blocks))
    # nondecreasing visibility: txs stage between block edges
    n_vis = np.sort(g.integers(0, n_txs + 1, n_blocks)).astype(np.int64)
    return tmax, gcum, times, n_vis, gas_limit


def _pack_tail(pack, tmax, gcum, times, n_vis, gas_limit, p):
    """Pack from the tail ``[p, n)`` alone, its gas cumsum rebased and
    the stops shifted back by ``p`` (what the fused loop hands the
    packer: only the unconfirmed rows)."""
    base = gcum[p - 1] if p else 0
    return p + pack(tmax[p:], gcum[p:] - base, times,
                    np.maximum(n_vis - p, 0), gas_limit, 0)


@pytest.mark.parametrize("n_txs,n_blocks,seed,gas_limit", [
    (1, 1, 0, 9_000_000),
    (100, 7, 1, 9_000_000),
    (1000, 33, 2, 300_000),            # gas-capped: head-of-line carry
    (513, 16, 3, 2**40),               # limit above any cumsum: time-bound
    (64, 5, 4, 21_000),                # ~one tx per block
    (0, 3, 5, 9_000_000),              # empty mempool
    (2000, 40, 6, 500_000),            # long history, gas-capped
    (300, 2, 7, 9_000_000),            # few blocks over a long mempool
])
def test_block_pack_impls_bit_exact(n_txs, n_blocks, seed, gas_limit):
    from repro.kernels.block_pack import block_pack_jax, block_pack_np
    args = _pack_stream(n_txs, n_blocks, seed, gas_limit)
    want = block_pack_np(*args, 0)
    assert want.dtype == np.int64
    np.testing.assert_array_equal(block_pack_jax(*args, 0), want)
    # nonzero start pointer (mid-run mempool state)
    p0 = int(want[0])
    want_p = block_pack_np(*args, p0)
    np.testing.assert_array_equal(block_pack_jax(*args, p0), want_p)
    # packing the tail [p, n) alone gives the same stops as packing the
    # whole history from p: at the start, after the first and the last
    # block, at the end (empty tail) and at a random point
    g = np.random.default_rng(seed + 100)
    for p in {0, p0, int(want[-1]), n_txs, int(g.integers(0, n_txs + 1))}:
        want_p = block_pack_np(*args, p)
        for pack in (block_pack_np, block_pack_jax):
            np.testing.assert_array_equal(_pack_tail(pack, *args, p),
                                          want_p, err_msg=f"p={p}")


def test_block_pack_matches_stepped_produce_block():
    """The kernel IS produce_block's packing decision, N blocks at once."""
    from repro.core.engine import FnRegistry, TxArrays, VectorChain
    from repro.kernels.block_pack import block_pack_np
    g = np.random.default_rng(11)
    n = 200
    fns = FnRegistry()
    fid = fns.id("bgPing")
    batch = TxArrays(np.cumsum(g.exponential(0.05, n)),
                     g.integers(21_000, 90_000, n).astype(np.int64),
                     np.full(n, fid, np.int32), np.zeros(n, np.int32), fns)
    chain = VectorChain()
    chain.submit_arrays(batch)
    chain.run_until(float(batch.submit_time[-1]) + 2.0)
    stepped = [(b.start, b.stop) for b in chain.blocks[1:]]
    times = np.array([b.time for b in chain.blocks[1:]])
    chain2 = VectorChain()
    chain2.submit_arrays(batch)
    chain2._consolidate()
    stops = block_pack_np(chain2._tmax[:n], chain2._gcum[:n], times,
                          np.full(len(times), n, np.int64),
                          chain2.block_gas_limit, 0)
    starts = np.concatenate([[0], stops[:-1]])
    assert list(zip(starts.tolist(), stops.tolist())) == stepped


@pytest.mark.parametrize("n_words,n_segs,seed", [
    (4, 1, 0),
    (4096, 17, 1),
    (100_000, 257, 2),
    (128, 128, 3),                     # one word per segment
    (40_000, 3, 5),                    # rows longer than one kernel block
])
def test_batch_seal_impls_bit_exact(chip_selection, n_words, n_segs, seed):
    """The chip's batch_seal (Pallas, interpreted here) through the
    factory equals the mirror."""
    from repro.kernels.digest import batch_seal_np
    from repro.kernels.factory import get_kernel, resolve_impl
    assert resolve_impl("batch_seal") == "pallas"
    g = np.random.default_rng(seed)
    words = g.integers(0, 2**32, n_words, dtype=np.uint64).astype(np.uint32)
    cuts = np.sort(g.choice(np.arange(1, n_words), n_segs - 1,
                            replace=False)) if n_segs > 1 else \
        np.empty(0, np.int64)
    starts = np.concatenate([[0], cuts]).astype(np.int64)
    want = batch_seal_np(words, starts)
    assert want.dtype == np.uint32 and want.shape == (n_segs,)
    np.testing.assert_array_equal(get_kernel("batch_seal")(words, starts),
                                  want)


def test_batch_seal_matches_single_digest():
    """One segment == the scalar xor_fold_digest the object path uses."""
    from repro.kernels.digest import batch_seal_np, xor_fold_digest
    g = np.random.default_rng(5)
    words = g.integers(0, 2**32, 777, dtype=np.uint64).astype(np.uint32)
    out = batch_seal_np(words, np.array([0], np.int64))
    assert int(out[0]) == xor_fold_digest(words)


@pytest.mark.parametrize("n_words,n_dirty,seed", [
    (1, 1, 0),
    (100, 1, 1),                       # single sub-chunk buffer
    (5_000, 2, 2),                     # padded tail chunk dirty
    (70_000, 7, 3),
    (300_000, 146, 4),                 # every chunk dirty (dup ids too)
])
def test_dirty_fold_impls_bit_exact(n_words, n_dirty, seed):
    from repro.core.state import STATE_CHUNK_WORDS
    from repro.kernels.digest import chunk_fold_digests, dirty_fold_np
    from repro.kernels.dirty_fold import dirty_fold_pallas
    g = np.random.default_rng(seed)
    words = g.integers(0, 2**32, n_words, dtype=np.uint64).astype(np.uint32)
    n_chunks = -(-n_words // STATE_CHUNK_WORDS)
    ids = g.integers(0, n_chunks, n_dirty)
    # the mirror IS the full fold restricted to the dirty ids
    want = chunk_fold_digests(words, STATE_CHUNK_WORDS)[ids]
    got = dirty_fold_np(words, ids, STATE_CHUNK_WORDS)
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        dirty_fold_pallas(words, ids, STATE_CHUNK_WORDS, interpret=True),
        want)


def test_dirty_fold_empty_ids():
    from repro.core.state import STATE_CHUNK_WORDS
    from repro.kernels.digest import dirty_fold_np
    from repro.kernels.dirty_fold import dirty_fold_pallas
    words = np.arange(4096, dtype=np.uint32)
    none = np.empty(0, np.int64)
    for impl in (dirty_fold_np, dirty_fold_pallas):
        out = impl(words, none, STATE_CHUNK_WORDS)
        assert out.shape == (0,) and out.dtype == np.uint32


def _resident_fold(*a, **k):
    from repro.kernels.dirty_fold import dirty_fold_pallas
    return dirty_fold_pallas(*a, interpret=True, **k)


@pytest.mark.parametrize("n_words,seed", [(5_000, 11), (70_000, 12)])
def test_dirty_fold_resident_windows_match_mirror(n_words, seed):
    """A holder over a sequence of windows of random word patches: each
    window stages only its touched words, the digests equal the mirror's
    at every step, the device copy equals the host buffer at the end, and
    the whole buffer was uploaded once."""
    from repro import obs
    from repro.core.state import STATE_CHUNK_WORDS as C
    from repro.kernels.digest import dirty_fold_np
    from repro.kernels.dirty_fold import UPLOADS, Resident
    g = np.random.default_rng(seed)
    words = g.integers(0, 2**32, n_words, dtype=np.uint64).astype(np.uint32)
    res = Resident()
    obs.reset()
    for _ in range(6):
        touched = np.unique(g.integers(0, n_words, g.integers(1, 300)))
        words[touched] = g.integers(0, 2**32, touched.size,
                                    dtype=np.uint64).astype(np.uint32)
        ids = np.unique(touched // C)
        got = _resident_fold(words, ids, C, resident=res, touched=touched)
        np.testing.assert_array_equal(got, dirty_fold_np(words, ids, C))
    host = np.asarray(res.lanes).ravel()
    np.testing.assert_array_equal(host[:n_words], words)
    assert not host[n_words:].any()            # the padded tail stays zero
    assert obs.counters()[UPLOADS] == 1
    obs.reset()


def test_dirty_fold_resident_window_with_no_touched_words():
    """Nothing touched and nothing dirty: no device work, no upload.
    Dirty ids with nothing touched fold the resident copy as it stands."""
    from repro import obs
    from repro.core.state import STATE_CHUNK_WORDS as C
    from repro.kernels.digest import dirty_fold_np
    from repro.kernels.dirty_fold import UPLOADS, Resident
    words = np.arange(3 * C, dtype=np.uint32)
    none = np.empty(0, np.int64)
    res = Resident()
    obs.reset()
    out = _resident_fold(words, none, C, resident=res, touched=none)
    assert out.shape == (0,) and out.dtype == np.uint32
    assert res.lanes is None and UPLOADS not in obs.counters()
    ids = np.array([0, 2])
    for _ in range(2):
        np.testing.assert_array_equal(
            _resident_fold(words, ids, C, resident=res, touched=none),
            dirty_fold_np(words, ids, C))
    assert obs.counters()[UPLOADS] == 1
    obs.reset()


@pytest.mark.parametrize("n", [0, 1, 7, 513, 4096])
def test_rollup_digest_factory_impls_bit_exact(n):
    """The factory's rollup_digest impls agree bit-for-bit: the chip's
    Pallas kernel (interpreted here) against the NumPy mirror."""
    from repro.kernels import factory
    rng = np.random.default_rng(2024 + n)
    words = rng.integers(0, 2**32, n, dtype=np.uint32)
    want = factory.get_kernel("rollup_digest", "numpy")(words)
    got = factory.get_kernel("rollup_digest", "pallas")(words)
    assert got == want


def test_kernel_factory_selection():
    from repro.kernels import factory
    from repro.kernels.block_pack import block_pack_np
    assert factory.get_kernel("block_pack", "numpy") is block_pack_np
    assert set(factory.available_impls("block_pack")) == {"numpy", "jax"}
    for op in ("batch_seal", "dirty_fold", "rollup_digest"):
        assert set(factory.available_impls(op)) == {"numpy", "pallas"}, op
    assert set(factory.available_impls("shard_seal")) == \
        {"numpy", "jax", "shard_map"}
    with pytest.raises(KeyError, match="unknown kernel op"):
        factory.get_kernel("no_such_op")
    with pytest.raises(KeyError, match="no impl"):
        factory.get_kernel("block_pack", "cuda")
    # env-var override is honored by the default resolution path
    old = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = "numpy"
    try:
        assert factory.get_kernel("batch_seal") is \
            factory.get_kernel("batch_seal", "numpy")
        assert factory.resolve_impl("dirty_fold") == "numpy"
        assert factory.resolve_impl("dirty_fold", "pallas") == "pallas"
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_IMPL"]
        else:
            os.environ["REPRO_KERNEL_IMPL"] = old


#: the factory's whole "auto" table: op -> (cpu choice, tpu choice)
AUTO_TABLE = {
    "block_pack": ("jax", "jax"),
    "batch_seal": ("numpy", "pallas"),
    "rollup_digest": ("numpy", "pallas"),
    "dirty_fold": ("numpy", "pallas"),
    "shard_seal": ("numpy", "jax"),
}


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("op", sorted(AUTO_TABLE))
def test_auto_selection_by_the_probe(monkeypatch, op, backend):
    """With no override, ``on_tpu()`` alone picks each op's impl."""
    from repro.kernels import factory
    monkeypatch.delenv(factory.IMPL_ENV, raising=False)
    monkeypatch.setattr(factory, "_ON_TPU", backend == "tpu")
    assert factory.on_tpu() is (backend == "tpu")
    want = AUTO_TABLE[op][backend == "tpu"]
    assert factory.resolve_impl(op) == want
    assert factory.available_ops() == tuple(sorted(AUTO_TABLE))


def test_kernels_import_nothing_from_the_layers_above():
    """``repro.kernels`` owns the digest format and the kernel choice:
    no module of it imports ``repro.core``, ``api``, ``fl`` or ``serve``
    (at the top or inside a function)."""
    import repro.kernels
    above = ("repro.core", "repro.api", "repro.fl", "repro.serve")
    root = os.path.dirname(repro.kernels.__file__)
    found = []
    for name in sorted(os.listdir(root)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(root, name), encoding="utf-8") as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.module:
                mods = [node.module] + [f"{node.module}.{a.name}"
                                        for a in node.names]
            else:
                continue
            found += [f"{name}:{node.lineno} {m}" for m in mods
                      if any(m == a or m.startswith(a + ".") for a in above)]
    assert found == []
