"""Shard-lane seal kernel (kernels/shard_lanes.py): bit-exact parity.

The ``shard_seal`` op folds K shard lanes' segmented xor digests in one
call — the fused fabric's per-batch tx roots and per-window update
digests.  Pinned here:

  * all three impls (numpy / jax / shard_map) reproduce the per-lane
    ``engine.xor_fold_digest_segments`` reference bit-for-bit, including
    empty lanes (n_words=0 rows) and padded cells (= MIX_SEED);
  * the factory registration (op ``"shard_seal"``) resolves every impl;
  * the mesh seeds: ``launch/mesh.make_shard_mesh`` + the
    ``sharding/specs`` lane axis helpers;
  * on a multi-device host (the CI ``shard-mesh`` job forces 8 CPU
    devices) the shard_map impl runs on a real mesh, including the
    pad-to-mesh-size lane path.
"""
import jax
import numpy as np
import pytest

from repro.core.engine import xor_fold_digest_segments
from repro.kernels.digest import MIX_SEED, shard_seal_np
from repro.kernels.factory import get_kernel
from repro.kernels.shard_lanes import shard_seal_jax, shard_seal_shard_map

IMPLS = {"numpy": shard_seal_np, "jax": shard_seal_jax,
         "shard_map": shard_seal_shard_map}


def _random_lanes(seed: int, k: int, max_words=300, max_seg=12,
                  empty_rows=()):
    """K (words, starts) rows honoring the call contract; rows listed in
    ``empty_rows`` are empty lanes (n_words = n_seg = 0)."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(k):
        if i in empty_rows:
            rows.append((np.zeros(0, np.uint32), np.zeros(0, np.int64)))
            continue
        nw = int(rng.integers(1, max_words))
        ns = int(rng.integers(1, min(max_seg, nw) + 1))
        starts = np.sort(rng.choice(nw, size=ns, replace=False)
                         ).astype(np.int64)
        words = rng.integers(0, 2 ** 32, nw,
                             dtype=np.uint64).astype(np.uint32)
        rows.append((words, starts))
    return rows


def _pack(rows):
    """Stack rows into the padded (K, W)/(K, B) grids of the contract:
    words zero-pad, starts pad with each row's n_words."""
    k = len(rows)
    n_words = np.array([len(w) for w, _ in rows], np.int64)
    n_seg = np.array([len(s) for _, s in rows], np.int64)
    W = max(int(n_words.max()), 1)
    B = max(int(n_seg.max()), 1)
    words = np.zeros((k, W), np.uint32)
    starts = np.repeat(n_words[:, None], B, axis=1)
    for i, (w, s) in enumerate(rows):
        words[i, : len(w)] = w
        starts[i, : len(s)] = s
    return words, starts, n_seg, n_words


def _expected(rows, B):
    """Per-row reference: xor_fold_digest_segments on the live prefix,
    MIX_SEED in every padded (and empty-lane) cell."""
    out = np.full((len(rows), B), MIX_SEED, np.uint32)
    for i, (w, s) in enumerate(rows):
        if len(s):
            out[i, : len(s)] = xor_fold_digest_segments(w, s)
    return out


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("k,seed", [(1, 0), (2, 1), (4, 2), (8, 3)])
def test_shard_seal_matches_reference(impl, k, seed):
    rows = _random_lanes(seed, k)
    words, starts, n_seg, n_words = _pack(rows)
    out = IMPLS[impl](words, starts.copy(), n_seg, n_words)
    np.testing.assert_array_equal(out, _expected(rows, starts.shape[1]))


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_shard_seal_empty_lanes(impl):
    """Empty lanes (n_words=0) fold to rows of MIX_SEED — the value the
    shard_map impl's pad-to-mesh-size rows must also produce."""
    rows = _random_lanes(9, 4, empty_rows=(1, 3))
    words, starts, n_seg, n_words = _pack(rows)
    out = IMPLS[impl](words, starts.copy(), n_seg, n_words)
    exp = _expected(rows, starts.shape[1])
    np.testing.assert_array_equal(out, exp)
    assert (out[1] == MIX_SEED).all() and (out[3] == MIX_SEED).all()


def test_shard_seal_jit_bucketing_stays_exact():
    """The jax/shard_map impls bucket shapes to powers of two for the jit
    cache; results must not depend on the bucket (different sizes hit
    different buckets, all bit-exact)."""
    for seed, k, mw in [(11, 3, 40), (12, 5, 500), (13, 2, 1500)]:
        rows = _random_lanes(seed, k, max_words=mw)
        words, starts, n_seg, n_words = _pack(rows)
        exp = _expected(rows, starts.shape[1])
        np.testing.assert_array_equal(
            shard_seal_jax(words, starts.copy(), n_seg, n_words), exp)
        np.testing.assert_array_equal(
            shard_seal_shard_map(words, starts.copy(), n_seg, n_words), exp)


def test_factory_resolves_every_impl():
    rows = _random_lanes(21, 4)
    words, starts, n_seg, n_words = _pack(rows)
    exp = _expected(rows, starts.shape[1])
    for impl in sorted(IMPLS):
        fn = get_kernel("shard_seal", impl)
        np.testing.assert_array_equal(
            fn(words, starts.copy(), n_seg, n_words), exp)


def test_mesh_seeds():
    from repro.launch.mesh import make_shard_mesh, n_local_devices
    from repro.sharding.specs import (SHARD_LANE_AXIS, shard_lane_sharding,
                                      shard_lane_spec)
    assert n_local_devices() == len(jax.devices()) >= 1
    mesh = make_shard_mesh()
    assert tuple(mesh.shape.keys()) == (SHARD_LANE_AXIS,)
    assert mesh.shape[SHARD_LANE_AXIS] == n_local_devices()
    spec = shard_lane_spec()
    assert spec == jax.sharding.PartitionSpec(SHARD_LANE_AXIS, None)
    sh = shard_lane_sharding(mesh)
    assert sh.spec == spec
    # capped mesh: never more devices than asked for
    assert make_shard_mesh(max_devices=1).shape[SHARD_LANE_AXIS] == 1


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs a multi-device host (CI shard-mesh job "
                           "forces 8 CPU devices via XLA_FLAGS)")
def test_shard_seal_on_real_mesh():
    """On a real multi-device mesh: lane counts that divide, exceed and
    undershoot the device count all stay bit-exact (the pad-to-mesh-size
    empty-lane path included)."""
    from repro.launch.mesh import make_shard_mesh
    d = len(jax.devices())
    for seed, k in [(31, 1), (32, d - 1), (33, d), (34, d + 3), (35, 2 * d)]:
        if k < 1:
            continue
        rows = _random_lanes(seed, k)
        words, starts, n_seg, n_words = _pack(rows)
        out = shard_seal_shard_map(words, starts.copy(), n_seg, n_words,
                                   mesh=make_shard_mesh())
        np.testing.assert_array_equal(out, _expected(rows, starts.shape[1]))
