"""Array-native L2 state tests: canonical encoding (digest-collision
regression), chunked commitment vs the Pallas chunk kernel, StateArrays
schema/root invariants, and the LedgerBackend state-handler adapters."""
import dataclasses

import numpy as np
import pytest

from repro.core.engine import TxArrays, VectorChain, VectorRollup
from repro.core.ledger import Chain, LedgerBackend, Tx
from repro.core.rollup import Rollup, state_digest
from repro.core.state import (STATE_SCHEMA, StateArrays, canonical_bytes,
                              chunked_root, default_state_handlers)
from repro.kernels.digest import chunk_fold_digests


# -- satellite: canonical byte encoding fixes the repr-truncation collision ----
def test_truncated_repr_collision_regression():
    """Two different 2000-element arrays share a truncated ``repr`` — the
    old ``json.dumps(..., default=repr)`` digest collided; the canonical
    encoding must not."""
    a = np.zeros(2000)
    b = np.zeros(2000)
    b[1000] = 7.0                      # inside the elided "..." region
    assert repr(a) == repr(b)          # the collision the fallback had
    assert canonical_bytes(a) != canonical_bytes(b)
    assert state_digest({"w": a}) != state_digest({"w": b})


def test_state_digest_deterministic_and_key_order_invariant():
    d1 = state_digest({"a": 1, "b": np.arange(5)})
    d2 = state_digest({"b": np.arange(5), "a": 1})
    assert d1 == d2
    assert d1 != state_digest({"a": 1, "b": np.arange(6)})


def test_canonical_bytes_type_tags_prevent_cross_type_collisions():
    pairs = [
        (1, "1"), (1, 1.0), (True, 1), (b"x", "x"),
        ([1, 2], (1, 2)), ({1, 2}, [1, 2]),
        (-0.0, 0.0),
        (np.zeros(4, np.int32), np.zeros(4, np.int64)),
        (np.zeros((2, 2)), np.zeros(4)),
    ]
    for x, y in pairs:
        assert canonical_bytes(x) != canonical_bytes(y), (x, y)


def test_canonical_bytes_dataclass():
    @dataclasses.dataclass
    class Rec:
        x: int
        y: object

    r1 = canonical_bytes(Rec(1, np.arange(3)))
    r2 = canonical_bytes(Rec(1, np.arange(3)))
    assert r1 == r2
    assert r1 != canonical_bytes(Rec(1, np.arange(4)))
    assert r1 != canonical_bytes(Rec(2, np.arange(3)))
    assert state_digest({"r": Rec(1, np.arange(3))}) == \
        state_digest({"r": Rec(1, np.arange(3))})


# -- chunked commitment: NumPy mirror == Pallas chunk kernel -------------------
@pytest.mark.parametrize("n", [1, 128, 2048, 4097, 70000])
def test_chunk_fold_digests_match_pallas_kernel(n):
    import jax.numpy as jnp

    from repro.kernels.rollup_digest import rollup_chunk_digests
    rng = np.random.default_rng(n)
    words = rng.integers(0, 2**32, n, dtype=np.uint32)
    want = np.asarray(rollup_chunk_digests(jnp.asarray(words),
                                           chunk_p=2048, interpret=True))
    got = chunk_fold_digests(words, 2048)
    np.testing.assert_array_equal(got, want)


def test_chunked_root_deterministic_and_tamper_evident():
    rng = np.random.default_rng(3)
    words = rng.integers(0, 2**32, 10_000, dtype=np.uint32)
    r1 = chunked_root(words)
    r2 = chunked_root(words.copy())
    assert r1 == r2
    tampered = words.copy()
    tampered[9_999] ^= 1
    assert chunked_root(tampered) != r1
    # the header participates: same words, different schema -> new root
    assert chunked_root(words, header=b"v2") != r1


# -- StateArrays ----------------------------------------------------------------
def test_state_arrays_roots_and_growth():
    s = StateArrays(4)
    s.balances[:4] = [1.0, 2.0, 3.0, 4.0]
    s.reputation[:4] = 0.5
    r0 = s.root()
    assert s.copy().root() == r0
    # growth beyond capacity preserves rows; new zero rows change the root
    # (the committed length is part of the commitment)
    s.ensure(500)
    assert s.n == 500 and s.balances[1] == 2.0
    assert s.root() != r0
    # per-field tamper evidence across the whole schema
    for name, _ in STATE_SCHEMA:
        t = s.copy()
        getattr(t, name)[137] += 1
        assert t.root() != s.root(), name


def test_state_arrays_partition_roots_cover_disjoint_rows():
    from repro.core.state import account_owner
    s = StateArrays(10)
    s.balances[:10] = np.arange(10)
    parts = [s.partition_root(k, 3) for k in range(3)]
    assert len(set(parts)) == 3
    # only the OWNING shard's partition root moves when a row changes —
    # ownership comes from account_owner, the same function hash routing
    # uses, so executing shard == committing shard
    owner = int(account_owner(np.array([4]), 3)[0])
    s2 = s.copy()
    s2.balances[4] = 99.0
    parts2 = [s2.partition_root(k, 3) for k in range(3)]
    for k in range(3):
        assert (parts2[k] != parts[k]) == (k == owner)


# -- incremental commitment: dirty-chunk refold == full refold -----------------
def test_incremental_root_pinned_to_full_refold():
    """A tracked state's cached root must equal the full refold after every
    window of scattered writes, including writes landing in the padded tail
    chunk and across chunk boundaries."""
    rng = np.random.default_rng(7)
    s = StateArrays(1500)               # ~4 chunks of committed words
    s.enable_dirty_tracking()
    assert s.root() == s.copy().root()  # cache build == untracked full fold
    for _ in range(5):
        ids = rng.integers(0, 1500, 40)
        s.balances[ids] += 1.5
        s.reputation[ids] = rng.random(40, dtype=np.float32)
        s.submissions[ids] += 1
        s.mark_dirty(ids)
        assert s.root() == s.copy().root()
    # untracked rows stay stale-proof: a no-op window reuses the cache
    assert s.root() == s.copy().root()


def test_incremental_partition_roots_pinned_and_growth_invalidates():
    rng = np.random.default_rng(8)
    s = StateArrays(900)
    s.enable_dirty_tracking()
    assert s.partition_roots(3) == s.copy().partition_roots(3)
    ids = rng.integers(0, 900, 25)
    s.stake[ids] = 2.0
    s.mark_dirty(ids)
    assert s.partition_roots(3) == s.copy().partition_roots(3)
    assert s.partition_root(1, 3) == s.copy().partition_roots(3)[1]
    # growing n shifts every field's word offset -> caches must drop
    s.ensure(2000)
    s.balances[1999] = 9.0
    s.mark_dirty(np.array([1999]))
    assert s.root() == s.copy().root()
    assert s.partition_roots(3) == s.copy().partition_roots(3)


def _uploads():
    from repro import obs
    from repro.kernels.dirty_fold import UPLOADS
    return obs.counters().get(UPLOADS, 0)


def _touch(s, rng, n_rows):
    ids = rng.integers(0, s.n, n_rows)
    s.balances[ids] += 1.5
    s.reputation[ids] = rng.random(n_rows, dtype=np.float32)
    s.rep_events[ids] += 1
    s.mark_dirty(ids)


@pytest.fixture
def device_impl(chip_selection):
    """The chip's ``dirty_fold`` (Pallas, interpreted here), counted
    afresh."""
    from repro import obs
    obs.reset()
    yield
    obs.reset()


def test_resident_root_pinned_over_windows_one_upload(device_impl):
    """The flat cache's resident device copy: roots equal the full
    refold after every window (and after an empty one), the device copy
    equals the host buffer, and the buffer is uploaded once in all."""
    rng = np.random.default_rng(21)
    s = StateArrays(3000)
    s.enable_dirty_tracking()
    assert s.root() == s.copy().root()
    for _ in range(5):
        _touch(s, rng, 50)
        assert s.root() == s.copy().root()
    assert s.root() == s.copy().root()          # a window touching nothing
    cache = s._commit_caches[("flat", 2048)]
    host = np.asarray(cache["resident"].lanes).ravel()
    np.testing.assert_array_equal(host[: cache["words"].size],
                                  cache["words"])
    assert _uploads() == 1


def test_resident_cache_rebuilt_after_growth_uploads_once(device_impl):
    rng = np.random.default_rng(22)
    s = StateArrays(1500)
    s.enable_dirty_tracking()
    s.root()
    _touch(s, rng, 30)
    assert s.root() == s.copy().root()
    assert _uploads() == 1
    s.ensure(5000)                  # new layout: the cache and holder drop
    s.stake[4999] = 3.0
    s.mark_dirty(np.array([4999]))
    assert s.root() == s.copy().root()
    for _ in range(3):
        _touch(s, rng, 40)
        assert s.root() == s.copy().root()
    assert _uploads() == 2


def test_resident_partition_roots_four_shards(device_impl):
    """Each of the 4 shard caches keeps its own device copy: one upload
    per shard over every window, roots equal to the full refold."""
    rng = np.random.default_rng(23)
    s = StateArrays(4000)
    s.enable_dirty_tracking()
    assert s.partition_roots(4) == s.copy().partition_roots(4)
    for _ in range(5):
        _touch(s, rng, 60)
        assert s.partition_roots(4) == s.copy().partition_roots(4)
    assert _uploads() == 4


def test_resident_copy_dropped_when_the_mirror_refolds(device_impl,
                                                       monkeypatch):
    """A window refolded by the NumPy mirror patches only the host
    buffer; the next device refold must not fold a stale device copy."""
    rng = np.random.default_rng(24)
    s = StateArrays(2000)
    s.enable_dirty_tracking()
    s.root()
    _touch(s, rng, 30)
    assert s.root() == s.copy().root()
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "numpy")
    _touch(s, rng, 30)
    assert s.root() == s.copy().root()
    monkeypatch.delenv("REPRO_KERNEL_IMPL")
    _touch(s, rng, 30)
    assert s.root() == s.copy().root()
    assert _uploads() == 2


def test_ledger_faces_enable_tracking_and_stay_pinned():
    """Every engine face opts its StateArrays into dirty tracking at
    register_state, and the roots it reports stay equal to an untracked
    full refold of the same rows."""
    for make in (lambda: VectorChain(), lambda: VectorRollup(VectorChain())):
        backend = make()
        for fn, handler in default_state_handlers().items():
            backend.register_state(fn, handler)
        assert backend.state_arrays._track_dirty
        txs = [Tx("submitLocalModel", f"m{i % 5}", {}, 1000, 0.1 * (i + 1))
               for i in range(10)]
        _feed(backend, txs)
        st = backend.state_arrays
        assert backend.state_root() == st.copy().root()


# -- handlers written once, run on all four LedgerBackend faces ----------------
def _feed(backend, txs):
    for t in txs:
        backend.submit(t)
    if isinstance(backend, (Chain, VectorChain)):
        backend.run_until(10.0)
    else:
        backend.flush()


@pytest.mark.parametrize("make", [
    lambda: Chain(), lambda: VectorChain(),
    lambda: Rollup(Chain()), lambda: VectorRollup(VectorChain()),
])
def test_state_handlers_once_for_all_ledger_faces(make):
    backend = make()
    assert isinstance(backend, LedgerBackend)
    for fn, handler in default_state_handlers().items():
        backend.register_state(fn, handler)
    txs = [Tx("submitLocalModel", f"t{i % 3}", {}, 1000, 0.1 * (i + 1))
           for i in range(6)]
    txs += [Tx("publishTask", "tp0", {}, 1000, 0.65)]
    _feed(backend, txs)
    st = backend.state_arrays
    counts = {backend.sender_id(s): c
              for s, c in (("t0", 2), ("t1", 2), ("t2", 2))}
    for sid, c in counts.items():
        assert st.submissions[sid] == c
    assert st.tasks_published[backend.sender_id("tp0")] == 1
    assert backend.state_root() != ""


def test_object_dtype_array_encoding_is_deterministic():
    """Regression: object-dtype tobytes() serializes PyObject pointers —
    two equal arrays encoded differently within one process."""
    a = np.array([{"x": 1}, [1, 2]], dtype=object)
    b = np.array([{"x": 1}, [1, 2]], dtype=object)
    assert canonical_bytes(a) == canonical_bytes(b)
    c = np.array([{"x": 2}, [1, 2]], dtype=object)
    assert canonical_bytes(a) != canonical_bytes(c)
    assert state_digest({"w": a}) == state_digest({"w": b})


def test_submit_arrays_preserves_sender_ids_on_object_faces():
    """Regression: the object-face SoA adapters lowered rows to synthetic
    'client<id>' names, re-minting NEW ids — state handlers then scattered
    to the wrong StateArrays rows."""
    from repro.core.engine import FnRegistry
    for backend in (Chain(), Rollup(Chain())):
        backend.register_state("publishTask",
                               default_state_handlers()["publishTask"])
        alice = backend.sender_id("alice")
        backend.submit(Tx("publishTask", "alice", {}, 1000, 0.1))
        fns = FnRegistry()
        batch = TxArrays(np.array([0.2]), np.array([1000]),
                         np.array([fns.id("publishTask")], np.int32),
                         np.array([alice], np.int32), fns)
        backend.submit_arrays(batch)           # row 0 IS alice, not a mint
        _feed(backend, [])
        st = backend.state_arrays
        assert st.tasks_published[alice] == 2
        assert np.sum(st.tasks_published[: st.n]) == 2
        # round-trip: the lowered name resolves back to the same id
        assert backend.sender_id(backend._sender_name(alice)) == alice


def test_state_root_matches_across_object_and_vector_rollups():
    """The SAME handler code produces the SAME committed state whether it
    ran through 1-row object views or fn-filtered vector views."""
    txs = [Tx("submitLocalModel", f"c{i % 4}", {}, 1000, 0.05 * (i + 1))
           for i in range(12)]
    roots = []
    for make in (lambda: Rollup(Chain()),
                 lambda: VectorRollup(VectorChain())):
        backend = make()
        for fn, handler in default_state_handlers().items():
            backend.register_state(fn, handler)
        _feed(backend, txs)
        roots.append(backend.state_root())
    assert roots[0] == roots[1] != ""
