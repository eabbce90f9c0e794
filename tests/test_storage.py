"""The content-addressed blob store (``repro.core.storage``).

A pytree of NumPy arrays is stored as its arrays, read-only, under an id
hashed from its raw buffers in 256 KiB chunks; the id depends on the
content alone (not the hashing pool, not the input's strides) and moves
with any byte, shape, dtype or key.  ``get`` catches tampering in memory
and on disk; every other object still goes through pickle.
"""
import collections
import os
import pickle
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
import pytest

from repro import obs
from repro.core import storage
from repro.core.storage import CHUNK_BYTES, BlobStore


def _tree(seed=0):
    """Nested dict of leaves: one over several chunks (the pool path), a
    small f32, bf16, a 0-d int and an empty leaf."""
    rng = np.random.default_rng(seed)
    return {"fc": {"w": rng.standard_normal((16, 80, 300), np.float32),
                   "b": rng.standard_normal(300).astype(np.float32)},
            "half": rng.standard_normal(7).astype(ml_dtypes.bfloat16),
            "step": np.asarray(3, np.int32),
            "none": np.zeros((0, 4), np.float32)}


def _assert_same(got, want):
    assert got.keys() == want.keys()
    for k in want:
        if isinstance(want[k], dict):
            _assert_same(got[k], want[k])
        else:
            assert got[k].dtype == want[k].dtype
            assert got[k].shape == want[k].shape
            assert got[k].tobytes() == want[k].tobytes()


def _store(tmp_path, spill):
    return BlobStore(str(tmp_path / "blobs") if spill else None)


def test_tree_spans_several_chunks():
    assert _tree()["fc"]["w"].nbytes > 4 * CHUNK_BYTES


@pytest.mark.parametrize("spill", [False, True], ids=["memory", "spill"])
def test_array_tree_round_trip(tmp_path, spill):
    store = _store(tmp_path, spill)
    tree = _tree()
    want = {"fc": {k: v.copy() for k, v in tree["fc"].items()},
            **{k: v.copy() for k, v in tree.items() if k != "fc"}}
    cid = store.put(tree)
    assert cid.startswith("Qm") and len(cid) == 46
    assert store.has(cid)
    _assert_same(store.get(cid), want)
    assert cid == BlobStore().put(_tree())          # both stores: one id
    if spill:
        with open(os.path.join(store.spill_dir, cid), "rb") as f:
            assert f.read(2) != b"\x80\x04"          # raw layout, no pickle
        assert not [n for n in os.listdir(store.spill_dir)
                    if n.endswith(".tmp")]


def test_same_id_at_any_pool_size(monkeypatch):
    default = BlobStore().put(_tree())
    monkeypatch.setattr(storage, "_POOL", ThreadPoolExecutor(1))
    try:
        assert BlobStore().put(_tree()) == default
    finally:
        storage._POOL.shutdown()


def test_same_id_for_any_memory_layout():
    base = np.random.default_rng(1).standard_normal((64, 2 * 2048),
                                                    np.float32)
    strided = base[:, ::2]
    assert not strided.flags.c_contiguous
    ids = {BlobStore().put({"w": a}) for a in
           (strided, np.ascontiguousarray(strided),
            np.asfortranarray(strided))}
    assert len(ids) == 1


def _flip_byte(t):
    w = t["fc"]["w"]
    w.view(np.uint8).reshape(-1)[12345] ^= 1


def _reshape(t):
    t["fc"]["w"] = t["fc"]["w"].reshape(80, 16, 300)


def _to_f16(t):
    t["fc"]["b"] = t["fc"]["b"].astype(np.float16)


def _swap_chunks(t):
    raw = t["fc"]["w"].view(np.uint8).reshape(-1)
    first = raw[:CHUNK_BYTES].copy()
    raw[:CHUNK_BYTES] = raw[CHUNK_BYTES:2 * CHUNK_BYTES]
    raw[CHUNK_BYTES:2 * CHUNK_BYTES] = first


def _rename_key(t):
    t["fc"]["bias"] = t["fc"].pop("b")


@pytest.mark.parametrize("change", [_flip_byte, _reshape, _to_f16,
                                    _swap_chunks, _rename_key],
                         ids=["flipped_byte", "reshaped", "f32_to_f16",
                              "swapped_chunks", "renamed_key"])
def test_any_change_moves_the_id(change):
    tree = _tree()
    change(tree)
    assert BlobStore().put(tree) != BlobStore().put(_tree())


def test_get_catches_a_tampered_array():
    store = BlobStore()
    tree = _tree()
    cid = store.put(tree)
    w = tree["fc"]["w"]                 # the stored array itself
    w.flags.writeable = True
    w[3, 5, 7] += 1.0
    with pytest.raises(AssertionError, match="content hash mismatch"):
        store.get(cid)


def test_get_catches_a_tampered_spilled_file(tmp_path):
    store = _store(tmp_path, True)
    cid = store.put(_tree())
    path = os.path.join(store.spill_dir, cid)
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0x10]))
    with pytest.raises(AssertionError, match="content hash mismatch"):
        store.get(cid)


def test_stored_leaves_are_read_only():
    store = BlobStore()
    tree = _tree()
    cid = store.put(tree)
    assert not tree["fc"]["w"].flags.writeable     # frozen by the put
    got = store.get(cid)
    for leaf in (tree["fc"]["w"], got["fc"]["w"], got["step"]):
        assert not leaf.flags.writeable
        with pytest.raises(ValueError):
            leaf[...] = 0


def test_one_chunk_hashes_inline(monkeypatch):
    class NoPool:
        def map(self, *_):
            raise AssertionError("a one-chunk tree used the pool")
    monkeypatch.setattr(storage, "_POOL", NoPool())
    store = BlobStore()
    cid = store.put({"w": np.ones(CHUNK_BYTES // 4, np.float32)})
    assert store.get(cid)["w"].nbytes == CHUNK_BYTES


Point = collections.namedtuple("Point", "x y")


@pytest.mark.parametrize("obj", [
    {"x": 1}, {"arch": "lenet5"}, {}, ["a", "b"],
    {"w": np.ones(3, np.float32), "tag": "t"},       # not every leaf an array
    {"o": np.array([1, "a"], dtype=object)},         # object array: pointers
    {"s": np.array(["ab", "c"])},                    # strings: no plain dtype
    Point(np.ones(2, np.float32), np.zeros(2, np.float32)),  # no literal
], ids=["int", "str", "empty", "list", "mixed", "object_array",
        "str_array", "namedtuple"])
@pytest.mark.parametrize("spill", [False, True], ids=["memory", "spill"])
def test_other_objects_round_trip_through_pickle(tmp_path, obj, spill):
    store = _store(tmp_path, spill)
    before = obs.counters()
    cid = store.put(obj)
    after = obs.counters()
    assert after.get("blob.pickle_puts", 0) == \
        before.get("blob.pickle_puts", 0) + 1
    assert after.get("blob.tree_puts") == before.get("blob.tree_puts")
    got = store.get(cid)
    assert type(got) is type(obj)
    assert pickle.dumps(got) == pickle.dumps(obj)
