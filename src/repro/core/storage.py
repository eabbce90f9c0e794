"""IPFS-style content-addressed off-chain blob store (paper §III-C.4).

Model weights / task descriptions live off-chain; only their content ids
(hashes) go on the ledger.  Backed by an in-memory dict with an optional
on-disk spill directory.

Two kinds of content, told apart by what ``put`` is handed:

* a pytree whose leaves are all NumPy arrays of plain values (the FL
  submissions): its id is formed the way IPFS forms a file's, from a DAG
  of fixed-size chunks.  Each leaf's C-order bytes are cut into 256 KiB
  chunks (IPFS's default chunker), each chunk is sha256-hashed in place,
  and the id is ``"Qm"`` + sha256 of a header followed by the chunk
  digests in order, leaf by leaf and chunk by chunk.  The header is the
  repr of the tree's skeleton (the tree with each leaf replaced by its
  index) and of each leaf's dtype name, byte order and shape, so the id
  changes with any byte, shape, dtype or key, and with nothing else: not
  the input's strides, nor how many threads hashed it.  A tree of more
  than one chunk hashes its chunks on a shared thread pool (``hashlib``
  releases the GIL on large buffers); a smaller one hashes inline.  The
  store keeps the arrays themselves, marked read-only; spilled, they are
  written raw after the header.
* anything else: pickled, the id a sha256 of the pickle, as before.

``get`` recomputes the id of what it read and asserts it.  Counters
(``repro.obs``): ``blob.tree_puts``, ``blob.pickle_puts`` and
``blob.hashed_bytes`` (leaf or pickle bytes hashed by puts).
"""
from __future__ import annotations

import ast
import hashlib
import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import obs

CHUNK_BYTES = 256 * 1024          # IPFS's default fixed-size chunk
_RAW_MAGIC = b"QmTree1\n"         # spilled array tree; a pickle starts b"\x80"
_ALIGN = 64                       # spilled leaves start on this boundary
_POOL: Optional[ThreadPoolExecutor] = None


def content_id(blob: bytes) -> str:
    return "Qm" + hashlib.sha256(blob).hexdigest()[:44]


def _pool() -> ThreadPoolExecutor:
    global _POOL
    if _POOL is None:
        _POOL = ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1),
                                   thread_name_prefix="blob-sha256")
    return _POOL


def _sha256(chunk: memoryview) -> bytes:
    return hashlib.sha256(chunk).digest()


def _dtype(name: str, order: str) -> np.dtype:
    return np.dtype(name).newbyteorder(order)


def _plain(leaf: Any) -> bool:
    """A NumPy array whose dtype its name and byte order rebuild: numbers,
    bools, times; no objects, strings or records."""
    if type(leaf) is not np.ndarray or leaf.dtype.hasobject:
        return False
    try:
        return _dtype(leaf.dtype.name, leaf.dtype.str[0]) == leaf.dtype
    except TypeError:
        return False


def _array_tree(obj: Any) -> Optional[Tuple[List[np.ndarray], Any, str]]:
    """(C-contiguous leaves, treedef, skeleton) when ``obj`` is a pytree of
    plain NumPy arrays whose skeleton reads back as a Python literal (so
    a spilled copy can rebuild it without unpickling); else None."""
    leaves, treedef = jax.tree.flatten(obj)
    if not leaves or not all(_plain(l) for l in leaves):
        return None
    skeleton = repr(jax.tree.unflatten(treedef, range(len(leaves))))
    try:
        if jax.tree.structure(ast.literal_eval(skeleton)) != treedef:
            return None
    except (ValueError, SyntaxError):
        return None
    leaves = [l if l.flags.c_contiguous else np.ascontiguousarray(l)
              for l in leaves]
    return leaves, treedef, skeleton


def _header(skeleton: str, leaves: List[np.ndarray]) -> bytes:
    metas = [(l.dtype.name, l.dtype.str[0], l.shape) for l in leaves]
    return repr((skeleton, metas)).encode()


def _tree_id(leaves: List[np.ndarray], skeleton: str) -> str:
    """``"Qm"`` + sha256(the header, then every 256 KiB chunk's sha256 in
    leaf order) over C-contiguous ``leaves``; on the thread pool when
    they hold more than one chunk."""
    chunks = []
    for leaf in leaves:
        buf = memoryview(leaf.reshape(-1).view(np.uint8))
        chunks += [buf[i:i + CHUNK_BYTES]
                   for i in range(0, len(buf), CHUNK_BYTES)]
    if sum(l.nbytes for l in leaves) > CHUNK_BYTES:
        digests = list(_pool().map(_sha256, chunks))
    else:
        digests = [_sha256(c) for c in chunks]
    return content_id(_header(skeleton, leaves) + b"".join(digests))


def _pad(n: int) -> int:
    return -n % _ALIGN


def _write_raw(path: str, skeleton: str, leaves: List[np.ndarray]) -> None:
    header = _header(skeleton, leaves)
    with open(path, "wb") as f:
        f.write(_RAW_MAGIC + len(header).to_bytes(8, "little") + header)
        f.write(bytes(_pad(len(_RAW_MAGIC) + 8 + len(header))))
        for leaf in leaves:
            f.write(memoryview(leaf.reshape(-1).view(np.uint8)))
            f.write(bytes(_pad(leaf.nbytes)))


def _read_raw(raw: np.ndarray):
    """(leaves, treedef, skeleton) of a spilled array tree (``raw`` is the
    whole file as uint8); the header is parsed as a literal only."""
    at = len(_RAW_MAGIC) + 8
    header = raw[at:at + int.from_bytes(raw[len(_RAW_MAGIC):at].tobytes(),
                                        "little")].tobytes()
    skeleton, metas = ast.literal_eval(header.decode())
    at += len(header) + _pad(at + len(header))
    leaves = []
    for name, order, shape in metas:
        dtype = _dtype(name, order)
        n = dtype.itemsize * int(np.prod(shape, dtype=np.int64))
        leaves.append(raw[at:at + n].view(dtype).reshape(shape))
        at += n + _pad(n)
    return leaves, jax.tree.structure(ast.literal_eval(skeleton)), skeleton


def _frozen(leaves: List[np.ndarray]) -> List[np.ndarray]:
    for leaf in leaves:
        leaf.flags.writeable = False
    return leaves


class BlobStore:
    def __init__(self, spill_dir: Optional[str] = None):
        # cid -> pickled bytes, or (read-only leaves, treedef, skeleton)
        self._mem: Dict[str, Any] = {}
        self.spill_dir = spill_dir
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)

    def put(self, obj: Any) -> str:
        tree = _array_tree(obj)
        if tree is not None:
            leaves, treedef, skeleton = tree
            cid = _tree_id(leaves, skeleton)
            obs.count("blob.tree_puts")
            obs.count("blob.hashed_bytes", sum(l.nbytes for l in leaves))
            item = (leaves, treedef, skeleton)
        else:
            item = pickle.dumps(obj)
            cid = content_id(item)
            obs.count("blob.pickle_puts")
            obs.count("blob.hashed_bytes", len(item))
        if self.spill_dir:
            path = os.path.join(self.spill_dir, cid)
            if not os.path.exists(path):
                tmp = path + ".tmp"
                if tree is None:
                    with open(tmp, "wb") as f:
                        f.write(item)
                else:
                    _write_raw(tmp, skeleton, leaves)
                os.replace(tmp, path)     # atomic publish
        else:
            if tree is not None:
                _frozen(leaves)           # the content may not change now
            self._mem[cid] = item
        return cid

    def get(self, cid: str) -> Any:
        if self.spill_dir:
            raw = np.fromfile(os.path.join(self.spill_dir, cid), np.uint8)
            item = (_read_raw(raw) if raw[:len(_RAW_MAGIC)].tobytes()
                    == _RAW_MAGIC else raw.tobytes())
        else:
            item = self._mem[cid]
        if isinstance(item, bytes):
            assert content_id(item) == cid, \
                "content hash mismatch (tampering?)"
            return pickle.loads(item)
        leaves, treedef, skeleton = item
        assert _tree_id(leaves, skeleton) == cid, \
            "content hash mismatch (tampering?)"
        return jax.tree.unflatten(treedef, _frozen(leaves))

    def drop(self, cids) -> None:
        """Unpin content ids (missing ones are ignored)."""
        for cid in cids:
            if self.spill_dir:
                path = os.path.join(self.spill_dir, cid)
                if os.path.exists(path):
                    os.remove(path)
            else:
                self._mem.pop(cid, None)

    def has(self, cid: str) -> bool:
        if self.spill_dir:
            return os.path.exists(os.path.join(self.spill_dir, cid))
        return cid in self._mem
