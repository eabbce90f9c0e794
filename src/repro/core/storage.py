"""IPFS-style content-addressed off-chain blob store (paper §III-C.4).

Model weights / task descriptions live off-chain; only their content ids
(hashes) go on the ledger.  Backed by an in-memory dict with an optional
on-disk spill directory (used by the checkpointer for model weights).
"""
from __future__ import annotations

import hashlib
import os
import pickle
from typing import Any, Dict, Optional


def content_id(blob: bytes) -> str:
    return "Qm" + hashlib.sha256(blob).hexdigest()[:44]


class BlobStore:
    def __init__(self, spill_dir: Optional[str] = None):
        self._mem: Dict[str, bytes] = {}
        self.spill_dir = spill_dir
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)

    def put(self, obj: Any) -> str:
        blob = pickle.dumps(obj)
        cid = content_id(blob)
        if self.spill_dir:
            path = os.path.join(self.spill_dir, cid)
            if not os.path.exists(path):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)     # atomic publish
        else:
            self._mem[cid] = blob
        return cid

    def get(self, cid: str) -> Any:
        if self.spill_dir:
            with open(os.path.join(self.spill_dir, cid), "rb") as f:
                blob = f.read()
        else:
            blob = self._mem[cid]
        assert content_id(blob) == cid, "content hash mismatch (tampering?)"
        return pickle.loads(blob)

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
