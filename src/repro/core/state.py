"""Array-native L2 account state + chunked state commitment.

The rollup's L2 state used to be a free-form ``Dict[str, Any]`` digested
with ``json.dumps(..., default=repr)`` — slow, schema-less and
collision-prone (ndarray ``repr`` truncates, so two different large arrays
could share a digest).  This module replaces it with

  * ``canonical_bytes`` — a total, type-tagged byte encoding for the values
    the ledger actually stores (scalars, strings, ndarrays, dataclasses,
    nested containers).  Used by ``rollup.state_digest`` so dict-state
    digests stay available for the object path, now collision-resistant.
  * ``StateArrays`` — a fixed-schema structure-of-arrays account state
    (balances, stake, reputation, task counters) indexed by the ledger's
    integer sender ids.  Handlers are written ONCE against ``StateArrays``
    + a ``TxArrays`` view (see ledger.LedgerBackend); the object path lifts
    single transactions into 1-row views.
  * a chunked Merkle-style commitment: the state's canonical u32 word
    buffer is split into fixed-size chunks, each chunk folded with the
    ledger's xor-mix (``kernels/digest.py``; the kernel factory decides
    whether the NumPy mirror or the chip folds, ``kernels/dirty_fold``),
    and the chunk digest vector is sealed with one sha256.  Chunking is
    independent of the shard count, so the same transactions produce the
    same root no matter how many shards executed them (core/shards.py).

Security note: like every digest in this simulator, the root is a validity
*stand-in* for a zk proof — deterministic and tamper-evident, but not a
cryptographic succinctness/soundness claim (see core/rollup.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro import obs
from repro.kernels.digest import mix
from repro.kernels.factory import get_kernel

# chunk size (u32 words) of the state commitment; lane-aligned for the
# Pallas path (kernels.rollup_digest.rollup_chunk_digests needs % 128 == 0)
STATE_CHUNK_WORDS = 2048


class Registry:
    """Stable name <-> integer-id mapping (append-only, insertion order).

    The generic form of the engine's ``FnRegistry``; also used for account
    namespaces.  Ids are dense and never reused, so they index SoA arrays.
    """

    def __init__(self, names: Sequence[str] = ()):
        self.names: List[str] = []
        self._ids: Dict[str, int] = {}
        for n in names:
            self.id(n)

    def id(self, name: str) -> int:
        i = self._ids.get(name)
        if i is None:
            i = len(self.names)
            self._ids[name] = i
            self.names.append(name)
        return i

    def get(self, name: str) -> Optional[int]:
        return self._ids.get(name)

    def __contains__(self, name: str) -> bool:
        return name in self._ids

    def __len__(self) -> int:
        return len(self.names)


def account_owner(account_ids, n_shards: int) -> np.ndarray:
    """Shard ownership of account ids: xor-mix of the id mod K.

    THE one partition function: core/shards.py routes transactions with it
    and ``StateArrays.partition_root`` commits rows with it, so a sender's
    txs always execute on the shard whose partition root covers its
    account rows.  Deterministic across runs/processes (no ``hash`` salt).
    """
    return (mix(account_ids) % np.uint32(n_shards)).astype(np.int64)


# ---------------------------------------------------------------------------
# canonical byte encoding (satellite of the dict-state digest fix)
# ---------------------------------------------------------------------------
def canonical_bytes(obj: Any) -> bytes:
    """Total, deterministic, type-tagged encoding of a state value.

    Every encoding is prefixed with a one-byte type tag and, where the
    payload is variable-length, a length header — so values of different
    types or shapes can never collide byte-wise.  ndarrays encode dtype,
    shape and the FULL buffer (``repr`` truncates at ~1000 elements, which
    is the collision the old ``json.dumps(..., default=repr)`` fallback
    had); dataclasses encode their field names and values recursively.
    """
    if obj is None:
        return b"N"
    if isinstance(obj, bool):                       # before int (bool is int)
        return b"B1" if obj else b"B0"
    if isinstance(obj, (int, np.integer)):
        b = str(int(obj)).encode()
        return b"I" + len(b).to_bytes(4, "big") + b
    if isinstance(obj, (float, np.floating)):
        # bit pattern, not repr: -0.0 vs 0.0 and precision stay distinct
        return b"F" + np.float64(obj).tobytes()
    if isinstance(obj, str):
        b = obj.encode()
        return b"S" + len(b).to_bytes(4, "big") + b
    if isinstance(obj, (bytes, bytearray)):
        return b"Y" + len(obj).to_bytes(4, "big") + bytes(obj)
    if isinstance(obj, np.ndarray):
        if obj.dtype == object:
            # object arrays hold PyObject POINTERS — tobytes() would be
            # process-random; encode shape + elements recursively instead
            head = str(obj.shape).encode()
            body = b"".join(canonical_bytes(v) for v in obj.ravel())
            return (b"P" + len(head).to_bytes(4, "big") + head
                    + len(body).to_bytes(8, "big") + body)
        a = np.ascontiguousarray(obj)
        head = repr(a.dtype.str).encode() + str(a.shape).encode()
        return (b"A" + len(head).to_bytes(4, "big") + head
                + len(a.tobytes()).to_bytes(8, "big") + a.tobytes())
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        items = [(f.name, getattr(obj, f.name))
                 for f in dataclasses.fields(obj)]
        body = b"".join(canonical_bytes(k) + canonical_bytes(v)
                        for k, v in items)
        name = type(obj).__name__.encode()
        return (b"C" + len(name).to_bytes(4, "big") + name
                + len(body).to_bytes(8, "big") + body)
    if isinstance(obj, dict):
        enc = sorted((canonical_bytes(k), canonical_bytes(v))
                     for k, v in obj.items())
        body = b"".join(k + v for k, v in enc)
        return b"D" + len(body).to_bytes(8, "big") + body
    if isinstance(obj, (list, tuple)):
        body = b"".join(canonical_bytes(v) for v in obj)
        tag = b"L" if isinstance(obj, list) else b"T"
        return tag + len(body).to_bytes(8, "big") + body
    if isinstance(obj, (set, frozenset)):
        body = b"".join(sorted(canonical_bytes(v) for v in obj))
        return b"E" + len(body).to_bytes(8, "big") + body
    # last resort: repr, tagged so it cannot collide with structured forms
    b = repr(obj).encode()
    return b"R" + len(b).to_bytes(4, "big") + b


# ---------------------------------------------------------------------------
# chunked xor-mix commitment
# ---------------------------------------------------------------------------
def _seal_digests(header: bytes, n_words: int, digests: np.ndarray) -> str:
    """One sha256 over the chunk digest vector + schema/length header."""
    h = hashlib.sha256()
    h.update(header)
    h.update(np.uint64(n_words).tobytes())
    h.update(np.ascontiguousarray(digests, np.uint32).tobytes())
    return h.hexdigest()[:32]


def chunked_root(words: np.ndarray, chunk: int = STATE_CHUNK_WORDS,
                 header: bytes = b"") -> str:
    """Two-level commitment: per-chunk xor-mix digests (on the side the
    kernel factory picks), sealed with one sha256 over the chunk digest
    vector + a schema/length header.  Returns a 32-hex root."""
    from repro.kernels.dirty_fold import fold_all
    return _seal_digests(header, len(words), fold_all(words, chunk))


def _refold(words: np.ndarray, touched: np.ndarray, digests: np.ndarray,
            resident, chunk: int) -> None:
    """Refold the chunks covering the ``touched`` word indices of a
    commit cache's patched ``words`` into its ``digests``, in place.  The
    cache's ``resident`` holder and the touched indices go to the
    factory's ``dirty_fold``: the device impl stages those words and not
    the buffer."""
    dirty = np.unique(touched // chunk)
    digests[dirty] = get_kernel("dirty_fold")(words, dirty, chunk,
                                              resident=resident,
                                              touched=touched)


# ---------------------------------------------------------------------------
# fixed-schema SoA account state
# ---------------------------------------------------------------------------
# (name, dtype) in commitment order — the schema IS part of the root header.
STATE_SCHEMA = (
    ("balances", np.float64),         # escrow-visible token balance
    ("stake", np.float64),            # locked collateral
    ("reputation", np.float32),       # R_i (Eq. 9-10), synced at settlement
    ("tasks_published", np.int64),    # publishTask count per account
    ("submissions", np.int64),        # submitLocalModel count per account
    ("rep_events", np.int64),         # calculate*Rep count per account
)


class StateArrays:
    """Fixed-schema SoA account state, indexed by ledger sender ids.

    Rows are accounts; the row index is the owning ledger's integer sender
    id (``LedgerBackend.sender_id``), so state handlers can scatter straight
    from a ``TxArrays`` view without any name lookups.  Arrays grow
    geometrically; only the filled prefix (``n``) is committed.

    Handler contract (see ledger.LedgerBackend.register_state): a handler
    is ``handler(state: StateArrays, txs: TxArrays-view)`` where the view
    holds ONLY the registered function's transactions, in confirmation
    order.  Handlers used under core/shards.py must be per-account
    commutative (counter/accumulator updates), so the merged state is
    independent of how transactions were partitioned across shards.
    """

    def __init__(self, n_accounts: int = 0):
        self.n = 0
        # incremental commitment (opt-in): caches of the committed word
        # buffer + per-chunk digest vector, refreshed by refolding only
        # the chunks covering rows marked dirty since the last seal.
        # OFF by default — engine faces opt in at register_state time, so
        # code that pokes the field arrays directly (tests, notebooks)
        # keeps the always-correct full refold.
        self._track_dirty = False
        self._commit_caches: Dict[Any, Dict[str, Any]] = {}
        cap = max(64, n_accounts)
        for name, dtype in STATE_SCHEMA:
            setattr(self, name, np.zeros(cap, dtype))
        if n_accounts:
            self.ensure(n_accounts)

    @property
    def capacity(self) -> int:
        return self.balances.shape[0]

    def ensure(self, n_accounts: int) -> None:
        """Grow the filled prefix to cover account ids < ``n_accounts``."""
        if n_accounts <= self.n:
            return
        if n_accounts > self.capacity:
            cap = max(n_accounts, 2 * self.capacity)
            for name, dtype in STATE_SCHEMA:
                old = getattr(self, name)
                new = np.zeros(cap, dtype)
                new[: self.n] = old[: self.n]
                setattr(self, name, new)
        # the commitment is field-major over the filled prefix: growing
        # ``n`` shifts every field's word offset, so cached buffers are
        # layout-stale — drop them and let the next root rebuild in full
        self._commit_caches.clear()
        self.n = n_accounts

    # -- dirty-row tracking ----------------------------------------------------
    def enable_dirty_tracking(self) -> None:
        """Opt this state into incremental commitment.  Callers take on
        the contract that EVERY write to the field arrays goes through a
        path that calls ``mark_dirty`` (the default handlers and the
        engine settlement paths do); direct array pokes after enabling
        would leave cached chunk digests stale."""
        self._track_dirty = True

    def mark_dirty(self, ids) -> None:
        """Record account rows whose fields changed since the last root.
        Cheap append; the unique/refold work happens at seal time."""
        if not self._track_dirty or not self._commit_caches:
            return
        ids = np.asarray(ids, np.int64)
        if ids.size:
            for cache in self._commit_caches.values():
                cache["pending"].append(ids)

    def ensure_ids(self, ids: np.ndarray) -> None:
        if len(ids):
            self.ensure(int(np.max(ids)) + 1)

    # -- commitment ------------------------------------------------------------
    def word_buffer(self) -> np.ndarray:
        """Canonical u32 word encoding of the filled prefix, schema order."""
        parts = []
        for name, _ in STATE_SCHEMA:
            a = np.ascontiguousarray(getattr(self, name)[: self.n])
            parts.append(a.view(np.uint8))
        blob = (np.concatenate(parts) if parts else
                np.zeros(0, np.uint8))
        pad = (-blob.size) % 4
        if pad:
            blob = np.concatenate([blob, np.zeros(pad, np.uint8)])
        return blob.view(np.uint32)

    def schema_header(self) -> bytes:
        return ";".join(f"{name}:{np.dtype(dt).str}"
                        for name, dt in STATE_SCHEMA).encode()

    def root(self, chunk: int = STATE_CHUNK_WORDS) -> str:
        """Chunked Merkle-style state root (shard-count independent).

        With dirty tracking enabled the word buffer and per-chunk digest
        vector are cached; only the chunks covering rows touched since the
        last call are refolded (``kernels/dirty_fold``) before the sha256
        seal — O(touched) per window instead of O(state).  On a device
        impl the cache also owns a resident device copy of the words
        (``dirty_fold.Resident``), patched with just the touched words.
        Pinned equal to the full refold by tests/test_state.py."""
        with obs.span("ledger.commit"):
            if not self._track_dirty:
                return chunked_root(self.word_buffer(), chunk,
                                    header=self.schema_header())
            cache = self._commit_caches.get(("flat", chunk))
            if cache is None:
                from repro.kernels.dirty_fold import Resident, fold_all
                words, resident = self.word_buffer(), Resident()
                cache = {"words": words, "resident": resident,
                         "digests": fold_all(words, chunk, resident),
                         "pending": []}
                self._commit_caches[("flat", chunk)] = cache
            elif cache["pending"]:
                rows = np.unique(np.concatenate(cache["pending"]))
                cache["pending"].clear()
                rows = rows[rows < self.n]
                if rows.size:
                    touched = self._patch_rows(cache["words"], self.n,
                                               rows, rows)
                    _refold(cache["words"], touched, cache["digests"],
                            cache["resident"], chunk)
            return _seal_digests(self.schema_header(), cache["words"].size,
                                 cache["digests"])

    def _patch_rows(self, words: np.ndarray, m: int, rows: np.ndarray,
                    pos: np.ndarray) -> np.ndarray:
        """Overwrite the cached word buffer in place with the CURRENT
        field values of ``rows`` and return the touched word indices.

        ``words`` is a field-major encoding of ``m`` rows (``word_buffer``
        for the flat commitment, ``_rows_words`` for a partition);
        ``pos`` is each row's position within that row set.  Every schema
        dtype is 4- or 8-byte, so field blocks are word-aligned and a
        row's slot in field ``f`` is ``off_f + pos * itemsize//4``."""
        touched = []
        off = 0
        for name, dtype in STATE_SCHEMA:
            isw = np.dtype(dtype).itemsize // 4
            vals = np.ascontiguousarray(
                getattr(self, name)[rows]).view(np.uint32)
            idx = off + pos[:, None] * isw + np.arange(isw)
            words[idx] = vals.reshape(-1, isw)
            touched.append(idx.ravel())
            off += m * isw
        return np.concatenate(touched)

    def _rows_words(self, idx: np.ndarray) -> np.ndarray:
        """Canonical u32 words over the selected rows, schema order."""
        parts = []
        for name, _ in STATE_SCHEMA:
            parts.append(np.ascontiguousarray(
                getattr(self, name)[idx]).view(np.uint8))
        blob = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
        pad = (-blob.size) % 4
        if pad:
            blob = np.concatenate([blob, np.zeros(pad, np.uint8)])
        return blob.view(np.uint32)

    def partition_roots(self, n_shards: int,
                        chunk: int = STATE_CHUNK_WORDS) -> List[str]:
        """All K per-shard roots in ONE ``account_owner`` pass.  Ownership
        is the same partition function hash routing uses — the shard that
        sequenced an account's txs is the shard whose root commits it.

        These are the per-shard commitments merged into the fabric root
        (core/shards.py); unlike ``root()`` they depend on the partition.
        With dirty tracking, each shard's word buffer + digest vector is
        cached (with a resident device copy of its own) and only its
        dirty chunks refold.
        """
        with obs.span("ledger.commit"):
            headers = [self.schema_header()
                       + f"|shard={k}/{n_shards}".encode()
                       for k in range(n_shards)]
            if not self._track_dirty:
                owner = account_owner(np.arange(self.n), n_shards)
                return [chunked_root(
                    self._rows_words(np.flatnonzero(owner == k)),
                    chunk, headers[k]) for k in range(n_shards)]
            cache = self._commit_caches.get(("part", n_shards, chunk))
            if cache is None:
                from repro.kernels.dirty_fold import Resident, fold_all
                owner = account_owner(np.arange(self.n), n_shards)
                rows_k = [np.flatnonzero(owner == k)
                          for k in range(n_shards)]
                words_k = [self._rows_words(r) for r in rows_k]
                res_k = [Resident() for _ in range(n_shards)]
                cache = {"rows": rows_k, "words": words_k,
                         "resident": res_k,
                         "digests": [fold_all(w, chunk, r)
                                     for w, r in zip(words_k, res_k)],
                         "pending": []}
                self._commit_caches[("part", n_shards, chunk)] = cache
            elif cache["pending"]:
                rows = np.unique(np.concatenate(cache["pending"]))
                cache["pending"].clear()
                rows = rows[rows < self.n]
                if rows.size:
                    owner = account_owner(rows, n_shards)
                    for k in range(n_shards):
                        rk = rows[owner == k]
                        if not rk.size:
                            continue
                        shard_rows = cache["rows"][k]
                        pos = np.searchsorted(shard_rows, rk)
                        touched = self._patch_rows(cache["words"][k],
                                                   shard_rows.size, rk, pos)
                        _refold(cache["words"][k], touched,
                                cache["digests"][k], cache["resident"][k],
                                chunk)
            return [_seal_digests(headers[k], cache["words"][k].size,
                                  cache["digests"][k])
                    for k in range(n_shards)]

    def partition_root(self, shard: int, n_shards: int,
                       chunk: int = STATE_CHUNK_WORDS) -> str:
        """Single-shard form of ``partition_roots`` — folds ONLY the
        requested shard's rows (the K-root loop the old form paid for one
        answer), unless a tracked cache already amortizes all K."""
        if self._track_dirty and ("part", n_shards,
                                  chunk) in self._commit_caches:
            return self.partition_roots(n_shards, chunk)[shard]
        owner = account_owner(np.arange(self.n), n_shards)
        return chunked_root(
            self._rows_words(np.flatnonzero(owner == shard)), chunk,
            self.schema_header() + f"|shard={shard}/{n_shards}".encode())

    def copy(self) -> "StateArrays":
        out = StateArrays()
        out.ensure(self.n)
        for name, _ in STATE_SCHEMA:
            getattr(out, name)[: self.n] = getattr(self, name)[: self.n]
        return out


# ---------------------------------------------------------------------------
# default protocol state handlers (written once, run on every ledger face)
# ---------------------------------------------------------------------------
def _counter_handler(field: str):
    def handler(state: StateArrays, txs) -> None:
        state.ensure_ids(txs.sender_id)
        np.add.at(getattr(state, field), txs.sender_id, 1)
        state.mark_dirty(txs.sender_id)
    return handler


def default_state_handlers() -> Dict[str, Any]:
    """{fn: handler} for the Table-I protocol functions.

    Pure per-account accumulators — commutative, hence shard-count
    invariant (the core/shards.py handler contract).
    """
    return {
        "publishTask": _counter_handler("tasks_published"),
        "submitLocalModel": _counter_handler("submissions"),
        "calculateObjectiveRep": _counter_handler("rep_events"),
        "calculateSubjectiveRep": _counter_handler("rep_events"),
    }
